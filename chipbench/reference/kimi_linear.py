"""Kimi-Linear (``kimi_linear``) forward pass, plainly: ``jax.numpy``,
float32, no cache, no kernels; the linear-attention layers as the TOKEN
RECURRENCE under ``lax.scan`` (not the chunked form the program's prefill
uses: the yardstick does not share the program's algebra), the latent
layers expanded under a full causal mask a block of query rows at a time,
one expert (or one slice of the dense MLP's width) at a time.

The yardstick the ``serve_state`` driver compares the program with. Per
layer ``l`` (counted from 1 as ``linear_attn_config`` counts), with ``x`` a
token's hidden state (``moonshotai/Kimi-Linear-48B-A3B-Instruct``,
``config.json``)::

    h = x + Mix_l(RMSNorm(x))          # KDA: l in kda_layers; MLA: the rest
    y = h + MLP(RMSNorm(h))                                  # l = 1 (dense)
    y = h + Shared(n) + 2.446 * sum_{e in top8(n), e held} w_e E_e(n),
        n = RMSNorm(h)                                       # l >= 2
    logits = RMSNorm(y_last) W_head

- KDA (32 heads, ``d_k = d_v = 128``): ``q, k, v = SiLU(conv4(n W_.))``,
  ``conv4`` a causal depthwise convolution, kernel 4, no bias, a plain sum
  of four shifted rows (``y_t = sum_i w_i x_{t-3+i}``, zeros before the
  sequence); per head ``q <- q / |q| * 128^-0.5``, ``k <- k / |k|``; decay
  ``g_t = -exp(A_log_h) * softplus((n W_fa) W_fb + dt_bias)`` a key
  channel, write rate ``beta_t = sigmoid(n W_b)`` a head; state ``S``
  (keys x values, zero at the start): ``S' = Diag(exp(g_t)) S``, ``S <- S'
  + beta_t k_t (v_t - S'^T k_t)^T``, ``o_t = S^T q_t``; then
  ``RMSNorm_128(o_t) * sigmoid((n W_ga) W_gb)`` a head and ``W_o``.
- MLA (32 heads): ``q = n W_q`` -> ``[q_nope 128 | q_pe 64]`` a head (no
  low-rank query); ``[c | k_pe] = n W_kva`` (512 + 64), ``c <-
  RMSNorm_512(c)``; NO rotation of ``q_pe`` or ``k_pe``; ``[k_nope_h |
  v_h] = c W_kvb``; scores ``(q_nope . k_nope + q_pe . k_pe) * 192^-0.5``,
  causal, softmax; ``concat_h(o_h) W_o``.
- Router: ``s = sigmoid(n W_r)`` over all 256; the 8 chosen are the largest
  of ``s + b``; ``w_e = s_e / sum_chosen s`` (without ``b``), times
  ``routed_scaling_factor``; one group, so no group limit. ``E(x) =
  (silu(x W_gate) * (x W_up)) W_down``.

Departures from the published model, all stated in the configuration file
(``assumed`` says which of these the config's keys do not settle): the KDA
parametrisation above (the published ``modeling_kimi.py`` /
flash-linear-attention ``KimiDeltaAttention``: low-rank width 128 for both
gates, ``A_log`` a head, ``dt_bias`` a channel, SiLU after each
convolution, L2-normalised q and k with 1e-6 under the root, a
sigmoid-gated per-head RMSNorm); "NoPE" read as: the 64 ``qk_rope``
dimensions are kept and not rotated; the selection bias present; **the
chip's share**: the sum over chosen experts runs over those in
``cfg["experts_held"]`` (first, count) only, the router and the weights'
normalisation keep all experts, the vocabulary is the rows the parameter
tree holds, the layers are the ones the tree holds.

Every product runs under ``default_matmul_precision("highest")``. It reads
the program's parameter tree as data (``embed/embedding``, ``h{i}/
{attn_norm, attn/{q, k, v, f_a, f_b, g_a, g_b, b, o_norm, o, conv, a_log,
dt_bias} | attn/{q, kv_a, kv_a_norm, kv_b, o}, mlp_norm, mlp/{gate, up,
down} | shared/{gate, up, down} + moe/{router/{w, bias}, w_gate, w_up,
w_down}}``, ``norm``, ``lm_head``; linear layers hold ``w`` [in, out];
``conv`` is ``[4, 3 * 4096]``, the q, k and v filters side by side) and
nothing else of the program. ``cfg`` is the configuration file's own dict.
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256       # query rows of a latent layer scored at a time
WIDTH_BLOCK = 2304      # columns of the dense MLP computed at a time


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms_norm(p, x, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(p["scale"])


def _rounder(dtype):
    """Identity, or a round trip through ``dtype``: what a pass that kept
    that quantity in that precision would lose. The yardstick itself never
    rounds; the option exists to place a limit between two readings
    (PERF.md): ``act_dtype`` rounds the activations (the matmuls' inputs
    and the cached rows), ``state_dtype`` the KDA state after every
    token."""
    if dtype is None:
        return lambda a: a
    if dtype == jnp.bfloat16:
        # not a cast there and back: the TPU compiler may keep the excess
        # precision of such a pair (it did: the control read the float32
        # numbers to the last digit), a reduce-precision op it must honour
        return lambda a: jax.lax.reduce_precision(a, exponent_bits=8,
                                                  mantissa_bits=7)
    return lambda a: a.astype(dtype).astype(jnp.float32)


def _conv4(x, w):
    """``y_t = sum_i w_i x_{t-K+1+i}`` over ``x`` [B, S, C], zeros before
    the sequence: a plain sum of ``K`` shifted copies."""
    k, s = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(padded[:, i:i + s] * _f32(w[i]) for i in range(k))


def _kda(p, x, cfg, rnd, rnd_state, keep_at=None):
    """-> (the layer's output, the state ``[B, H, d_k, d_v]`` as it stood
    after the token at index ``keep_at`` [B]; zeros without one)."""
    x = rnd(x)
    b, s, _ = x.shape
    lin = cfg["linear_attn_config"]
    heads, d = lin["num_heads"], lin["head_dim"]
    proj = rnd(jnp.concatenate([x @ _f32(p[n]["w"]) for n in "qkv"], -1))
    q, k, v = (a.reshape(b, s, heads, d) for a in jnp.split(
        jax.nn.silu(_conv4(proj, p["conv"])), 3, axis=-1))
    q = q / jnp.sqrt((q * q).sum(-1, keepdims=True) + 1e-6) * d ** -0.5
    k = k / jnp.sqrt((k * k).sum(-1, keepdims=True) + 1e-6)
    f = rnd(x @ _f32(p["f_a"]["w"])) @ _f32(p["f_b"]["w"])
    g = -jnp.exp(_f32(p["a_log"]))[:, None] * jax.nn.softplus(
        f + _f32(p["dt_bias"])).reshape(b, s, heads, d)
    beta = jax.nn.sigmoid(x @ _f32(p["b"]["w"]))
    gate = jax.nn.sigmoid(
        rnd(x @ _f32(p["g_a"]["w"])) @ _f32(p["g_b"]["w"])
    ).reshape(b, s, heads, d)

    def token(carry, t):                    # state [B, H, d_k, d_v]
        state, kept = carry
        q_t, k_t, v_t, g_t, b_t, i = t
        decayed = state * jnp.exp(g_t)[..., None]
        seen = jnp.einsum("bhkv,bhk->bhv", decayed, k_t)
        state = rnd_state(decayed + k_t[..., None]
                          * (b_t[..., None] * (v_t - seen))[..., None, :])
        if keep_at is not None:     # a copy beside the scan, not a part of it
            kept = jnp.where((i == keep_at)[:, None, None, None], state, kept)
        return (state, kept), jnp.einsum("bhkv,bhk->bhv", state, q_t)

    by_token = lambda a: jnp.moveaxis(a, 1, 0)      # noqa: E731
    zeros = jnp.zeros((b, heads, d, d), jnp.float32)
    (_, kept), o = jax.lax.scan(
        token, (zeros, zeros if keep_at is not None else jnp.zeros(())),
        (*(by_token(a) for a in (q, k, v, g, beta)), jnp.arange(s)))
    o = _rms_norm(p["o_norm"], jnp.moveaxis(o, 0, 1), cfg["rms_norm_eps"])
    return (rnd((o * gate).reshape(b, s, heads * d)) @ _f32(p["o"]["w"]),
            kept)


def _mla(p, x, cfg, rnd):
    x = rnd(x)
    b, s, _ = x.shape
    heads = cfg["num_attention_heads"]
    n, r, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                cfg["v_head_dim"])
    rank, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    q = (x @ _f32(p["q"]["w"])).reshape(b, s, heads, n + r)
    kv = x @ _f32(p["kv_a"]["w"])
    c_kv = rnd(_rms_norm(p["kv_a_norm"], kv[..., :rank], eps))
    k_pe = rnd(kv[..., rank:])                      # kept, not rotated
    kv = (c_kv @ _f32(p["kv_b"]["w"])).reshape(b, s, heads, n + dv)
    k_nope, v = kv[..., :n], kv[..., n:]
    out = []
    for lo in range(0, s, QUERY_BLOCK):             # a block of query rows
        hi = min(lo + QUERY_BLOCK, s)
        sc = (jnp.einsum("bqhn,bkhn->bhqk", q[:, lo:hi, :, :n], k_nope[:, :hi])
              + jnp.einsum("bqhr,bkr->bhqk", q[:, lo:hi, :, n:], k_pe[:, :hi]))
        causal = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        sc = jnp.where(causal, sc * (n + r) ** -0.5, -jnp.inf)
        out.append(jnp.einsum("bhqk,bkhv->bqhv", jax.nn.softmax(sc, -1),
                              v[:, :hi]))
    o = jnp.concatenate(out, axis=1).reshape(b, s, heads * dv)
    return rnd(o) @ _f32(p["o"]["w"])


def _gated(w_gate, w_up, w_down, x, rnd=lambda a: a):
    return rnd(jax.nn.silu(x @ _f32(w_gate)) * (x @ _f32(w_up))) @ _f32(w_down)


def _dense_mlp(p, x, rnd):
    """The gated MLP a slice of its width at a time (the sum over slices
    of the down projection is the whole): one slice's float32 weights
    live at a time."""
    x = rnd(x)
    width = p["gate"]["w"].shape[1]
    block = WIDTH_BLOCK if width % WIDTH_BLOCK == 0 else width

    def add_slice(i, y):
        cols = lambda w: jax.lax.dynamic_slice_in_dim(w, i * block, block, 1)
        rows = jax.lax.dynamic_slice_in_dim(p["down"]["w"], i * block, block, 0)
        return y + _gated(cols(p["gate"]["w"]), cols(p["up"]["w"]), rows, x, rnd)

    return jax.lax.fori_loop(0, width // block, add_slice, jnp.zeros_like(x))


def _moe(blk, x, cfg, rnd=lambda a: a):
    """-> (E_shared(x) + the held part of the routed sum, margin): margin
    [B, S] is the least change of a selection score ``s + b``, in bf16
    ulps (2**-8) of the last chosen one, that would move a HELD expert
    into or out of the chosen set (+inf where no expert held is near
    either side): how near this token's result lies to a different choice
    of experts."""
    x = rnd(x)
    k = cfg["num_experts_per_token"]
    first, count = cfg["experts_held"]
    router = blk["moe"]["router"]
    s = jax.nn.sigmoid(x @ _f32(router["w"]))
    choose = s + _f32(router["bias"])
    top, ids = jax.lax.top_k(choose, k + 1)
    w = jnp.take_along_axis(s, ids[..., :k], -1)
    if cfg["moe_renormalize"]:
        w = w / w.sum(-1, keepdims=True)
    w = w * cfg["routed_scaling_factor"]
    sh = blk["shared"]
    y = _gated(sh["gate"]["w"], sh["up"]["w"], sh["down"]["w"], x, rnd)
    m = blk["moe"]

    def add_expert(e, y):                       # one expert at a time
        w_e = jnp.where(ids[..., :k] == first + e, w, 0.0).sum(-1)
        return y + w_e[..., None] * _gated(m["w_gate"][e], m["w_up"][e],
                                           m["w_down"][e], x, rnd)

    y = jax.lax.fori_loop(0, count, add_expert, y)
    # How far the choice lies from one that changes this chip's result: a
    # held expert among the chosen must stay above the best one left out,
    # and a held expert left out must stay below the last one chosen.
    held = ((jnp.arange(choose.shape[-1]) >= first)
            & (jnp.arange(choose.shape[-1]) < first + count))
    last_in, first_out = top[..., k - 1:k], top[..., k:k + 1]
    chosen = choose >= last_in
    gap = jnp.minimum(
        jnp.where(held & chosen, choose - first_out, jnp.inf).min(-1),
        jnp.where(held & ~chosen, last_in - choose, jnp.inf).min(-1))
    ulp = 2.0 ** -8 * jnp.maximum(1.0, jnp.abs(top[..., k - 1]))
    return y, gap / ulp


def _layer(blk, x, cfg, is_kda, act_dtype=None, state_dtype=None,
           keep_at=None):
    """-> (the layer's output, the router margin [B, S], a KDA layer's
    state after the token at ``keep_at``: see :func:`_kda`)."""
    rnd = _rounder(act_dtype)
    with jax.default_matmul_precision("highest"):
        eps = cfg["rms_norm_eps"]
        n = _rms_norm(blk["attn_norm"], x, eps)
        mixed, kept = (_kda(blk["attn"], n, cfg, rnd, _rounder(state_dtype),
                            keep_at)
                       if is_kda else (_mla(blk["attn"], n, cfg, rnd), None))
        x = x + mixed
        n = _rms_norm(blk["mlp_norm"], x, eps)
        if "mlp" in blk:                        # the leading dense layer
            return (x + _dense_mlp(blk["mlp"], n, rnd),
                    jnp.full(x.shape[:2], jnp.inf, jnp.float32), kept)
        y, margin = _moe(blk, n, cfg, rnd)
        return x + y, margin, kept


_LAYER_FNS: dict = {}


def _layer_fn(cfg, is_kda, act_dtype=None, state_dtype=None):
    """The jitted layer for ``cfg`` and one kind of mixer (one function
    object each, so a second sequence does not trace it again)."""
    key = json.dumps(cfg, sort_keys=True, default=str) + str(
        (is_kda, act_dtype, state_dtype))
    if key not in _LAYER_FNS:
        _LAYER_FNS[key] = jax.jit(lambda blk, x, keep_at=None: _layer(
            blk, x, cfg, is_kda, act_dtype, state_dtype, keep_at))
    return _LAYER_FNS[key]


def hidden(params, tokens, cfg, act_dtype=None, state_dtype=None,
           state_at=None):
    """-> (final-norm hidden states [B, S, h], router margin [B, S]: the
    least over the layers, the FIRST layer's KDA state [B, H, d_k, d_v]
    after the token at index ``state_at`` [B], or None without one: the
    one state no router stands before, being fed by the embedding). One
    layer is one compiled program, so that at the published widths the
    pass fits beside a loaded model: call this un-jitted."""
    x = _f32(params["embed"]["embedding"][tokens])
    margin = jnp.full(tokens.shape, jnp.inf, jnp.float32)
    kda_layers = cfg["linear_attn_config"]["kda_layers"]    # from 1
    if state_at is not None and 1 not in kda_layers:
        raise ValueError("state_at: the first layer keeps no state")
    layer, state = 0, None
    while f"h{layer}" in params:
        fn = _layer_fn(cfg, layer + 1 in kda_layers, act_dtype, state_dtype)
        if layer == 0 and state_at is not None:
            x, m, state = fn(params["h0"], x, state_at)
        else:
            x, m, _ = fn(params[f"h{layer}"], x)
        margin = jnp.minimum(margin, m)
        layer += 1
    with jax.default_matmul_precision("highest"):
        return _rms_norm(params["norm"], x, cfg["rms_norm_eps"]), margin, state


def logits_at(params, tokens, positions, cfg, with_margins: bool = False,
              act_dtype=None, state_dtype=None, state_at=None):
    """Logits [B, K, V] over the vocabulary held, at ``positions`` [B, K]
    only; with ``with_margins`` also the router margins [B, K] there; with
    ``state_at`` [B] also, last, the first layer's state after the token
    at that index (:func:`hidden`). ``act_dtype`` / ``state_dtype`` (by
    hand only): see :func:`_rounder`."""
    h, margin, state = hidden(params, tokens, cfg, act_dtype, state_dtype,
                              state_at)
    with jax.default_matmul_precision("highest"):
        rows = _rounder(act_dtype)(
            jnp.take_along_axis(h, positions[..., None], axis=1))
        out = (rows @ _f32(params["lm_head"]["w"]),)
        if with_margins:
            out += (jnp.take_along_axis(margin, positions, axis=1),)
        if state_at is not None:
            out += (state,)
        return out if len(out) > 1 else out[0]
