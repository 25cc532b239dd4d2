"""K-EXAONE (``exaone_moe``) forward pass, plainly: ``jax.numpy``, float32,
no cache, no kernels, full causal / windowed masks, a block of query rows
and one expert (or one slice of the dense MLP's width) at a time.

The yardstick the ``serve_hybrid`` driver compares the program with. Per
layer ``l``, with ``x`` a token's hidden state
(``LGAI-EXAONE/K-EXAONE-236B-A23B``, ``config.json``)::

    h = x + Attn_l(RMSNorm(x))
    y = h + MLP(RMSNorm(h))                                  # l = 0 (dense)
    y = h + Shared(n) + 2.5 * sum_{e in top8(n), e held} w_e E_e(n),
        n = RMSNorm(h)                                       # l >= 1
    logits = RMSNorm(y_last) W_head

- Attention: ``q = n W_q`` -> 64 heads of 128, ``k = n W_k``, ``v = n W_v``
  -> 8 heads of 128, no biases; ``q_h``, ``k_g`` RMS-normed over the head
  dimension (a learned 128-vector each), before rotation; a
  ``sliding_attention`` layer rotates ``q, k`` over all 128 dimensions in
  the half-split form (``x * cos + rotate_half(x) * sin``, theta 1e6), a
  ``full_attention`` layer does not rotate; query head ``h`` reads K/V head
  ``h // 8``; scores ``q . k * 128^-0.5``, softmax; key ``j`` visible to
  query ``i`` iff ``j <= i`` and, on a sliding layer, ``i - j < 128``;
  ``concat_h(o_h) W_o``.
- Router: ``s = sigmoid(n W_r)`` over all 128; the 8 chosen are the largest
  of ``s + b``; ``w_e = s_e / sum_chosen s`` (without ``b``), times
  ``routed_scaling_factor``. ``E(x) = (silu(x W_gate) * (x W_up)) W_down``.

Departures from the published model, all stated in the configuration file
(``assumed`` says which of these the config's keys do not settle):
pre-norm residual blocks; QK-norm as RMSNorm over the head dimension
before rotation; the selection bias present; **the chip's share**: the sum
over chosen experts runs over those in ``cfg["experts_held"]`` (first,
count) only, the router and the weights' normalisation keep all experts,
the vocabulary is the rows the parameter tree holds; the layers are the
ones the tree holds (``layer_types[l]`` names layer ``l``); no
multi-token-prediction layer.

Every product runs under ``default_matmul_precision("highest")``. It reads
the program's parameter tree as data (``embed/embedding``, ``h{i}/
{attn_norm, attn/{q, k, v, q_norm, k_norm, o}, mlp_norm, mlp/{gate, up,
down} | shared/{gate, up, down} + moe/{router/{w, bias}, w_gate, w_up,
w_down}}``, ``norm``, ``lm_head``; linear layers hold ``w`` [in, out]) and
nothing else of the program. ``cfg`` is the configuration file's own dict.
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp

# 256 query rows: the global layer's scores for the slot's whole 8,192
# positions (the check's longest prompts) are 0.54 GB a block; at 512 the
# layer's temporaries are 1.95 GB there, at 256 1.43 (compiled for a
# described v5e, PR 30), beside a model that leaves ~3 GB free
QUERY_BLOCK = 256
WIDTH_BLOCK = 2048      # columns of a dense MLP computed at a time


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms_norm(p, x, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(p["scale"])


def _rope(x, positions, theta):
    """``x * cos + rotate_half(x) * sin`` over the whole last dimension:
    pair ``i`` is ``(x[i], x[i + dim/2])``, turned ``pos * theta^(-2i/dim)``
    radians. ``x`` [B, S, heads, dim], ``positions`` [S]."""
    dim = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = positions[None, :, None, None] * inv_freq
    ang = jnp.concatenate([ang, ang], axis=-1)
    half = dim // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * jnp.cos(ang) + rotated * jnp.sin(ang)


def _rounder(act_dtype):
    """Identity, or a round trip through ``act_dtype``: what a pass that
    kept its activations (the matmuls' inputs and the cached rows) in
    that precision would lose. The yardstick itself never rounds; the
    option exists to place a limit between two readings (PERF.md)."""
    if act_dtype is None:
        return lambda a: a
    return lambda a: a.astype(act_dtype).astype(jnp.float32)


def _attention(p, x, cfg, window, rnd):
    x = rnd(x)
    b, s, _ = x.shape
    heads, kvh, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    pos = jnp.arange(s, dtype=jnp.float32)
    q = _rms_norm(p["q_norm"], (x @ _f32(p["q"]["w"])).reshape(b, s, heads, d),
                  eps)
    k = _rms_norm(p["k_norm"], (x @ _f32(p["k"]["w"])).reshape(b, s, kvh, d),
                  eps)
    v = rnd((x @ _f32(p["v"]["w"])).reshape(b, s, kvh, d))
    if window is not None:
        theta = float(cfg["rope_parameters"]["rope_theta"])
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    k = rnd(k)
    q = q.reshape(b, s, kvh, heads // kvh, d)       # head h = (h // 8, h % 8)
    out = []
    for lo in range(0, s, QUERY_BLOCK):             # a block of query rows
        hi = min(lo + QUERY_BLOCK, s)
        k0 = 0 if window is None else max(lo - window + 1, 0)
        sc = jnp.einsum("bqkgd,blkd->bkgql", q[:, lo:hi], k[:, k0:hi])
        i = jnp.arange(lo, hi)[:, None]
        j = jnp.arange(k0, hi)[None, :]
        visible = j <= i
        if window is not None:
            visible &= i - j < window
        sc = jnp.where(visible, sc * d ** -0.5, -jnp.inf)
        out.append(jnp.einsum("bkgql,blkd->bqkgd", jax.nn.softmax(sc, -1),
                              v[:, k0:hi]))
    o = jnp.concatenate(out, axis=1).reshape(b, s, heads * d)
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
    k = cfg["num_experts_per_tok"]
    first, count = cfg["experts_held"]
    router = blk["moe"]["router"]
    s = jax.nn.sigmoid(x @ _f32(router["w"]))
    choose = s + _f32(router["bias"])
    top, ids = jax.lax.top_k(choose, k + 1)
    w = jnp.take_along_axis(s, ids[..., :k], -1)
    if cfg["norm_topk_prob"]:
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


def _layer(blk, x, cfg, window, act_dtype=None):
    rnd = _rounder(act_dtype)
    with jax.default_matmul_precision("highest"):
        eps = cfg["rms_norm_eps"]
        x = x + _attention(blk["attn"], _rms_norm(blk["attn_norm"], x, eps),
                           cfg, window, rnd)
        n = _rms_norm(blk["mlp_norm"], x, eps)
        if "mlp" in blk:                        # a leading dense layer
            return (x + _dense_mlp(blk["mlp"], n, rnd),
                    jnp.full(x.shape[:2], jnp.inf, jnp.float32))
        y, margin = _moe(blk, n, cfg, rnd)
        return x + y, margin


_LAYER_FNS: dict = {}


def _layer_fn(cfg, window, act_dtype=None):
    """The jitted layer for ``cfg`` and one kind of attention (one
    function object each, so a second sequence does not trace it again)."""
    key = json.dumps(cfg, sort_keys=True, default=str) + str(
        (window, act_dtype))
    if key not in _LAYER_FNS:
        _LAYER_FNS[key] = jax.jit(
            lambda blk, x: _layer(blk, x, cfg, window, act_dtype))
    return _LAYER_FNS[key]


def hidden(params, tokens, cfg, act_dtype=None):
    """-> (final-norm hidden states [B, S, h], router margin [B, S]: the
    least over the layers). One layer is one compiled program, so that at
    the published widths the pass fits beside a loaded model: call this
    un-jitted."""
    x = _f32(params["embed"]["embedding"][tokens])
    margin = jnp.full(tokens.shape, jnp.inf, jnp.float32)
    layer = 0
    while f"h{layer}" in params:
        window = (cfg["sliding_window"]
                  if cfg["layer_types"][layer] == "sliding_attention"
                  else None)
        x, m = _layer_fn(cfg, window, act_dtype)(params[f"h{layer}"], x)
        margin = jnp.minimum(margin, m)
        layer += 1
    with jax.default_matmul_precision("highest"):
        return _rms_norm(params["norm"], x, cfg["rms_norm_eps"]), margin


def logits_at(params, tokens, positions, cfg, with_margins: bool = False,
              act_dtype=None):
    """Logits [B, K, V] over the vocabulary held, at ``positions`` [B, K]
    only; with ``with_margins`` also the router margins [B, K] there.
    ``act_dtype`` (by hand only): see :func:`_rounder`."""
    h, margin = hidden(params, tokens, cfg, act_dtype)
    with jax.default_matmul_precision("highest"):
        rows = _rounder(act_dtype)(
            jnp.take_along_axis(h, positions[..., None], axis=1))
        out = rows @ _f32(params["lm_head"]["w"])
        if with_margins:
            return out, jnp.take_along_axis(margin, positions, axis=1)
        return out
