"""Xing4.0 (``xing4_0``) forward pass, plainly: ``jax.numpy``, float32, no
cache, no kernels, no batching; four residual streams a token mixed by
manifold-constrained hyper-connections (mHC, arXiv:2512.24880) round the
DeepSeek-V3 line's sublayers. Attention is expanded under a full causal
mask, a block of heads and a block of query rows at a time; the streams are
mixed a block of tokens at a time, the dense MLP a slice of its width at a
time, the experts one at a time, the head a slice of the vocabulary at a
time: a 14k-token prompt at the published widths fits beside a loaded
model.

The yardstick the ``serve_mhc`` driver compares the program with
(``XingChen-AGI/Xing4.0-29B-A4B``, ``config.json``). A token carries ``X`` in
``R^{n x C}`` (``n = hc_mult``); ``X_0`` is its embedding in every stream.
For each sublayer ``F`` (attention, then the MLP; two a layer), with its own
``phi_pre, phi_post`` in ``R^{nC x n}``, ``phi_res`` in ``R^{nC x n*n}``,
scalars ``a_pre, a_post, a_res`` and biases ``b_pre, b_post`` in ``R^n``,
``b_res`` in ``R^{n x n}``::

    x~     = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)
    H_pre  = sigmoid(a_pre * (x~ phi_pre) + b_pre)
    H_post = 2 sigmoid(a_post * (x~ phi_post) + b_post)
    M      = exp(clamp(a_res * mat(x~ phi_res) + b_res, clamp_min, clamp_max))
    H_res  = hc_sinkhorn_iters times: rows of M over (their sums + hc_eps),
             then columns over (their sums + hc_eps)
    u      = H_pre X;    X <- H_res X + H_post^T F(RMSNorm_C(u))

and ``logits = RMSNorm(sum_i X_i) W_head``.

- Attention (MLA): ``c_q = RMSNorm(x W_qa)``, ``q = c_q W_qb`` -> per head
  ``[q_nope | q_rope]``; ``[c_kv | k_r] = x W_kva``, ``c_kv = RMSNorm(c_kv)``,
  ``k_r = RoPE(k_r)`` shared by all heads; ``[k_nope | v]_h = c_kv W_kvb,h``;
  causal ``softmax((q_nope . k_nope + RoPE(q_rope) . k_r) * scale) v``, then
  ``W_o``. Rotary: yarn frequencies, pairs interleaved; ``scale = 192^-0.5 *
  m^2``, ``m = 0.1 * mscale_all_dim * ln(factor) + 1``.
- MLP: ``(silu(x W_gate) * (x W_up)) W_down`` of width ``intermediate_size``
  on the first ``first_k_dense_replace`` layers; after them ``Shared(x) +
  routed_scaling_factor * sum_{e in top-k} w_e E_e(x)`` with ``s = sigmoid(x
  W_r)``, the ``k`` chosen the largest of ``s + b`` (the selection bias),
  ``w_e = s_e / sum_chosen s``; one group, so no group limit.

What the config's keys do not settle is listed under ``assumed`` in the
configuration file, each with the alternative not run. **The chip's
share**: the sum over chosen experts runs over those in
``cfg["experts_held"]`` (all 64 in the benchmark's configuration), the
vocabulary and the layers are the ones the parameter tree holds.

Every product runs under ``default_matmul_precision("highest")``. It reads
the program's parameter tree as data (``embed/embedding``, ``h{i}/{hc_attn,
hc_mlp: {phi [n*n + 2n, n*C]: row k is column k of [phi_pre | phi_post |
phi_res], alpha [3], b [n*n + 2n]}, attn_norm, attn/{q_a, q_a_norm, q_b,
kv_a, kv_a_norm, kv_b, o}, mlp_norm, mlp/{gate, up, down} | shared/{gate, up,
down} + moe/{router/{w, bias}, w_gate, w_up, w_down}}``, ``norm``,
``lm_head``; linear layers hold ``w`` [in, out]) and nothing else of the
program. ``cfg`` is the configuration file's own dict.
"""

from __future__ import annotations

import json
import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256       # query rows scored at a time
HEAD_BLOCK = 4          # heads expanded and scored at a time
TOKEN_BLOCK = 512       # tokens whose streams are mixed at a time
WIDTH_BLOCK = 2304      # columns of the dense MLP computed at a time
VOCAB_BLOCK = 16384     # columns of the head computed at a time


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms_norm(p, x, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(p["scale"])


def _rounder(dtype):
    """Identity, or a round trip through ``dtype``: what a pass that kept
    its activations (the matmuls' inputs and the cached row) in that
    precision would lose. The yardstick itself never rounds; the option
    exists to place a limit between two readings (PERF.md). ``act_dtype``
    never rounds the streams or the maps, which the configuration states
    in float32; ``stream_dtype`` (a second control) rounds exactly those:
    the streams as a mix reads and writes them, the three maps, ``u``."""
    if dtype is None:
        return lambda a: a
    if dtype == jnp.bfloat16:
        # a reduce-precision op, which the TPU compiler must honour (it
        # may keep the excess precision of a cast there and back)
        return lambda a: jax.lax.reduce_precision(a, exponent_bits=8,
                                                  mantissa_bits=7)
    return lambda a: a.astype(dtype).astype(jnp.float32)


# ------------------------------------------------------ hyper-connections
def sinkhorn(m, iters: int, eps: float):
    """``m`` [..., n, n]: ``iters`` times rows over (row sums + eps), then
    columns over (column sums + eps)."""
    for _ in range(iters):
        m = m / (m.sum(-1, keepdims=True) + eps)
        m = m / (m.sum(-2, keepdims=True) + eps)
    return m


def hyper_maps(p, x, cfg, iters=None, clamp=True):
    """``x`` [B, S, n, C] -> (``H_pre`` [B, S, n], ``H_post`` [B, S, n],
    ``H_res`` [B, S, n, n]). ``iters`` / ``clamp`` (tests only): fewer
    Sinkhorn rounds, or no clamp, to show that either is caught."""
    b, s, n, c = x.shape
    v = x.reshape(b, s, n * c)
    v = v / jnp.sqrt((v * v).mean(-1, keepdims=True) + cfg["rms_norm_eps"])
    phi, bias, alpha = _f32(p["phi"]), _f32(p["b"]), _f32(p["alpha"])
    proj = v @ phi.T                                        # [B, S, n*n + 2n]
    h_pre = jax.nn.sigmoid(alpha[0] * proj[..., :n] + bias[:n])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * proj[..., n:2 * n]
                                  + bias[n:2 * n])
    h = (alpha[2] * proj[..., 2 * n:] + bias[2 * n:]).reshape(b, s, n, n)
    if clamp:
        h = jnp.clip(h, cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"])
    return h_pre, h_post, sinkhorn(
        jnp.exp(h), cfg["hc_sinkhorn_iters"] if iters is None else iters,
        cfg["hc_eps"])


def _hyper(p, x, fn, cfg, srnd=lambda a: a):
    """``X <- H_res X + H_post^T fn(H_pre X)``; ``x`` [B, S, n, C]. The
    maps and both mixes are a token's own, so they run a block of tokens
    at a time (a long prompt's streams are 0.85 GB: one more copy of them
    is all that is alive beside the sublayer's own work); ``fn`` sees the
    whole sequence."""
    b, s, n, c = x.shape
    tb = TOKEN_BLOCK if s % TOKEN_BLOCK == 0 else s
    blocks = lambda a: jnp.moveaxis(                # noqa: E731
        a.reshape((b, s // tb, tb) + a.shape[2:]), 1, 0)
    whole = lambda a: jnp.moveaxis(a, 0, 1).reshape(     # noqa: E731
        (b, s) + a.shape[3:])

    def read(xb):
        xb = srnd(xb)
        h_pre, h_post, h_res = map(srnd, hyper_maps(p, xb, cfg))
        return srnd(jnp.einsum("bsn,bsnc->bsc", h_pre, xb)), h_post, h_res

    u, h_post, h_res = jax.lax.map(read, blocks(x))
    y = fn(whole(u))

    def write(args):
        xb, yb, h_post, h_res = args
        return srnd(jnp.einsum("bsij,bsjc->bsic", h_res, srnd(xb))
                    + h_post[..., None] * yb[:, :, None, :])

    return whole(jax.lax.map(write, (blocks(x), blocks(y), h_post, h_res)))


# --------------------------------------------------------------- attention
def yarn_inv_freq(cfg: dict, dim: int) -> np.ndarray:
    """[dim / 2] frequencies. Pair ``i`` turns ``theta^(-2i/dim)`` radians a
    position; a pair that makes fewer than ``beta_slow`` turns in the
    original context is slowed by ``factor``, one that makes more than
    ``beta_fast`` is kept, and the pairs between are blended linearly."""
    rope = cfg["rope_scaling"]
    theta, factor = float(cfg["rope_theta"]), float(rope["factor"])
    n0 = float(rope["original_max_position_embeddings"])
    turns_at = lambda r: dim * math.log(n0 / (r * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(turns_at(rope["beta_fast"])), 0)
    high = min(math.ceil(turns_at(rope["beta_slow"])), dim - 1)
    out = []
    for i in range(dim // 2):
        f = theta ** (-2.0 * i / dim)
        t = min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
        out.append(f * (1.0 - t) + f / factor * t)
    return np.asarray(out, np.float32)


def _rope(x, positions, inv_freq):
    """``x[..., 2i] + i x[..., 2i+1]`` times ``exp(i pos f_i)``."""
    z = jax.lax.complex(x[..., 0::2], x[..., 1::2]) * jnp.exp(
        1j * (positions[..., None] * inv_freq).astype(jnp.complex64))
    return jnp.stack([z.real, z.imag], -1).reshape(x.shape)


def softmax_scale(cfg: dict) -> float:
    rope = cfg["rope_scaling"]
    m = 0.1 * rope["mscale_all_dim"] * math.log(rope["factor"]) + 1.0 \
        if rope["factor"] > 1 else 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def _attention(p, x, cfg, rnd):
    x = rnd(x)
    b, s, _ = x.shape
    heads = cfg["num_attention_heads"]
    n, r, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                cfg["v_head_dim"])
    rank, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    inv_freq = jnp.asarray(yarn_inv_freq(cfg, r))
    pos = jnp.arange(s, dtype=jnp.float32)
    c_q = rnd(_rms_norm(p["q_a_norm"], x @ _f32(p["q_a"]["w"]), eps))
    kv = x @ _f32(p["kv_a"]["w"])
    c_kv = rnd(_rms_norm(p["kv_a_norm"], kv[..., :rank], eps))
    k_r = rnd(_rope(kv[..., rank:], pos[None, :], inv_freq))
    w_qb = _f32(p["q_b"]["w"]).reshape(-1, heads, n + r)
    w_kvb = _f32(p["kv_b"]["w"]).reshape(rank, heads, n + dv)
    scale = softmax_scale(cfg)
    hb = HEAD_BLOCK if heads % HEAD_BLOCK == 0 else heads
    qb = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    keys = jnp.arange(s)
    out = []
    for h0 in range(0, heads, hb):              # a block of heads
        q = jnp.einsum("bsk,khd->bshd", c_q, w_qb[:, h0:h0 + hb])
        q_nope = q[..., :n]
        q_rope = _rope(q[..., n:], pos[None, :, None], inv_freq)
        kv_h = jnp.einsum("bsk,khd->bshd", c_kv, w_kvb[:, h0:h0 + hb])
        k_nope, v = kv_h[..., :n], kv_h[..., n:]

        def rows(lo):                           # a block of query rows
            cut = lambda a: jax.lax.dynamic_slice_in_dim(a, lo, qb, 1)
            sc = (jnp.einsum("bqhn,bkhn->bhqk", cut(q_nope), k_nope)
                  + jnp.einsum("bqhr,bkr->bhqk", cut(q_rope), k_r))
            causal = keys[None, :] <= (lo + jnp.arange(qb))[:, None]
            sc = jnp.where(causal, sc * scale, -jnp.inf)
            return jnp.einsum("bhqk,bkhv->bqhv", jax.nn.softmax(sc, -1), v)

        o = jax.lax.map(rows, jnp.arange(0, s, qb))     # [blocks, B, qb, hb, dv]
        out.append(jnp.moveaxis(o, 0, 1).reshape(b, s, hb * dv))
    return rnd(jnp.concatenate(out, axis=-1)) @ _f32(p["o"]["w"])


# --------------------------------------------------------------------- MLPs
def _gated(w_gate, w_up, w_down, x, rnd=lambda a: a):
    return rnd(jax.nn.silu(x @ _f32(w_gate)) * (x @ _f32(w_up))) @ _f32(w_down)


def _dense_mlp(p, x, rnd):
    """The gated MLP a slice of its width at a time (the sum over slices
    of the down projection is the whole)."""
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
    into or out of the chosen set: how near this token's result lies to a
    different choice of experts."""
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
    held = ((jnp.arange(choose.shape[-1]) >= first)
            & (jnp.arange(choose.shape[-1]) < first + count))
    last_in, first_out = top[..., k - 1:k], top[..., k:k + 1]
    chosen = choose >= last_in
    gap = jnp.minimum(
        jnp.where(held & chosen, choose - first_out, jnp.inf).min(-1),
        jnp.where(held & ~chosen, last_in - choose, jnp.inf).min(-1))
    ulp = 2.0 ** -8 * jnp.maximum(1.0, jnp.abs(top[..., k - 1]))
    return y, gap / ulp


# ------------------------------------------------------------------- layers
def _layer(blk, x, cfg, act_dtype=None, stream_dtype=None):
    """``x`` [B, S, n, C] -> (the new streams, the router margin [B, S])."""
    rnd, srnd = _rounder(act_dtype), _rounder(stream_dtype)
    eps = cfg["rms_norm_eps"]
    margin = [jnp.full(x.shape[:2], jnp.inf, jnp.float32)]

    def attn(u):
        return _attention(blk["attn"], _rms_norm(blk["attn_norm"], u, eps),
                          cfg, rnd)

    def mlp(u):
        v = _rms_norm(blk["mlp_norm"], u, eps)
        if "mlp" in blk:                        # a leading dense layer
            return _dense_mlp(blk["mlp"], v, rnd)
        y, margin[0] = _moe(blk, v, cfg, rnd)
        return y

    with jax.default_matmul_precision("highest"):
        x = _hyper(blk["hc_attn"], x, attn, cfg, srnd)
        x = _hyper(blk["hc_mlp"], x, mlp, cfg, srnd)
    return x, margin[0]


_LAYER_FNS: dict = {}


def _layer_fn(cfg, act_dtype=None, stream_dtype=None):
    """The jitted layer for ``cfg`` (one function object a configuration;
    jit keys its two kinds of layer by the parameter tree's structure)."""
    key = json.dumps(cfg, sort_keys=True, default=str) + str(
        (act_dtype, stream_dtype))
    if key not in _LAYER_FNS:
        # the streams of a long prompt are 0.85 GB: on the chip a layer
        # writes its result over its input (the CPU cannot donate)
        _LAYER_FNS[key] = jax.jit(
            lambda blk, x: _layer(blk, x, cfg, act_dtype, stream_dtype),
            donate_argnums=(1,) if jax.default_backend() == "tpu" else ())
    return _LAYER_FNS[key]


def hidden(params, tokens, cfg, act_dtype=None, stream_dtype=None):
    """-> (final-norm hidden states [B, S, C], router margin [B, S]: the
    least over the layers). One layer is one compiled program, so that at
    the published widths the pass fits beside a loaded model: call this
    un-jitted."""
    layer_fn = _layer_fn(cfg, act_dtype, stream_dtype)
    e = _f32(params["embed"]["embedding"][tokens])
    x = jnp.stack([e] * cfg["hc_mult"], axis=2)     # every stream starts as e
    margin = jnp.full(tokens.shape, jnp.inf, jnp.float32)
    layer = 0
    while f"h{layer}" in params:
        x, m = layer_fn(params[f"h{layer}"], x)
        margin = jnp.minimum(margin, m)
        layer += 1
    with jax.default_matmul_precision("highest"):
        return _rms_norm(params["norm"], x.sum(axis=2),
                         cfg["rms_norm_eps"]), margin


def logits_at(params, tokens, positions, cfg, with_margins: bool = False,
              act_dtype=None, stream_dtype=None):
    """Logits [B, K, V] over the vocabulary held, at ``positions`` [B, K]
    only; with ``with_margins`` also the router margins [B, K] there.
    ``act_dtype`` / ``stream_dtype`` (by hand only): see :func:`_rounder`."""
    h, margin = hidden(params, tokens, cfg, act_dtype, stream_dtype)
    with jax.default_matmul_precision("highest"):
        rows = _rounder(act_dtype)(
            jnp.take_along_axis(h, positions[..., None], axis=1))
        w = params["lm_head"]["w"]
        block = VOCAB_BLOCK if w.shape[1] % VOCAB_BLOCK == 0 else w.shape[1]
        out = jnp.concatenate(
            [rows @ _f32(w[:, lo:lo + block])
             for lo in range(0, w.shape[1], block)], axis=-1)
        if with_margins:
            return out, jnp.take_along_axis(margin, positions, axis=1)
        return out
