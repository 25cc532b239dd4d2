"""Mistral-Small-4 (``mistral4``) forward pass, plainly: ``jax.numpy``,
float32, no cache, no kernels, expanded attention only, a Python loop over
the experts held.

The yardstick the ``serve_lm`` driver compares the program with. Per layer,
with ``x`` a token's hidden state (``mistralai/Mistral-Small-4-119B-2603``,
``config.json``; the layer equations of the DeepSeek-V3 line, whose keys
the config uses)::

    h = x + Attn(RMSNorm(x));   y = h + MoE(RMSNorm(h))
    logits = RMSNorm(y_last) W_head

- Attention (MLA): ``c_q = RMSNorm(x W_qa)``, ``q = c_q W_qb`` -> per head
  ``[q_nope | q_rope]``; ``[c_kv | k_r] = x W_kva``, ``c_kv = RMSNorm(c_kv)``,
  ``k_r = RoPE(k_r)`` shared by all heads; ``[k_nope | v]_h = c_kv W_kvb,h``;
  causal ``softmax((q_nope . k_nope + RoPE(q_rope) . k_r) * scale) v``, then
  ``W_o``. Computed a block of query rows at a time.
- Rotary: yarn frequencies (:func:`yarn_inv_freq`), pairs interleaved in
  the projection's output; ``scale = d_qk^-0.5 * m^2``, ``m = 0.1 *
  mscale_all_dim * ln(factor) + 1``; the query times ``1 + beta * ln(1 +
  floor(pos / original_max))`` (``llama_4_scaling_beta``).
- Experts: ``g = softmax(x W_r)`` over all routed experts, the
  ``num_experts_per_tok`` largest, weights renormalised over those
  (``norm_topk_prob``), ``MoE(x) = E_shared(x) + sum_e w_e E_e(x)``,
  ``E(x) = (silu(x W_gate) * (x W_up)) W_down``. One expert at a time.

Departures from the published model, both stated in the configuration
file: the text path only (no vision tower); **the chip's share**: the sum
over chosen experts runs over those in ``cfg["experts_held"]`` (first,
count) only, the router and the weights' normalisation keep all experts,
and the vocabulary is the rows the parameter tree holds.

Every product runs under ``default_matmul_precision("highest")``. It reads
the program's parameter tree as data (``embed/embedding``, ``h{i}/
{attn_norm, attn/{q_a, q_a_norm, q_b, kv_a, kv_a_norm, kv_b, o}, mlp_norm,
shared/{gate, up, down}, moe/{router, w_gate, w_up, w_down}}``, ``norm``,
``lm_head``; linear layers hold ``w`` [in, out]) and nothing else of the
program. ``cfg`` is the configuration file's own dict.
"""

from __future__ import annotations

import json
import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms_norm(p, x, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(p["scale"])


def yarn_inv_freq(rope: dict, dim: int) -> np.ndarray:
    """[dim / 2] frequencies. Pair ``i`` turns ``theta^(-2i/dim)`` radians a
    position; a pair that makes fewer than ``beta_slow`` turns in the
    original context is slowed by ``factor``, one that makes more than
    ``beta_fast`` is kept, and the pairs between are blended linearly."""
    theta, factor = float(rope["rope_theta"]), float(rope["factor"])
    n0 = float(rope["original_max_position_embeddings"])
    out = []
    turns_at = lambda r: dim * math.log(n0 / (r * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(turns_at(rope["beta_fast"])), 0)
    high = min(math.ceil(turns_at(rope["beta_slow"])), dim - 1)
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
    rope = cfg["rope_parameters"]
    m = 0.1 * rope["mscale_all_dim"] * math.log(rope["factor"]) + 1.0 \
        if rope["factor"] > 1 else 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def _rounder(act_dtype):
    """Identity, or a round trip through ``act_dtype``: what a pass that
    kept its activations (the matmuls' inputs and the cached row) in
    that precision would lose. The yardstick itself never rounds; the
    option exists to place a limit between two readings (PERF.md)."""
    if act_dtype is None:
        return lambda a: a
    return lambda a: a.astype(act_dtype).astype(jnp.float32)


def _attention(p, x, cfg, rnd):
    x = rnd(x)
    b, s, _ = x.shape
    heads = cfg["num_attention_heads"]
    n, r, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                cfg["v_head_dim"])
    rank, rope = cfg["kv_lora_rank"], cfg["rope_parameters"]
    eps = cfg["rms_norm_eps"]
    inv_freq = jnp.asarray(yarn_inv_freq(rope, r))
    pos = jnp.arange(s, dtype=jnp.float32)
    c_q = rnd(_rms_norm(p["q_a_norm"], x @ _f32(p["q_a"]["w"]), eps))
    q = (c_q @ _f32(p["q_b"]["w"])).reshape(b, s, heads, n + r)
    q = q * (1.0 + rope["llama_4_scaling_beta"] * jnp.log1p(jnp.floor(
        pos / rope["original_max_position_embeddings"])))[None, :, None, None]
    q_nope, q_rope = q[..., :n], _rope(q[..., n:], pos[None, :, None], inv_freq)
    kv = x @ _f32(p["kv_a"]["w"])
    c_kv = rnd(_rms_norm(p["kv_a_norm"], kv[..., :rank], eps))
    k_r = rnd(_rope(kv[..., rank:], pos[None, :], inv_freq))
    kv = (c_kv @ _f32(p["kv_b"]["w"])).reshape(b, s, heads, n + dv)
    k_nope, v = kv[..., :n], kv[..., n:]
    scale = softmax_scale(cfg)
    out = []
    for lo in range(0, s, QUERY_BLOCK):        # a block of query rows
        hi = min(lo + QUERY_BLOCK, s)
        sc = (jnp.einsum("bqhn,bkhn->bhqk", q_nope[:, lo:hi], k_nope[:, :hi])
              + jnp.einsum("bqhr,bkr->bhqk", q_rope[:, lo:hi], k_r[:, :hi]))
        causal = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        sc = jnp.where(causal, sc * scale, -jnp.inf)
        out.append(jnp.einsum("bhqk,bkhv->bqhv", jax.nn.softmax(sc, -1),
                              v[:, :hi]))
    o = jnp.concatenate(out, axis=1).reshape(b, s, heads * dv)
    return rnd(o) @ _f32(p["o"]["w"])


def _gated(w_gate, w_up, w_down, x, rnd=lambda a: a):
    return rnd(jax.nn.silu(x @ _f32(w_gate)) * (x @ _f32(w_up))) @ _f32(w_down)


def _moe(blk, x, cfg, rnd=lambda a: a):
    """-> (E_shared(x) + the held part of the routed sum, margin): margin
    [B, S] is the least router-logit change, in bf16 ulps (2**-8) of the
    last chosen logit, that would move a HELD expert into or out of the
    chosen set (+inf where no expert held is near either side): how near
    this token's result lies to a different choice of experts."""
    x = rnd(x)
    k = cfg["num_experts_per_tok"]
    first, count = cfg["experts_held"]
    router = x @ _f32(blk["moe"]["router"]["w"])
    top, ids = jax.lax.top_k(router, k + 1)
    w = jax.nn.softmax(router, -1)
    w = jnp.take_along_axis(w, ids[..., :k], -1)
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

    # a loop the compiler cannot unroll: one expert's float32 weights
    # (three 4096 x 2048 matrices at the published widths) live at a time
    y = jax.lax.fori_loop(0, count, add_expert, y)
    # How far the choice lies from one that changes this chip's result:
    # a held expert among the chosen must stay above the best one left
    # out, and a held expert left out must stay below the last one chosen
    # (two small gaps in a row can cost a held expert its place without
    # the 4th and 5th experts being held themselves).
    held = ((jnp.arange(router.shape[-1]) >= first)
            & (jnp.arange(router.shape[-1]) < first + count))
    last_in, first_out = top[..., k - 1:k], top[..., k:k + 1]
    chosen = router >= last_in
    gap = jnp.minimum(
        jnp.where(held & chosen, router - first_out, jnp.inf).min(-1),
        jnp.where(held & ~chosen, last_in - router, jnp.inf).min(-1))
    ulp = 2.0 ** -8 * jnp.maximum(1.0, jnp.abs(top[..., k - 1]))
    margin = gap / ulp
    return y, margin


def _layer(blk, x, cfg, act_dtype=None):
    rnd = _rounder(act_dtype)
    with jax.default_matmul_precision("highest"):
        eps = cfg["rms_norm_eps"]
        x = x + _attention(blk["attn"], _rms_norm(blk["attn_norm"], x, eps),
                           cfg, rnd)
        y, margin = _moe(blk, _rms_norm(blk["mlp_norm"], x, eps), cfg, rnd)
        return x + y, margin


_LAYER_FNS: dict = {}


def _layer_fn(cfg, act_dtype=None):
    """The jitted layer for ``cfg`` (one function object a configuration,
    so a second sequence does not trace it again)."""
    key = json.dumps(cfg, sort_keys=True, default=str) + str(act_dtype)
    if key not in _LAYER_FNS:
        _LAYER_FNS[key] = jax.jit(
            lambda blk, x: _layer(blk, x, cfg, act_dtype))
    return _LAYER_FNS[key]


def hidden(params, tokens, cfg, act_dtype=None):
    """-> (final-norm hidden states [B, S, h], router margin [B, S]: the
    least over the layers). One layer is one compiled program (the same
    one for every layer), so that at the published widths the pass fits
    beside a loaded model: call this un-jitted."""
    layer_fn = _layer_fn(cfg, act_dtype)
    x = _f32(params["embed"]["embedding"][tokens])
    margin = jnp.full(tokens.shape, jnp.inf, jnp.float32)
    layer = 0
    while f"h{layer}" in params:
        x, m = layer_fn(params[f"h{layer}"], x)
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
