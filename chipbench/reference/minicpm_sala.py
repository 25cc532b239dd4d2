"""MiniCPM-SALA (``minicpm_sala``) forward pass, plainly: ``jax.numpy``,
float32, no cache, no kernels; the sparse layer's selection made for
EVERY query from the sequence's complete windows, as the rule is written
(an overlap matrix of windows and blocks, a rank count for the top-k, no
``lax.top_k``), then causal softmax attention over a token mask; the
lightning layers as the TOKEN RECURRENCE under ``lax.scan``.

The yardstick the ``serve_sala`` driver compares the program with. Per
layer, with ``x`` a token's hidden state (``openbmb/MiniCPM-SALA``
``config.json``; ``L`` = 32 published layers; MiniCPM4's
``sparse_config`` and MiniMax-01's decay, as the configuration file's
``assumed`` states them)::

    x_0  = E[t] * scale_emb
    n    = RMSNorm(x)
    x    = x + Mixer_l(n) * scale_depth / sqrt(L)
    x    = x + W_down(SiLU(m W_gate) * (m W_up)) * scale_depth / sqrt(L),
           m = RMSNorm(x)
    logits = RMSNorm(x_N) W_head / (hidden_size / dim_model_base)

    minicpm4 (sparse): q = RMSNorm_h(n W_q), k = RMSNorm_h(n W_k), v = n W_v
      (32 query heads over 2 K/V heads of 128, no rotation)
      c_j = mean(k[16 j : 16 j + 32])        windows complete at the query
      s_{g,j} = sum_{h in g} softmax_j(q_h . c_j / sqrt(128))
      S_{g,b} = max over the complete windows j overlapping block b of s_{g,j}
      I_g = block 0 U the blocks of the last 2,048 positions U the 64 best
            other visible blocks by S (ties: the lower block id); every
            visible block while the query's visible length is <= 8,192
      o_h = causal softmax attention over the tokens of I_g
      out = W_o(o * sigmoid(n W_g))
    lightning-attn: q, k = RoPE(RMSNorm_h(n W_q)), RoPE(RMSNorm_h(n W_k))
      (half-split, theta 1e4); v = n W_v; 32 heads of 128
      S_t = g_h S_{t-1} + k_t^T v_t;  o_t = (q_t / sqrt(128)) S_t
      g_h = exp(-slope_h (1 - l / (L - 1) + 1e-5)), slope_h = 2^(-8 (h+1)/32)
      out = W_o(RMSNorm_h(o) * sigmoid(n W_g))

Every product runs under ``default_matmul_precision("highest")``. It
reads the program's parameter tree as data (``embed/embedding``,
``h{i}/{input_layernorm, attn/{q, k, v, q_norm, k_norm, o_gate, o,
o_norm}, post_attention_layernorm, mlp/{gate, up, down}}``, ``norm``,
``lm_head``; linear layers hold ``w`` [in, out]) and nothing else of the
program. ``cfg`` is the configuration file's own dict.
"""

from __future__ import annotations

import json
import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 64        # query rows of the sparse layer at a time
WIDTH_BLOCK = 4096      # columns of the MLP computed at a time


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(scale)


def _rounder(dtype):
    """Identity, or what a pass that kept a quantity in ``dtype`` would
    lose (by hand only, to place a limit between two readings):
    ``act_dtype`` rounds the activations, ``state_dtype`` the lightning
    state after every token."""
    if dtype is None:
        return lambda a: a
    if dtype == jnp.bfloat16:
        return lambda a: jax.lax.reduce_precision(a, exponent_bits=8,
                                                  mantissa_bits=7)
    return lambda a: a.astype(dtype).astype(jnp.float32)


def sparse_sizes(cfg):
    return cfg["assumed_sizes"]["sparse_config"]


def _selected_blocks(s, lengths, z, window_only):
    """``s`` [..., Q, NC] the queries' window scores (``-inf`` where a
    window is not complete at the query), ``lengths`` [Q] their visible
    lengths -> the selected blocks [..., Q, M] bool."""
    nc = s.shape[-1]
    bs, st, ker = z["block_size"], z["kernel_stride"], z["kernel_size"]
    m = nc * st // bs
    j, b = jnp.arange(nc)[:, None], jnp.arange(m)[None, :]
    overlap = (st * j < bs * (b + 1)) & (st * j + ker > bs * b)     # [NC, M]
    score = jnp.where(overlap, s[..., :, None], -jnp.inf).max(-2)   # [..., M]
    blocks = jnp.arange(m)
    length = lengths[:, None]                                        # [Q, 1]
    visible = blocks * bs < length
    forced = visible & ((blocks < z["init_blocks"]) | (
        blocks >= jnp.maximum(length - z["window_size"], 0) // bs))
    cand = visible & ~forced
    sc = jnp.where(cand, score, -jnp.inf)
    a, c = sc[..., :, None], sc[..., None, :]                 # [own, other]
    earlier = blocks[None, :] < blocks[:, None]
    rank = ((c > a) | ((c == a) & earlier)).sum(-1)
    chosen = forced | (cand & (rank < z["topk"]) & (not window_only))
    return jnp.where(length <= z["dense_len"], visible, chosen)


def _sparse(p, n, cfg, rnd, window_only=False):
    """-> (the mixer's output, the compressed keys [B, NC, KVH, D] of the
    windows that start inside the sequence: those that end past it are
    means over zeros past its end, and no query reads them)."""
    n = rnd(n)
    b, s, _ = n.shape
    heads, kvh, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
    eps, z = cfg["rms_norm_eps"], sparse_sizes(cfg)
    st, ker = z["kernel_stride"], z["kernel_size"]
    q = _rms((n @ _f32(p["q"]["w"])).reshape(b, s, heads, d),
             p["q_norm"]["scale"], eps)
    k = rnd(_rms((n @ _f32(p["k"]["w"])).reshape(b, s, kvh, d),
                 p["k_norm"]["scale"], eps))
    v = rnd((n @ _f32(p["v"]["w"])).reshape(b, s, kvh, d))
    nc = -(-s // z["block_size"]) * (z["block_size"] // st)
    kp = jnp.pad(k, ((0, 0), (0, nc * st + ker - s), (0, 0), (0, 0)))
    taps = st * jnp.arange(nc)[:, None] + jnp.arange(ker)[None, :]
    ck = kp[:, taps].mean(2)                               # [B, NC, KVH, D]
    group = heads // kvh
    qb = min(QUERY_BLOCK, s)
    pad = -s % qb
    qg = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
        b, (s + pad) // qb, qb, kvh, group, d)

    def block(args):
        qq, lo = args                                       # [B,Q,KVH,G,D]
        lengths = lo + jnp.arange(qb) + 1
        n_c = jnp.maximum((lengths - ker) // st + 1, 0)
        complete = jnp.arange(nc) < n_c[:, None]            # [Q, NC]
        lg = jnp.einsum("bqghd,bjgd->bgqhj", qq, ck) / math.sqrt(d)
        lg = jnp.where(complete[:, None], lg, -jnp.inf)
        prob = jnp.where(complete[:, None], jax.nn.softmax(lg, -1), 0.0)
        sg = jnp.where(complete, prob.sum(3), -jnp.inf)     # [B,KVH,Q,NC]
        sel = _selected_blocks(sg, lengths, z, window_only)  # [B,KVH,Q,M]
        mask = (jnp.repeat(sel, z["block_size"], -1)[..., :s]
                & (jnp.arange(s) < lengths[:, None]))
        sc = jnp.einsum("bqghd,btgd->bgqht", qq, k) / math.sqrt(d)
        sc = jnp.where(mask[:, :, :, None], sc, -jnp.inf)
        return jnp.einsum("bgqht,btgd->bqghd", jax.nn.softmax(sc, -1), v)

    out = jax.lax.map(block, (jnp.moveaxis(qg, 1, 0),
                              jnp.arange(0, s + pad, qb)))
    o = jnp.moveaxis(out, 0, 1).reshape(b, s + pad, heads * d)[:, :s]
    gate = jax.nn.sigmoid(n @ _f32(p["o_gate"]["w"]))
    return rnd(o * gate) @ _f32(p["o"]["w"]), ck


def _slopes(n):
    def power(m):
        start = 2.0 ** (-(2.0 ** -(math.log2(m) - 3)))
        return [start ** (i + 1) for i in range(m)]
    if math.log2(n).is_integer():
        return power(n)
    near = 2 ** math.floor(math.log2(n))
    return power(near) + power(2 * near)[0::2][:n - near]


def _rotate(x, theta):
    """Half-split rotary over the last axis at positions 0..S-1 (axis 1)."""
    d, s = x.shape[-1], x.shape[1]
    inv = float(theta) ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, c = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - c * sin, c * cos + a * sin], -1)


def decay_of(cfg, layer):
    """A lightning layer's per-head decay ``g_h`` at published index
    ``layer``."""
    big_l = len(cfg["mixer_types"])
    heads = cfg["lightning_nh"]
    return jnp.exp(-jnp.asarray(_slopes(heads), jnp.float32)
                   * (1.0 - layer / (big_l - 1) + 1e-5))


def _lightning(p, n, cfg, decay, rnd, rnd_state, keep_at=None):
    """-> (the mixer's output, the state [B, H, D, D] (key dims, value
    dims) after the token at index ``keep_at`` [B]; zeros without one)."""
    n = rnd(n)
    b, s, _ = n.shape
    heads, d = cfg["lightning_nh"], cfg["lightning_head_dim"]
    eps = cfg["rms_norm_eps"]
    q = _rms((n @ _f32(p["q"]["w"])).reshape(b, s, heads, d),
             p["q_norm"]["scale"], eps)
    k = _rms((n @ _f32(p["k"]["w"])).reshape(b, s, heads, d),
             p["k_norm"]["scale"], eps)
    v = rnd((n @ _f32(p["v"]["w"])).reshape(b, s, heads, d))
    q = _rotate(q, cfg["rope_theta"]) / math.sqrt(d)
    k = rnd(_rotate(k, cfg["rope_theta"]))

    def token(carry, t):
        state, kept = carry
        q_t, k_t, v_t, i = t
        state = rnd_state(state * decay[:, None, None]
                          + k_t[..., :, None] * v_t[..., None, :])
        if keep_at is not None:
            kept = jnp.where((i == keep_at)[:, None, None, None], state, kept)
        return (state, kept), jnp.einsum("bhk,bhkv->bhv", q_t, state)

    by_token = lambda t: jnp.moveaxis(t, 1, 0)      # noqa: E731
    zeros = jnp.zeros((b, heads, d, d), jnp.float32)
    (_, kept), o = jax.lax.scan(
        token, (zeros, zeros if keep_at is not None else jnp.zeros(())),
        (by_token(q), by_token(k), by_token(v), jnp.arange(s)))
    o = _rms(jnp.moveaxis(o, 0, 1), jnp.ones((d,)), eps).reshape(b, s, -1)
    o = o * _f32(p["o_norm"]["scale"])
    gate = jax.nn.sigmoid(n @ _f32(p["o_gate"]["w"]))
    return rnd(o * gate) @ _f32(p["o"]["w"]), kept


def _mlp(p, m, rnd):
    """The gated MLP a slice of its width at a time."""
    m = rnd(m)
    width = p["gate"]["w"].shape[1]
    block = WIDTH_BLOCK if width % WIDTH_BLOCK == 0 else width

    def add_slice(i, y):
        cols = lambda w: _f32(jax.lax.dynamic_slice_in_dim(  # noqa: E731
            w, i * block, block, 1))
        rows = _f32(jax.lax.dynamic_slice_in_dim(p["down"]["w"], i * block,
                                                 block, 0))
        h = jax.nn.silu(m @ cols(p["gate"]["w"])) * (m @ cols(p["up"]["w"]))
        return y + rnd(h) @ rows

    return jax.lax.fori_loop(0, width // block, add_slice, jnp.zeros_like(m))


def _layer(blk, x, decay, cfg, sparse, act_dtype=None, state_dtype=None,
           keep_at=None, window_only=False):
    """-> (the layer's output, what its mixer keeps: the sparse layer's
    compressed keys, a lightning layer's state after ``keep_at``).
    ``decay`` [H]: a lightning layer's per-head decay."""
    rnd = _rounder(act_dtype)
    branch = cfg["scale_depth"] / math.sqrt(len(cfg["mixer_types"]))
    with jax.default_matmul_precision("highest"):
        eps = cfg["rms_norm_eps"]
        n = _rms(x, blk["input_layernorm"]["scale"], eps)
        if sparse:
            a, kept = _sparse(blk["attn"], n, cfg, rnd, window_only)
        else:
            a, kept = _lightning(blk["attn"], n, cfg, decay, rnd,
                                 _rounder(state_dtype), keep_at)
        x = x + a * branch
        m = _rms(x, blk["post_attention_layernorm"]["scale"], eps)
        return x + _mlp(blk["mlp"], m, rnd) * branch, kept


_LAYER_FNS: dict = {}


def _layer_fn(cfg, sparse, act_dtype=None, state_dtype=None,
              window_only=False):
    """The jitted layer of a kind for ``cfg`` (one function object a
    kind, so a second layer or sequence does not trace it again)."""
    key = json.dumps(cfg, sort_keys=True, default=str) + str(
        (sparse, act_dtype, state_dtype, window_only))
    if key not in _LAYER_FNS:
        _LAYER_FNS[key] = jax.jit(lambda blk, x, decay, keep_at=None: _layer(
            blk, x, decay, cfg, sparse, act_dtype, state_dtype, keep_at,
            window_only))
    return _LAYER_FNS[key]


def hidden(params, tokens, cfg, act_dtype=None, state_dtype=None,
           state_at=None, window_only=False):
    """-> (final-norm hidden states [B, S, h], what each layer keeps: the
    sparse layers' compressed keys [B, NC, KVH, D], the lightning layers'
    state [B, H, D, D] after the token at index ``state_at`` [B]). One
    layer is one compiled program, so that at the published widths the
    pass fits beside a loaded model: call this un-jitted.
    ``window_only`` (by hand only): the sparse layers select the init
    block and the window's blocks alone past ``dense_len``."""
    x = _f32(params["embed"]["embedding"][tokens]) * cfg["scale_emb"]
    kept = []
    layer = 0
    while f"h{layer}" in params:
        sparse = cfg["mixer_types"][layer] == "minicpm4"
        fn = _layer_fn(cfg, sparse, act_dtype, state_dtype, window_only)
        x, k = fn(params[f"h{layer}"], x, decay_of(cfg, layer), state_at)
        kept.append(k)
        layer += 1
    with jax.default_matmul_precision("highest"):
        return _rms(x, params["norm"]["scale"], cfg["rms_norm_eps"]), kept


def logits_at(params, tokens, positions, cfg, act_dtype=None,
              state_dtype=None, state_at=None, window_only=False):
    """Logits [B, K, V] at ``positions`` [B, K] only; with ``state_at``
    [B] also what each layer keeps (:func:`hidden`)."""
    h, kept = hidden(params, tokens, cfg, act_dtype, state_dtype, state_at,
                     window_only)
    with jax.default_matmul_precision("highest"):
        rows = _rounder(act_dtype)(
            jnp.take_along_axis(h, positions[..., None], axis=1))
        logits = rows / (cfg["hidden_size"] / cfg["dim_model_base"]) @ _f32(
            params["lm_head"]["w"])
        return logits if state_at is None else (logits, kept)
