"""Kimi-Linear (``model_type: kimi_linear``): linear-attention layers with
a recurrent state (KDA, Kimi Delta Attention) beside latent-attention
layers without positions (NoPE MLA), 3 to 1; a leading dense layer and
then sigmoid-routed dropless experts with a shared expert.

Per layer ``l``, with ``x`` a token's hidden state (the residual stream is
float32, as in ``mistral4.py``)::

    h = x + Mix_l(RMSNorm(x))              # KDA or MLA by linear_attn_config
    y = h + MLP(RMSNorm(h))                          # l < first_k_dense_replace
    y = h + Shared(n) + sum_{e in top-k, held} w_e E_e(n),  n = RMSNorm(h)

**KDA layer** (``H`` heads, ``d_k = d_v = head_dim``; ``n = RMSNorm(x)``)::

    q, k, v = SiLU(conv4(n W_q)), SiLU(conv4(n W_k)), SiLU(conv4(n W_v))
    q <- q / |q| * d_k^-0.5,  k <- k / |k|                    # per head
    g = -exp(A_log_h) * softplus((n W_fa) W_fb + dt_bias)     # [H, d_k] <= 0
    beta = sigmoid(n W_b)                                     # [H]
    S' = Diag(exp(g)) S;  S <- S' + beta k (v - S'^T k)^T;  o = S^T q
    out = concat_h(RMSNorm_dv(o_h) * sigmoid((n W_ga) W_gb)_h) W_o

``conv4`` is a causal depthwise convolution over the sequence (kernel
``short_conv_kernel_size``, one filter a channel, no bias). What a KDA
layer caches is per SEQUENCE, not per token: the state ``S`` (``[H, d_k,
d_v]`` float32) and the convolutions' tails (the last ``kernel - 1``
inputs of the three convolutions, ``[kernel - 1, 3*H*d_k]``, stored as
rows of 128 lanes). Both live
in the pool's STATE group (``serve/slots.py``: one entry a slot). Decode
is ``ops.pallas.kda.kda_conv_step`` (the convolution's one output and the
shifted tail) and then ``kda_decode`` (one pass over the state), or their
``jax.numpy`` twins off the TPU; a prefill chunk scans the bucket's
``kda_chunk``-token chunks with ``kda_chunked`` from the slot's state
(zeros at offset 0, whatever the entry held: that is the reset at
admission) and writes back the state and the tail after the chunk's last
REAL token: a pad has ``beta = 0`` and ``g = 0`` and does not enter the
tail (the engine says how many tokens are real: ``cache["valid"]``).

**MLA layer**: ``mistral4.MLAttention`` with a direct query projection
(``q_lora_rank=None``) and no rotation (``mla_use_nope``): nothing in this
model reads a position. Its latent rows live in the growing table;
decode runs the paged kernel's latent form, a prefill chunk folds the
deployment's long table 512 cached keys at a time.

**Router**: sigmoid scores with a selection bias (``parallel.expert.
route_top_k``), one group. **The chip's share**, as in ``mistral4.py``:
``experts_held``, ``vocab_held``; the ``full`` preset is the cut
``chipbench/configs/kimi-linear-48b.json`` states.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from nezha_tpu import nn
from nezha_tpu.models.mistral4 import (GatedMLP, MLAttention, _linear,
                                       _project_f32)
from nezha_tpu.nn.module import Module, Variables, run_child
from nezha_tpu.ops.pallas import (kda_chunked, kda_conv_step,
                                  kda_conv_step_reference, kda_decode,
                                  kda_decode_reference)
from nezha_tpu.parallel.expert import DroplessMoE, DroplessMoEConfig
from nezha_tpu.tensor.policy import DEFAULT_POLICY, Policy

# The published pattern (1-indexed layers, as ``linear_attn_config`` has
# them): every fourth layer and the last are full attention.
_FULL = (4, 8, 12, 16, 20, 24, 27)
_KDA = tuple(l for l in range(1, 28) if l not in _FULL)


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    # The published keys (moonshotai/Kimi-Linear-48B-A3B-Instruct
    # config.json; ``linear_attn_config`` flattened to ``kda_*`` /
    # ``full_attn_layers`` / ``short_conv_kernel_size``).
    vocab_size: int = 163840
    hidden_size: int = 2304
    intermediate_size: int = 9216
    num_hidden_layers: int = 27
    num_attention_heads: int = 32
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_use_nope: bool = True
    kda_layers: Tuple[int, ...] = _KDA            # 1-indexed
    full_attn_layers: Tuple[int, ...] = _FULL     # 1-indexed
    kda_num_heads: int = 32
    kda_head_dim: int = 128
    short_conv_kernel_size: int = 4
    first_k_dense_replace: int = 1
    num_experts: int = 256
    num_experts_per_token: int = 8
    num_shared_experts: int = 1
    moe_intermediate_size: int = 1024
    moe_renormalize: bool = True
    routed_scaling_factor: float = 2.446
    moe_router_activation_func: str = "sigmoid"
    rms_norm_eps: float = 1e-5
    model_max_length: int = 1048576
    # Not in the published config (the published code's constants): the
    # width of the two low-rank gates, and the chunk of the prefill scan.
    kda_gate_rank: int = 128
    kda_chunk: int = 64
    # The chip's share (the defaults are the whole model).
    experts_held: Tuple[int, int] = (0, 256)
    vocab_held: int = 163840
    # How a decode step reads both caches (mistral4.Mistral4Config,
    # ServeConfig.decode_impl): "auto" is the kernels on a TPU (the paged
    # kernel's latent form, the KDA update and convolution step) and their
    # ``jax.numpy`` twins elsewhere; "kernel" / "xla" force one.
    decode_impl: str = "auto"

    # What serve.Engine and the pools read of any model's config.
    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def num_heads(self) -> int:
        return self.num_attention_heads

    @property
    def max_positions(self) -> int:
        return self.model_max_length

    @property
    def num_experts_per_tok(self) -> int:
        return self.num_experts_per_token

    # What mistral4.MLAttention reads beside the published keys.
    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_row_width(self) -> int:
        """The cached row in whole 128-lane tiles (576 -> 640)."""
        return -(-self.latent_width // 128) * 128

    @property
    def softmax_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    def is_kda(self, layer: int) -> bool:
        """``layer`` counted from 0; the published lists count from 1."""
        return layer + 1 in self.kda_layers

    @property
    def sparse_layers(self) -> Tuple[int, ...]:
        return tuple(range(self.first_k_dense_replace,
                           self.num_hidden_layers))


# One chip's share of the stated deployment: the first five layers (the
# dense layer, then a whole [KDA, KDA, MLA, KDA] period of sparse layers),
# 64 of the 256 routed experts and a quarter of the vocabulary.
FULL_KW = dict(num_hidden_layers=5, experts_held=(0, 64), vocab_held=40960)
# CPU tests: every mechanism at widths a test can afford.
TINY_KW = dict(
    vocab_size=512, vocab_held=512, hidden_size=64, intermediate_size=128,
    num_hidden_layers=5, num_attention_heads=4, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=16, v_head_dim=16,
    kda_num_heads=4, kda_head_dim=16, kda_gate_rank=8, kda_chunk=8,
    num_experts=16, num_experts_per_token=4, experts_held=(0, 4),
    moe_intermediate_size=32, model_max_length=4096)


def _conv_taps(rows, w):
    """``y_t = sum_i w_i x_{t-K+1+i}`` over ``rows`` ``[..., K - 1 + T,
    C]`` (the tail, then the chunk) -> ``[..., T, C]`` float32: a plain
    sum of ``K`` shifted slices."""
    k = w.shape[0]
    t = rows.shape[-2] - k + 1
    rows, w = rows.astype(jnp.float32), w.astype(jnp.float32)
    return sum(rows[..., i:i + t, :] * w[i] for i in range(k))


class KDAttention(Module):
    """One KDA layer (the module docstring has the equations)."""

    def __init__(self, cfg: KimiLinearConfig, policy: Policy):
        self.cfg, self.policy = cfg, policy
        h = cfg.hidden_size
        inner = cfg.kda_num_heads * cfg.kda_head_dim
        self.q = _linear(h, inner, policy)
        self.k = _linear(h, inner, policy)
        self.v = _linear(h, inner, policy)
        self.f_a = _linear(h, cfg.kda_gate_rank, policy)
        self.f_b = _linear(cfg.kda_gate_rank, inner, policy)
        self.g_a = _linear(h, cfg.kda_gate_rank, policy)
        self.g_b = _linear(cfg.kda_gate_rank, inner, policy)
        self.b = _linear(h, cfg.kda_num_heads, policy)
        self.o_norm = nn.RMSNorm(cfg.kda_head_dim, cfg.rms_norm_eps, policy)
        self.o = _linear(inner, h, policy)

    def init(self, rng: jax.Array) -> Variables:
        """The linear children, and the leaves that are no child's:
        ``conv`` (``[K, 3*H*d]``: the q, k and v filters side by side,
        uniform(+-K^-0.5), a depthwise convolution's usual draw),
        ``a_log`` (a head; ``exp`` of it uniform in [1, 16]) and
        ``dt_bias`` (a channel; ``softplus`` of it log-uniform in [1e-3,
        1e-1]): decays spread over (0, 1), as the published code draws
        them. Both in float32."""
        c = self.cfg
        v = super().init(rng)
        r_conv, r_a, r_dt = jax.random.split(jax.random.fold_in(rng, 7), 3)
        inner, k = c.kda_num_heads * c.kda_head_dim, c.short_conv_kernel_size
        dt = jnp.exp(jax.random.uniform(
            r_dt, (inner,), jnp.float32, math.log(1e-3), math.log(1e-1)))
        v["params"].update(
            conv=jax.random.uniform(r_conv, (k, 3 * inner), jnp.float32,
                                    -k ** -0.5, k ** -0.5
                                    ).astype(self.policy.param_dtype),
            a_log=jnp.log(jax.random.uniform(r_a, (c.kda_num_heads,),
                                             jnp.float32, 1.0, 16.0)),
            dt_bias=dt + jnp.log(-jnp.expm1(-dt)))      # softplus^-1(dt)
        return v

    def project(self, variables: Variables, x):
        """-> (``qkv`` [B, S, 3*H*d] in the compute dtype: the
        convolutions' inputs; ``g`` [B, S, H, d], ``beta`` [B, S, H],
        ``gate`` [B, S, H, d]: float32)."""
        c = self.cfg
        b, s, _ = x.shape
        heads, d = c.kda_num_heads, c.kda_head_dim
        st: dict = {}
        qkv = jnp.concatenate(
            [run_child(m, n, variables, st, x)
             for m, n in ((self.q, "q"), (self.k, "k"), (self.v, "v"))],
            axis=-1)
        p = variables["params"]
        f = _project_f32(self.f_b, variables, "f_b",
                         _project_f32(self.f_a, variables, "f_a", x))
        g = -jnp.exp(p["a_log"])[:, None] * jax.nn.softplus(
            f + p["dt_bias"]).reshape(b, s, heads, d)
        beta = jax.nn.sigmoid(_project_f32(self.b, variables, "b", x))
        gate = jax.nn.sigmoid(_project_f32(
            self.g_b, variables, "g_b",
            _project_f32(self.g_a, variables, "g_a", x)))
        return qkv, g, beta, gate.reshape(b, s, heads, d)

    def _heads(self, conv_out):
        """The convolutions' outputs ``[..., 3*H*d]`` float32 -> ``q``
        (L2-normalised, scaled), ``k`` (L2-normalised), ``v``: ``[..., H,
        d]``."""
        c = self.cfg
        y = jax.nn.silu(conv_out)
        q, k, v = (a.reshape(*a.shape[:-1], c.kda_num_heads, c.kda_head_dim)
                   for a in jnp.split(y, 3, axis=-1))

        def unit(a):
            return a * lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

        return unit(q) * c.kda_head_dim ** -0.5, unit(k), v

    def _out(self, variables, o, gate):
        """``o`` [B, S, H, d] float32 -> the layer's output [B, S, h]."""
        o = run_child(self.o_norm, "o_norm", variables, {}, o)
        o = (o.astype(jnp.float32) * gate).reshape(*o.shape[:2], -1)
        return _project_f32(self.o, variables, "o", o)

    def _scan(self, conv_w, qkv_rows, g, beta, s0, valid):
        """One sequence: ``qkv_rows`` ``[K - 1 + S, 3*H*d]`` (the tail,
        then the chunk), ``g`` / ``beta`` of the chunk's ``S`` tokens, of
        which the first ``valid`` are real. -> (``o`` [S, H, d], the
        state after the last real token)."""
        c = self.cfg
        s = g.shape[0]
        q, k, v = self._heads(_conv_taps(qkv_rows, conv_w))
        real = jnp.arange(s) < valid
        g = jnp.where(real[:, None, None], g, 0.0)
        beta = jnp.where(real[:, None], beta, 0.0)
        pad = -s % c.kda_chunk
        if pad:     # the cache-less forward at any length
            q, k, v, g, beta = (
                jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                for a in (q, k, v, g, beta))
        o, s_new = kda_chunked(q, k, v, g, beta, s0, chunk=c.kda_chunk)
        return o[:s], s_new

    def apply(self, variables: Variables, x, training: bool = False, rng=None,
              cache=None, pos=None, prefill: bool = False, active=None):
        del training, rng, prefill
        c = self.cfg
        b, s, _ = x.shape
        heads, d = c.kda_num_heads, c.kda_head_dim
        taps = c.short_conv_kernel_size - 1
        conv_w = variables["params"]["conv"]
        qkv, g, beta, gate = self.project(variables, x)
        states: dict = {}
        if cache is None:
            zeros = jnp.zeros((taps, qkv.shape[-1]), qkv.dtype)
            s0 = jnp.zeros((heads, d, d), jnp.float32)
            o = jnp.stack([
                self._scan(conv_w, jnp.concatenate([zeros, qkv[i]]), g[i],
                           beta[i], s0, s)[0] for i in range(b)])
            return self._out(variables, o, gate), states
        if "s" not in cache:
            raise ValueError(
                "a KDA layer's cache is the state group's: it takes a "
                "cache with 's', 'conv' and 'tables' (the serve engine's)")
        s_pool, conv_pool, tab = cache["s"], cache["conv"], cache["tables"]
        per_row = getattr(pos, "ndim", 0) == 1
        if per_row and s > 1:
            raise ValueError(
                "multi-token steps at per-row positions (speculative "
                "verify) are not implemented: a state cannot take back a "
                "rejected token")
        if per_row:
            # Decode: one token a row; a row that must not advance
            # updates the scratch entry (entry 0) instead of its own.
            ent = tab[:, 0] if active is None else jnp.where(
                active, tab[:, 0], 0)
            impl = c.decode_impl
            conv_step, update = (
                (kda_conv_step, kda_decode) if impl == "kernel" or (
                    impl == "auto" and jax.default_backend() == "tpu")
                else (kda_conv_step_reference, kda_decode_reference))
            y, conv_pool = conv_step(conv_pool, ent, qkv[:, 0], conv_w)
            q, k, v = self._heads(y)
            o, s_pool = update(s_pool, ent, q, k, v, g[:, 0], beta[:, 0])
            o = o[:, None]
        else:
            # A prefill chunk of ONE row at the traced offset ``pos``: from
            # zeros at offset 0, else from the slot's entry.
            if b != 1:
                raise ValueError("a prefill chunk is one row's")
            valid = cache.get("valid", s)
            ent = tab[0, 0]
            fresh = pos == 0
            tail = jnp.where(fresh, 0, conv_pool[ent]).reshape(taps, -1)
            s0 = jnp.where(fresh, 0.0, s_pool[ent])
            rows = jnp.concatenate([tail, qkv[0].astype(conv_pool.dtype)])
            o, s_new = self._scan(conv_w, rows, g[0], beta[0], s0, valid)
            o = o[None]
            # the tail after the last REAL token: rows valid .. valid+K-2
            conv_pool = lax.dynamic_update_slice_in_dim(
                conv_pool, lax.dynamic_slice_in_dim(
                    rows, valid, taps, axis=0).reshape(
                        (1,) + conv_pool.shape[1:]), ent, axis=0)
            s_pool = lax.dynamic_update_slice_in_dim(
                s_pool, s_new[None], ent, axis=0)
        states["cache"] = {"s": s_pool, "conv": conv_pool, "tables": tab}
        return self._out(variables, o, gate), states


class Block(Module):
    def __init__(self, cfg: KimiLinearConfig, layer: int, policy: Policy):
        h = cfg.hidden_size
        self.attn_norm = nn.RMSNorm(h, cfg.rms_norm_eps, policy)
        self.attn = (KDAttention(cfg, policy) if cfg.is_kda(layer)
                     else MLAttention(cfg, policy))
        self.mlp_norm = nn.RMSNorm(h, cfg.rms_norm_eps, policy)
        self.sparse = layer >= cfg.first_k_dense_replace
        if not self.sparse:
            self.mlp = GatedMLP(h, cfg.intermediate_size, policy)
            return
        self.shared = GatedMLP(
            h, cfg.moe_intermediate_size * cfg.num_shared_experts, policy)
        self.moe = DroplessMoE(DroplessMoEConfig(
            d_model=h, d_ff=cfg.moe_intermediate_size,
            num_experts=cfg.num_experts, top_k=cfg.num_experts_per_token,
            experts_held=cfg.experts_held,
            norm_topk_prob=cfg.moe_renormalize,
            routed_scaling_factor=cfg.routed_scaling_factor,
            score_func=cfg.moe_router_activation_func), policy)

    def apply(self, variables: Variables, x, training: bool = False, rng=None,
              cache=None, pos=None, prefill: bool = False, active=None):
        states: dict = {}
        b, s, h = x.shape
        a = run_child(self.attn, "attn", variables, states,
                      run_child(self.attn_norm, "attn_norm", variables,
                                states, x),
                      cache=cache, pos=pos, prefill=prefill, active=active)
        x = x.astype(jnp.float32) + a       # float32 residual (mistral4.py)
        y = run_child(self.mlp_norm, "mlp_norm", variables, states, x)
        if not self.sparse:
            return x + run_child(self.mlp, "mlp", variables, states, y), states
        shared = run_child(self.shared, "shared", variables, states, y)
        routed = run_child(
            self.moe, "moe", variables, states, y.reshape(b * s, h),
            active=active if (active is not None and s == 1) else None)
        return x + shared + routed.reshape(b, s, h), states


class KimiLinear(Module):
    """Returns logits [B, S, vocab_held] (float32); untied head."""

    def __init__(self, cfg: KimiLinearConfig = KimiLinearConfig(),
                 policy: Policy = DEFAULT_POLICY):
        if not 1 <= cfg.vocab_held <= cfg.vocab_size:
            raise ValueError(f"vocab_held {cfg.vocab_held} outside the "
                             f"vocabulary of {cfg.vocab_size}")
        if cfg.kda_chunk & (cfg.kda_chunk - 1):
            raise ValueError(f"kda_chunk {cfg.kda_chunk}: the prefill "
                             f"buckets are scanned in power-of-two chunks")
        self.cfg = cfg
        self.policy = policy
        self.embed = nn.Embedding(cfg.vocab_held, cfg.hidden_size,
                                  policy=policy)
        self.h = [Block(cfg, i, policy)
                  for i in range(cfg.num_hidden_layers)]
        self.norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps, policy)
        self.lm_head = _linear(cfg.hidden_size, cfg.vocab_held, policy)

    def apply(self, variables: Variables, batch, training: bool = False,
              rng=None, cache=None, pos=None, prefill: bool = False,
              active=None):
        tokens = batch["tokens"][:, :-1] if isinstance(batch, dict) else batch
        states: dict = {}
        x = run_child(self.embed, "embed", variables, states, tokens)
        for i, block in enumerate(self.h):
            x = run_child(block, f"h{i}", variables, states, x,
                          cache=None if cache is None else cache[i],
                          pos=pos, prefill=prefill, active=active)
        x = run_child(self.norm, "norm", variables, states, x)
        return _project_f32(self.lm_head, variables, "lm_head", x), states

    # ------------------------------------- what serve.Engine asks a model
    def cache_leaves(self, block_size: int, dtype, quantized: bool = False
                     ) -> list:
        """One entry a layer, ``(group, window, leaves)``: an MLA layer's
        latent rows ``(block_size, row)`` in the growing group; a KDA
        layer's ``s`` (float32, whatever the pool's dtype: the state is
        summed into for a sequence's whole life) and ``conv`` (the
        tail's ``kernel - 1`` inputs, oldest first, as rows of 128 lanes:
        an entry is whole tiles, contiguous in HBM, which is what
        ``kda_conv_step`` copies; an entry ``[kernel - 1, 3*H*d]`` has a
        sublane dimension of 3, and the compiler then re-lays the whole
        leaf out around every access) in the STATE group, whose leaves are per
        slot, not per block of tokens."""
        if quantized:
            raise ValueError(
                "kv_dtype='int8': neither a recurrent state nor a latent "
                "row has a block quantizer")
        c = self.cfg
        heads, d = c.kda_num_heads, c.kda_head_dim
        taps, chans = c.short_conv_kernel_size - 1, 3 * heads * d
        lanes = 128 if chans % 128 == 0 else chans
        state = {"s": ((heads, d, d), jnp.float32),
                 "conv": ((taps * chans // lanes, lanes), dtype)}
        latent = {"latent": ((block_size, c.latent_row_width), dtype)}
        return [("state", None, state) if c.is_kda(i)
                else ("global", None, latent)
                for i in range(c.num_hidden_layers)]

    def caches_from_states(self, states: dict, prev: list) -> list:
        return [states.get(f"h{i}", {}).get("attn", {}).get("cache", prev[i])
                for i in range(self.cfg.num_hidden_layers)]

    def expert_load(self, states: dict):
        """[sparse layers, experts held] int32: pairs computed per held
        expert in this forward pass."""
        return jnp.stack([states[f"h{i}"]["moe"]["load"]
                          for i in self.cfg.sparse_layers])

    def expert_visits(self, states: dict):
        """[sparse layers, 2] int32: the (row tile, expert) visits the
        experts' kernel made in this forward pass and the held experts
        it touched (``ops/pallas/moe_experts.py::visit_plan``)."""
        return jnp.stack([states[f"h{i}"]["moe"]["visits"]
                          for i in self.cfg.sparse_layers])

    def paged_prefill_uses_kernel(self) -> bool:
        return False

    def prefill_scan_chunks(self, width: int) -> int:
        """Chunks a state layer's scan walks in a prefill bucket of
        ``width`` tokens (asked of a model with state layers only: the
        ``serve.engine.prefill`` span's ``state_chunks``)."""
        return -(-width // self.cfg.kda_chunk)


def kimi_linear(preset: str = "full", policy: Optional[Policy] = None,
                **overrides) -> KimiLinear:
    """``full``: one chip's share at the published widths (``FULL_KW``),
    bf16 parameters and compute. ``tiny``: float32, for CPU tests."""
    if preset == "full":
        kw = dict(FULL_KW)
        policy = policy or Policy(jnp.bfloat16, jnp.bfloat16)
    elif preset == "tiny":
        kw = dict(TINY_KW)
        policy = policy or DEFAULT_POLICY
    else:
        raise ValueError(f"unknown preset {preset!r}")
    kw.update(overrides)
    return KimiLinear(KimiLinearConfig(**kw), policy=policy)
