"""MiniCPM-SALA (``model_type: minicpm_sala``): a MiniCPM decoder whose
layers are of two kinds, ``mixer_types`` a layer: ``minicpm4`` (InfLLM-v2
block-sparse attention, ``attention.SparseAttention``) and
``lightning-attn`` (linear attention with a per-head decay,
:class:`LightningAttention`); a SiLU-gated MLP after each; MiniCPM's µP
scales on the embedding, the branches and the head.

Per layer, with ``x`` a token's hidden state (float32 between the blocks,
as in ``decoder.py``; ``x_0 = E[t] * scale_emb``; ``L`` the PUBLISHED
layer count, also in a cut model)::

    x = x + Mixer(RMSNorm(x)) * scale_depth / sqrt(L)
    x = x + W_down(SiLU(m W_gate) * (m W_up)) * scale_depth / sqrt(L),
        m = RMSNorm(x)
    logits = RMSNorm(x_N) W_head / (hidden_size / dim_model_base)

**Lightning** (``H`` heads of ``D``, per head; ``l`` the layer's published
index, ``slope_h`` the ALiBi slope of head ``h``)::

    q, k = RoPE(RMSNorm_h(x W_q)), RoPE(RMSNorm_h(x W_k));  v = x W_v
    S_t = g_h S_{t-1} + k_t^T v_t,   g_h = exp(-slope_h (1 - l/(L-1) + 1e-5))
    o_t = (q_t / sqrt(D)) S_t
    out = W_o(RMSNorm_h(o) * sigmoid(x W_g))

which is ``ops/pallas/ssm.py``'s Mamba-2 recurrence with ``dt = 1``,
``a_h = log g_h``, ``B = k``, ``C = q``, ``x = v`` and a group a head
(``N = P = D``): decode through ``ssm_decode`` (``nezha_ssm_decode``), a
prefill chunk through ``ssd_chunked`` from the slot's state. The state
(``[H, D, D]`` float32) is the layer's one leaf in the pool's STATE
group; the sparse layer's ``k``, ``v`` and ``ck`` leaves are in the
growing group.

**The chip's share**: the ``full`` preset is the cut
``chipbench/configs/minicpm-sala.json`` states (the published layers
0-3, the whole vocabulary).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from nezha_tpu import nn
from nezha_tpu.models.attention import SparseAttention
from nezha_tpu.models.decoder import (DecoderConfig, DecoderLM, GatedMLP,
                                      build_preset, linear, project_f32)
from nezha_tpu.nn.module import Module, Variables, run_child
from nezha_tpu.ops import rotary
from nezha_tpu.ops.pallas import (SparseSizes, ssd_chunked, ssm_decode,
                                  ssm_decode_reference)
from nezha_tpu.ops.pallas.common import use_kernels
from nezha_tpu.tensor.policy import DEFAULT_POLICY, Policy

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
# The published pattern (openbmb/MiniCPM-SALA config.json, mixer_types).
MIXER_TYPES = (SPARSE,) + (LIGHTNING,) * 8 + (SPARSE,) + (LIGHTNING,) * 6 \
    + (SPARSE,) * 2 + (LIGHTNING,) * 4 + (SPARSE,) + (LIGHTNING,) * 6 \
    + (SPARSE,) * 3


@dataclasses.dataclass(frozen=True)
class MiniCPMSALAConfig(DecoderConfig):
    # The published keys (openbmb/MiniCPM-SALA config.json).
    vocab_size: int = 73448
    hidden_size: int = 4096
    intermediate_size: int = 16384
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    lightning_nh: int = 32
    lightning_head_dim: int = 128
    rope_theta: float = 10000.0
    max_position_embeddings: int = 524288
    rms_norm_eps: float = 1e-6
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    mixer_types: Tuple[str, ...] = MIXER_TYPES
    # MiniCPM4's sparse_config (assumed: the catalog's row lacks it)
    sparse_kernel_size: int = 32
    sparse_kernel_stride: int = 16
    sparse_block_size: int = 64
    sparse_topk: int = 64
    sparse_init_blocks: int = 1
    sparse_window_size: int = 2048
    sparse_dense_len: int = 8192
    # Lightning prefill: tokens a chunk of the SSD chunked form.
    lightning_chunk: int = 128
    # The vocabulary rows held (the whole vocabulary).
    vocab_held: int = 73448
    # "auto": the kernels on a TPU, their jax.numpy twins elsewhere.
    decode_impl: str = "auto"

    @property
    def published_layers(self) -> int:
        """``L`` of the depth scale and the decay: the published count."""
        return len(self.mixer_types)

    @property
    def sparse_sizes(self) -> SparseSizes:
        return SparseSizes(
            self.sparse_kernel_size, self.sparse_kernel_stride,
            self.sparse_block_size, self.sparse_topk,
            self.sparse_init_blocks, self.sparse_window_size,
            self.sparse_dense_len)

    def is_sparse(self, layer: int) -> bool:
        return self.mixer_types[layer] == SPARSE

    @property
    def sparse_layers(self) -> Tuple[int, ...]:
        """No expert layer (``DecoderLM.expert_load``'s question)."""
        return ()


# One chip's share of the stated deployment: the published layers 0-3
# (one sparse layer, three lightning ones), every width, the whole
# vocabulary.
FULL_KW = dict(num_hidden_layers=4)
# CPU tests: every mechanism at widths a test can afford, the sparse
# sizes cut so that ~100-token contexts select.
TINY_KW = dict(
    vocab_size=512, vocab_held=512, hidden_size=64, intermediate_size=128,
    num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, lightning_nh=4, lightning_head_dim=16,
    sparse_kernel_size=4, sparse_kernel_stride=2, sparse_block_size=8,
    sparse_topk=4, sparse_window_size=16, sparse_dense_len=32,
    lightning_chunk=16, max_position_embeddings=4096)


def alibi_slopes(heads: int):
    """MiniMax-01's (ALiBi's) slope a head: ``2^(-8/n)`` to the powers
    ``1..n`` for ``n`` a power of two; else the closest power's slopes and
    every other one of twice that."""
    def power_of_two(n):
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start * start ** i for i in range(n)]

    if math.log2(heads).is_integer():
        return power_of_two(heads)
    near = 2 ** math.floor(math.log2(heads))
    return (power_of_two(near)
            + power_of_two(2 * near)[0::2][:heads - near])


def lightning_log_decay(cfg: MiniCPMSALAConfig, layer: int):
    """``log g_h`` [H] of the layer at published index ``layer``."""
    depth = 1.0 - layer / (cfg.published_layers - 1) + 1e-5
    return -jnp.asarray(alibi_slopes(cfg.lightning_nh), jnp.float32) * depth


class LightningAttention(Module):
    """One lightning layer (the module docstring has the equations)."""

    def __init__(self, cfg: MiniCPMSALAConfig, layer: int, policy: Policy):
        self.cfg, self.layer, self.policy = cfg, layer, policy
        h, inner = cfg.hidden_size, cfg.lightning_nh * cfg.lightning_head_dim
        d = cfg.lightning_head_dim
        self.q = linear(h, inner, policy)
        self.k = linear(h, inner, policy)
        self.v = linear(h, inner, policy)
        self.q_norm = nn.RMSNorm(d, cfg.rms_norm_eps, policy)
        self.k_norm = nn.RMSNorm(d, cfg.rms_norm_eps, policy)
        self.o_norm = nn.RMSNorm(inner, cfg.rms_norm_eps, policy)
        self.o_gate = linear(h, inner, policy)
        self.o = linear(inner, h, policy)

    def project(self, variables: Variables, x, positions):
        """-> q (scaled), k, v ``[B, S, H, D]`` float32."""
        c = self.cfg
        b, s, _ = x.shape
        heads, d = c.lightning_nh, c.lightning_head_dim
        st: dict = {}
        q, k, v = (project_f32(m, variables, n, x).reshape(b, s, heads, d)
                   for m, n in ((self.q, "q"), (self.k, "k"), (self.v, "v")))
        q = run_child(self.q_norm, "q_norm", variables, st, q)
        k = run_child(self.k_norm, "k_norm", variables, st, k)
        inv_freq = c.rope_theta ** (
            -jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        q = rotary.apply_half_split(q.astype(jnp.float32),
                                    positions[..., None], inv_freq)
        k = rotary.apply_half_split(k.astype(jnp.float32),
                                    positions[..., None], inv_freq)
        return q * d ** -0.5, k, v

    def _out(self, variables, o, x):
        """``o`` [B, S, H, D] float32 -> the mixer's output [B, S, h]."""
        c = self.cfg
        p = variables["params"]
        o = o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + c.rms_norm_eps)
        o = o.reshape(*o.shape[:2], -1) * p["o_norm"]["scale"].astype(
            jnp.float32)
        gate = jax.nn.sigmoid(project_f32(self.o_gate, variables, "o_gate",
                                          x))
        return project_f32(self.o, variables, "o", o * gate)

    def apply(self, variables: Variables, x, training: bool = False, rng=None,
              cache=None, pos=None, prefill: bool = False, active=None):
        del training, rng, prefill
        c = self.cfg
        b, s, _ = x.shape
        heads, d = c.lightning_nh, c.lightning_head_dim
        per_row = getattr(pos, "ndim", 0) == 1
        if per_row:
            positions = pos[:, None] + jnp.arange(s)[None, :]
        else:
            positions = (0 if pos is None else pos) + jnp.arange(s)[None, :]
        q, k, v = self.project(variables, x, positions)
        a = lightning_log_decay(c, self.layer)
        states: dict = {}
        if cache is None:
            pad = -s % c.lightning_chunk
            zeros = jnp.zeros((heads, d, d), jnp.float32)

            def one(qr, kr, vr):
                t = [jnp.pad(t_, ((0, pad), (0, 0), (0, 0)))
                     for t_ in (vr, qr, kr)]
                dt = (jnp.arange(s + pad) < s)[:, None] * jnp.ones((heads,))
                y, _ = ssd_chunked(t[0], dt, a, t[2], t[1], zeros,
                                   chunk=c.lightning_chunk)
                return y[:s]

            return self._out(variables, jax.vmap(one)(q, k, v), x), states
        s_pool, tab = cache["s"], cache["tables"]
        if per_row:
            if s > 1:
                raise ValueError(
                    "multi-token steps at per-row positions (speculative "
                    "verify) are not implemented: a state cannot take back "
                    "a rejected token")
            # a row that must not advance updates the scratch entry 0
            ent = tab[:, 0] if active is None else jnp.where(
                active, tab[:, 0], 0)
            update = (ssm_decode if use_kernels(c.decode_impl)
                      else ssm_decode_reference)
            y, s_pool = update(s_pool, ent, v[:, 0], jnp.ones((b, heads)), a,
                               k[:, 0], q[:, 0])
            y = y[:, None]
        else:
            # a prefill chunk of ONE row at the traced offset ``pos``: from
            # zeros at offset 0, else from the slot's entry; a pad has
            # dt = 0 (it neither decays nor writes the state)
            if b != 1:
                raise ValueError("a prefill chunk is one row's")
            valid = cache.get("valid", s)
            ent = tab[0, 0]
            s0 = jnp.where(pos == 0, 0.0, s_pool[ent])
            dt = (jnp.arange(s) < valid)[:, None] * jnp.ones((heads,))
            y, s_new = ssd_chunked(v[0], dt, a, k[0], q[0], s0,
                                   chunk=c.lightning_chunk)
            y = y[None]
            s_pool = lax.dynamic_update_slice_in_dim(
                s_pool, s_new[None], ent, axis=0)
        states["cache"] = {"s": s_pool, "tables": tab}
        return self._out(variables, y, x), states


class Block(Module):
    def __init__(self, cfg: MiniCPMSALAConfig, layer: int, policy: Policy):
        self.cfg = cfg
        h = cfg.hidden_size
        self.input_layernorm = nn.RMSNorm(h, cfg.rms_norm_eps, policy)
        self.attn = (SparseAttention(cfg, policy) if cfg.is_sparse(layer)
                     else LightningAttention(cfg, layer, policy))
        self.post_attention_layernorm = nn.RMSNorm(h, cfg.rms_norm_eps,
                                                   policy)
        self.mlp = GatedMLP(h, cfg.intermediate_size, policy)

    def apply(self, variables: Variables, x, training: bool = False, rng=None,
              cache=None, pos=None, prefill: bool = False, active=None):
        c = self.cfg
        branch = c.scale_depth / math.sqrt(c.published_layers)
        states: dict = {}
        a = run_child(self.attn, "attn", variables, states,
                      run_child(self.input_layernorm, "input_layernorm",
                                variables, states, x),
                      cache=cache, pos=pos, prefill=prefill, active=active)
        x = x.astype(jnp.float32) + a * branch
        m = run_child(self.post_attention_layernorm,
                      "post_attention_layernorm", variables, states, x)
        return x + run_child(self.mlp, "mlp", variables, states, m) \
            * branch, states


class MiniCPMSALA(DecoderLM):
    """The embedding enters times ``scale_emb``; the last hidden state
    leaves divided by ``hidden_size / dim_model_base`` before the head."""

    Block = Block

    def __init__(self, cfg: MiniCPMSALAConfig = MiniCPMSALAConfig(),
                 policy: Policy = DEFAULT_POLICY):
        if cfg.num_attention_heads % cfg.num_key_value_heads:
            raise ValueError(
                f"{cfg.num_attention_heads} query heads are not whole "
                f"groups over {cfg.num_key_value_heads} K/V heads")
        if cfg.lightning_chunk & (cfg.lightning_chunk - 1):
            raise ValueError(
                f"lightning_chunk {cfg.lightning_chunk}: the prefill "
                f"buckets are scanned in power-of-two chunks")
        if cfg.num_hidden_layers > cfg.published_layers:
            raise ValueError(f"{cfg.num_hidden_layers} layers: the pattern "
                             f"has {cfg.published_layers}")
        cfg.sparse_sizes.validate()
        super().__init__(cfg, policy)

    def enter(self, x):
        return x.astype(jnp.float32) * self.cfg.scale_emb

    def read_out(self, variables: Variables, states: dict, x):
        c = self.cfg
        x = run_child(self.norm, "norm", variables, states, x)
        return project_f32(self.lm_head, variables, "lm_head",
                           x / (c.hidden_size / c.dim_model_base))

    # ------------------------------------- what serve.Engine asks a model
    @property
    def ssm_layers(self) -> int:
        """Layers whose state a decode step updates (the engine's
        ``serve.ssm.*`` counters): the lightning layers."""
        c = self.cfg
        return sum(not c.is_sparse(i) for i in range(c.num_hidden_layers))

    @property
    def sparse_attention_layers(self) -> int:
        """Layers that select blocks (the ``serve.sparse.*`` counters)."""
        c = self.cfg
        return sum(c.is_sparse(i) for i in range(c.num_hidden_layers))

    def sparse_blocks(self, lengths):
        """-> (blocks a decode query at each visible length reads, blocks
        its row holds), summed over the K/V heads and the sparse layers:
        the selection rule's count, from the lengths alone."""
        c, z = self.cfg, self.cfg.sparse_sizes
        lengths = lengths[lengths > 0]
        held = -(-lengths // z.block)
        first = (lengths - z.window).clip(0) // z.block   # the window's
        forced = held - first + np.minimum(z.init_blocks, first)
        read = np.where(lengths > z.dense_len,
                        np.minimum(held, forced + z.topk), held)
        per = c.num_key_value_heads * self.sparse_attention_layers
        return int(read.sum()) * per, int(held.sum()) * per

    def cache_leaves(self, block_size: int, dtype, quantized: bool = False
                     ) -> list:
        """One entry a layer, ``(group, window, leaves)``: the sparse
        layer's ``k``, ``v`` rows ``(block_size, KVH*D)`` and ``ck``
        (``(r*KVH, D)`` float32, the block's compressed keys) in the
        growing group; a lightning layer's ``s`` (float32, ``[H, D, D]``:
        key dims on sublanes, value dims in lanes) in the STATE group."""
        c = self.cfg
        z = c.sparse_sizes
        if quantized:
            raise ValueError(
                "kv_dtype='int8': neither a recurrent state nor a "
                "compressed key has a block quantizer")
        if block_size != z.block:
            raise ValueError(
                f"kv_block_size {block_size}: the sparse layer selects "
                f"blocks of {z.block} tokens, which are the pool's blocks")
        kvh, d = c.num_key_value_heads, c.head_dim
        heads, ld = c.lightning_nh, c.lightning_head_dim
        kv = ((block_size, kvh * d), dtype)
        sparse = {"k": kv, "v": kv,
                  "ck": ((z.per_block * kvh, d), jnp.float32)}
        state = {"s": ((heads, ld, ld), jnp.float32)}
        return [("global", None, sparse) if c.is_sparse(i)
                else ("state", None, state)
                for i in range(c.num_hidden_layers)]

    def prefill_scan_chunks(self, width: int) -> int:
        """Chunks a lightning layer's scan walks in a prefill bucket of
        ``width`` tokens (the ``serve.engine.prefill`` span's
        ``state_chunks``)."""
        return -(-width // self.cfg.lightning_chunk)


def minicpm_sala(preset: str = "full", policy: Optional[Policy] = None,
                 **overrides) -> MiniCPMSALA:
    """``models.decoder.build_preset``'s ``full`` / ``tiny``."""
    return build_preset(MiniCPMSALA, MiniCPMSALAConfig, FULL_KW, TINY_KW,
                        preset, policy, overrides)
