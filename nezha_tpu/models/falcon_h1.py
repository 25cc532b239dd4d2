"""Falcon-H1 (``model_type: falcon_h1``): every layer runs grouped-query
attention and a Mamba-2 mixer side by side on the same normed input, then
a SwiGLU MLP; µP multipliers on every branch.

Per layer, with ``x`` a token's hidden state (the residual stream is
float32, as in ``decoder.py``; ``x_0 = E[t] * embedding_multiplier``)::

    n   = RMSNorm(x)                                       input_layernorm
    att = Attn(n * attention_in_multiplier) * attention_out_multiplier
    ssm = SSM(n * ssm_in_multiplier) * ssm_out_multiplier
    x   = x + att + ssm
    m   = RMSNorm(x)                                       pre_ff_layernorm
    x   = x + W_down(SiLU((m W_gate) * g) * (m W_up)) * d   (g, d: mlp_multipliers)
    logits = (RMSNorm(x_L) W_head) * lm_head_multiplier

**Attention**: ``attention.GQAttention``'s paged decode and blocked
prefill, with this model's projection (:class:`Attention`): no QK-norm,
``k`` times ``key_multiplier``, ``q`` and ``k`` rotated in the half-split
form at ``rope_theta``; query head ``h`` reads K/V head ``h // (H /
KVH)``. Its K/V rows live in the GROWING group.

**SSM** (Mamba-2, :class:`SSMixer`; ``H`` heads of ``P`` channels, d_state
``N``, ``G`` groups)::

    [z, xs, B, C, dt] = (u W_in) * mup        mup: ssm_multipliers a segment
    [xs, B, C] = SiLU(conv4([xs, B, C]) + b_conv)   causal, depthwise
    dt = softplus(dt + dt_bias);  a = -exp(A_log)          a scalar a head
    S_h <- exp(dt_h a_h) S_h + B_g (dt_h xs_h)^T;  y_h = S_h^T C_g + D_h xs_h
    out = (GroupRMSNorm_G(y * SiLU(z)) * w) W_out           norm after gate

What the mixer caches is per SEQUENCE: the state ``S`` (``[H, N, P]``
float32: ``ops/pallas/ssm.py`` keeps d_state on sublanes) and the
convolution's tail (its last ``d_conv - 1`` inputs of ``H*P + 2*G*N``
channels, as rows of 128 lanes), both in the pool's STATE group. So a
layer holds an entry in TWO groups: its ``cache_leaves`` declares both
parts, and the engine hands it both tables (``tables``: the growing
group's; ``state_tables``: the state group's). Decode is
``kda.kda_conv_step`` (the convolution before its bias) and
``ssm.ssm_decode`` (``nezha_ssm_decode``), or their ``jax.numpy`` twins
off the TPU; a prefill chunk scans its ``mamba_chunk_size``-token chunks
with ``ssm.ssd_chunked`` from the slot's state (zeros at offset 0: the
reset at admission) and writes back the state and the tail after the
chunk's last REAL token: a pad has ``dt = 0`` and does not enter the tail.

**The chip's share**: ``vocab_held`` rows of the embedding and of the
untied head; the ``full`` preset is the cut
``chipbench/configs/falcon-h1-34b.json`` states.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from nezha_tpu import nn
from nezha_tpu.models.attention import GQAttention
from nezha_tpu.models.decoder import (DecoderConfig, DecoderLM,
                                      build_preset, conv_taps, linear,
                                      project_f32)
from nezha_tpu.nn.module import Module, Variables, run_child
from nezha_tpu.ops import rotary
from nezha_tpu.ops.pallas import (kda_conv_step, kda_conv_step_reference,
                                  ssd_chunked, ssm_decode,
                                  ssm_decode_reference)
from nezha_tpu.ops.pallas.common import use_kernels
from nezha_tpu.tensor.policy import DEFAULT_POLICY, Policy


@dataclasses.dataclass(frozen=True)
class FalconH1Config(DecoderConfig):
    # The published keys (tiiuae/Falcon-H1-34B-Instruct config.json).
    vocab_size: int = 261120
    hidden_size: int = 5120
    intermediate_size: int = 21504
    num_hidden_layers: int = 72
    num_attention_heads: int = 20
    num_key_value_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e11
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-5
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    mlp_multipliers: Tuple[float, float] = (0.1767766952966369,
                                            0.011160714285714284)
    ssm_in_multiplier: float = 0.25
    # z, x, B, C, dt segments of the input projection
    ssm_multipliers: Tuple[float, ...] = (
        0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
        0.3535533905932738)
    ssm_out_multiplier: float = 0.08838834764831845
    mamba_d_ssm: int = 4096
    mamba_d_head: int = 128
    mamba_n_heads: int = 32
    mamba_d_state: int = 256
    mamba_n_groups: int = 2
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    # The chip's share (the default is the whole vocabulary).
    vocab_held: int = 261120
    # "auto": the kernels on a TPU (the paged grouped decode kernel, the
    # SSM update and the convolution step) and their ``jax.numpy`` twins
    # elsewhere; "kernel" / "xla" force one (ServeConfig.decode_impl).
    decode_impl: str = "auto"

    @property
    def conv_dim(self) -> int:
        """The convolution's channels: ``xs``, ``B`` and ``C``."""
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def sparse_layers(self) -> Tuple[int, ...]:
        """No expert layer."""
        return ()


# One chip's share of the stated deployment: the first four layers (all of
# one kind) and an eighth of the vocabulary.
FULL_KW = dict(num_hidden_layers=4, vocab_held=32640)
# CPU tests: every mechanism at widths a test can afford (a group > 1 on
# both mixers).
TINY_KW = dict(
    vocab_size=512, vocab_held=512, hidden_size=256, intermediate_size=512,
    num_hidden_layers=4, num_attention_heads=10, num_key_value_heads=2,
    head_dim=16, mamba_d_ssm=128, mamba_d_head=16, mamba_n_heads=8,
    mamba_d_state=32, mamba_n_groups=2, mamba_chunk_size=16,
    rope_theta=1e4, max_position_embeddings=4096)


class Attention(GQAttention):
    """``GQAttention`` (its cache-less, decode and prefill paths) over
    this model's projection: no QK-norm; the keys times
    ``key_multiplier``; both rotated (half-split, ``rope_theta``)."""

    def __init__(self, cfg: FalconH1Config, policy: Policy):
        self.cfg, self.window, self.policy = cfg, None, policy
        h, d = cfg.hidden_size, cfg.head_dim
        self.q = linear(h, cfg.num_attention_heads * d, policy)
        self.k = linear(h, cfg.num_key_value_heads * d, policy)
        self.v = linear(h, cfg.num_key_value_heads * d, policy)
        self.o = linear(cfg.num_attention_heads * d, h, policy)

    def project(self, variables: Variables, x, positions):
        c = self.cfg
        b, s, _ = x.shape
        st: dict = {}
        q = run_child(self.q, "q", variables, st, x).reshape(
            b, s, c.num_attention_heads, c.head_dim)
        k = run_child(self.k, "k", variables, st, x).reshape(
            b, s, c.num_key_value_heads, c.head_dim)
        v = run_child(self.v, "v", variables, st, x).reshape(
            b, s, c.num_key_value_heads, c.head_dim)
        k = (k.astype(jnp.float32) * c.key_multiplier).astype(k.dtype)
        inv_freq = c.rope_theta ** (
            -jnp.arange(0, c.head_dim, 2, dtype=jnp.float32) / c.head_dim)
        q = rotary.apply_half_split(q, positions[..., None], inv_freq)
        k = rotary.apply_half_split(k, positions[..., None], inv_freq)
        return q, k, v


class SSMixer(Module):
    """One Mamba-2 mixer (the module docstring has the equations)."""

    def __init__(self, cfg: FalconH1Config, policy: Policy):
        self.cfg, self.policy = cfg, policy
        c = cfg
        self.in_proj = linear(
            c.hidden_size,
            2 * c.mamba_d_ssm + 2 * c.mamba_n_groups * c.mamba_d_state
            + c.mamba_n_heads, policy)
        self.out_proj = linear(c.mamba_d_ssm, c.hidden_size, policy)

    def init(self, rng: jax.Array) -> Variables:
        """The two projections, and the leaves that are no child's:
        ``conv`` ``[d_conv, conv_dim]`` and ``conv_bias`` (uniform(+-
        d_conv^-0.5), a depthwise convolution's usual draw), ``a_log``
        (a head; ``exp`` of it uniform in [1, 16]), ``dt_bias`` (a head;
        ``softplus`` of it log-uniform in [1e-3, 1e-1]), ``d`` (ones) and
        the gated norm's ``norm`` (ones): decays spread over (0, 1), as
        the published code draws them. ``a_log``, ``dt_bias`` and ``d``
        in float32."""
        c = self.cfg
        v = super().init(rng)
        r_conv, r_bias, r_a, r_dt = jax.random.split(
            jax.random.fold_in(rng, 7), 4)
        k, bound = c.mamba_d_conv, c.mamba_d_conv ** -0.5
        pdt = self.policy.param_dtype
        dt = jnp.exp(jax.random.uniform(
            r_dt, (c.mamba_n_heads,), jnp.float32, math.log(1e-3),
            math.log(1e-1)))
        v["params"].update(
            conv=jax.random.uniform(r_conv, (k, c.conv_dim), jnp.float32,
                                    -bound, bound).astype(pdt),
            conv_bias=jax.random.uniform(r_bias, (c.conv_dim,), jnp.float32,
                                         -bound, bound).astype(pdt),
            a_log=jnp.log(jax.random.uniform(r_a, (c.mamba_n_heads,),
                                             jnp.float32, 1.0, 16.0)),
            dt_bias=dt + jnp.log(-jnp.expm1(-dt)),      # softplus^-1(dt)
            d=jnp.ones((c.mamba_n_heads,), jnp.float32),
            norm=jnp.ones((c.mamba_d_ssm,), pdt))
        return v

    def project(self, variables: Variables, u):
        """-> (``z`` [B, S, d_ssm] float32, ``xbc`` [B, S, conv_dim] in the
        compute dtype: the convolution's inputs, ``dt`` [B, S, H] float32
        after softplus)."""
        c = self.cfg
        p = variables["params"]
        zxbcdt = project_f32(self.in_proj, variables, "in_proj", u)
        sizes = (c.mamba_d_ssm, c.mamba_d_ssm,
                 c.mamba_n_groups * c.mamba_d_state,
                 c.mamba_n_groups * c.mamba_d_state, c.mamba_n_heads)
        mup = jnp.concatenate([jnp.full((n,), m, jnp.float32)
                               for n, m in zip(sizes, c.ssm_multipliers)])
        zxbcdt = zxbcdt * mup
        z, xbc, dt = jnp.split(
            zxbcdt, [c.mamba_d_ssm, c.mamba_d_ssm + c.conv_dim], axis=-1)
        dt = jax.nn.softplus(dt + p["dt_bias"])
        return z, xbc.astype(self.policy.compute_dtype), dt

    def _split(self, conv_out):
        """The convolution's output ``[..., conv_dim]`` float32 (bias
        added) -> ``xs`` ``[..., H, P]``, ``B``, ``C`` ``[..., G, N]``."""
        c = self.cfg
        y = jax.nn.silu(conv_out)
        xs, b, cc = jnp.split(
            y, [c.mamba_d_ssm, c.mamba_d_ssm + c.mamba_n_groups
                * c.mamba_d_state], axis=-1)
        lead = y.shape[:-1]
        return (xs.reshape(*lead, c.mamba_n_heads, c.mamba_d_head),
                b.reshape(*lead, c.mamba_n_groups, c.mamba_d_state),
                cc.reshape(*lead, c.mamba_n_groups, c.mamba_d_state))

    def _out(self, variables, y, xs, z):
        """``y`` [B, S, H, P] float32 (the scan's, without the skip) ->
        the mixer's output [B, S, h] float32."""
        c = self.cfg
        p = variables["params"]
        y = y + p["d"][:, None] * xs
        y = y.reshape(*y.shape[:2], -1) * jax.nn.silu(z)
        g = y.reshape(*y.shape[:2], c.mamba_n_groups, -1)
        g = g * lax.rsqrt(jnp.mean(g * g, -1, keepdims=True)
                          + c.rms_norm_eps)
        y = g.reshape(y.shape) * p["norm"].astype(jnp.float32)
        return project_f32(self.out_proj, variables, "out_proj", y)

    def _scan(self, variables, rows, dt, s0, valid):
        """One sequence: ``rows`` ``[d_conv - 1 + S, conv_dim]`` (the
        tail, then the chunk), ``dt`` of the chunk's ``S`` tokens, of
        which the first ``valid`` are real. -> (``y`` [S, H, P], ``xs``
        [S, H, P], the state after the last real token)."""
        c = self.cfg
        p = variables["params"]
        s = dt.shape[0]
        xs, b, cc = self._split(conv_taps(rows, p["conv"])
                                + p["conv_bias"].astype(jnp.float32))
        dt = jnp.where((jnp.arange(s) < valid)[:, None], dt, 0.0)
        pad = -s % c.mamba_chunk_size       # the cache-less forward at any length
        scan_in = [jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
                   for a in (xs, dt, b, cc)]
        y, s_new = ssd_chunked(*scan_in[:2], -jnp.exp(p["a_log"]),
                               *scan_in[2:], s0, chunk=c.mamba_chunk_size)
        return y[:s], xs, s_new

    def apply(self, variables: Variables, u, training: bool = False, rng=None,
              cache=None, pos=None, prefill: bool = False, active=None):
        del training, rng, prefill
        c = self.cfg
        b, s, _ = u.shape
        taps = c.mamba_d_conv - 1
        p = variables["params"]
        z, xbc, dt = self.project(variables, u)
        states: dict = {}
        if cache is None:
            zeros = jnp.zeros((taps, c.conv_dim), xbc.dtype)
            s0 = jnp.zeros((c.mamba_n_heads, c.mamba_d_state,
                            c.mamba_d_head), jnp.float32)
            ys, xss = zip(*[self._scan(variables, jnp.concatenate(
                [zeros, xbc[i]]), dt[i], s0, s)[:2] for i in range(b)])
            return self._out(variables, jnp.stack(ys), jnp.stack(xss),
                             z), states
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
            conv_step, update = (
                (kda_conv_step, ssm_decode) if use_kernels(c.decode_impl)
                else (kda_conv_step_reference, ssm_decode_reference))
            y, conv_pool = conv_step(conv_pool, ent, xbc[:, 0], p["conv"])
            xs, bb, cc = self._split(y + p["conv_bias"].astype(jnp.float32))
            y, s_pool = update(s_pool, ent, xs, dt[:, 0],
                               -jnp.exp(p["a_log"]), bb, cc)
            y, xs = y[:, None], xs[:, None]
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
            rows = jnp.concatenate([tail, xbc[0].astype(conv_pool.dtype)])
            y, xs, s_new = self._scan(variables, rows, dt[0], s0, valid)
            y, xs = y[None], xs[None]
            # the tail after the last REAL token: rows valid .. valid+K-2
            conv_pool = lax.dynamic_update_slice_in_dim(
                conv_pool, lax.dynamic_slice_in_dim(
                    rows, valid, taps, axis=0).reshape(
                        (1,) + conv_pool.shape[1:]), ent, axis=0)
            s_pool = lax.dynamic_update_slice_in_dim(
                s_pool, s_new[None], ent, axis=0)
        states["cache"] = {"s": s_pool, "conv": conv_pool, "tables": tab}
        return self._out(variables, y, xs, z), states


class MLP(Module):
    """``W_down(SiLU((x W_gate) * g) * (x W_up)) * d``; float32 out."""

    def __init__(self, cfg: FalconH1Config, policy: Policy):
        self.cfg = cfg
        self.gate = linear(cfg.hidden_size, cfg.intermediate_size, policy)
        self.up = linear(cfg.hidden_size, cfg.intermediate_size, policy)
        self.down = linear(cfg.intermediate_size, cfg.hidden_size, policy)

    def apply(self, variables: Variables, x, training: bool = False, rng=None):
        g_mult, d_mult = self.cfg.mlp_multipliers
        g = project_f32(self.gate, variables, "gate", x)
        u = project_f32(self.up, variables, "up", x)
        return project_f32(self.down, variables, "down",
                           jax.nn.silu(g * g_mult) * u) * d_mult, {}


class Block(Module):
    def __init__(self, cfg: FalconH1Config, layer: int, policy: Policy):
        del layer       # every layer is alike
        self.cfg = cfg
        h = cfg.hidden_size
        self.input_layernorm = nn.RMSNorm(h, cfg.rms_norm_eps, policy)
        self.attn = Attention(cfg, policy)
        self.mamba = SSMixer(cfg, policy)
        self.pre_ff_layernorm = nn.RMSNorm(h, cfg.rms_norm_eps, policy)
        self.mlp = MLP(cfg, policy)

    def apply(self, variables: Variables, x, training: bool = False, rng=None,
              cache=None, pos=None, prefill: bool = False, active=None):
        c = self.cfg
        states: dict = {}
        attn_cache = ssm_cache = None
        if cache is not None:
            # the layer's two parts: K/V through the growing group's
            # table, the state and tail through the state group's
            extra = {"valid": cache["valid"]} if "valid" in cache else {}
            attn_cache = {"k": cache["k"], "v": cache["v"],
                          "tables": cache["tables"], **extra}
            ssm_cache = {"s": cache["s"], "conv": cache["conv"],
                         "tables": cache["state_tables"], **extra}
        n = run_child(self.input_layernorm, "input_layernorm", variables,
                      states, x)
        att = run_child(self.attn, "attn", variables, states,
                        n * c.attention_in_multiplier, cache=attn_cache,
                        pos=pos, prefill=prefill, active=active)
        ssm = run_child(self.mamba, "mamba", variables, states,
                        n * c.ssm_in_multiplier, cache=ssm_cache, pos=pos,
                        prefill=prefill, active=active)
        x = (x.astype(jnp.float32) + att * c.attention_out_multiplier
             + ssm * c.ssm_out_multiplier)
        m = run_child(self.pre_ff_layernorm, "pre_ff_layernorm", variables,
                      states, x)
        return x + run_child(self.mlp, "mlp", variables, states, m), states


class FalconH1(DecoderLM):
    """The embedding enters times ``embedding_multiplier``, the logits
    leave times ``lm_head_multiplier``."""

    Block = Block

    def __init__(self, cfg: FalconH1Config = FalconH1Config(),
                 policy: Policy = DEFAULT_POLICY):
        if cfg.num_attention_heads % cfg.num_key_value_heads:
            raise ValueError(
                f"{cfg.num_attention_heads} query heads are not whole "
                f"groups over {cfg.num_key_value_heads} K/V heads")
        if (cfg.mamba_n_heads % cfg.mamba_n_groups
                or cfg.mamba_n_heads * cfg.mamba_d_head != cfg.mamba_d_ssm):
            raise ValueError(
                f"{cfg.mamba_n_heads} SSM heads of {cfg.mamba_d_head} over "
                f"{cfg.mamba_n_groups} groups do not make d_ssm "
                f"{cfg.mamba_d_ssm}")
        if cfg.mamba_chunk_size & (cfg.mamba_chunk_size - 1):
            raise ValueError(
                f"mamba_chunk_size {cfg.mamba_chunk_size}: the prefill "
                f"buckets are scanned in power-of-two chunks")
        super().__init__(cfg, policy)

    def enter(self, x):
        return x.astype(jnp.float32) * self.cfg.embedding_multiplier

    def read_out(self, variables: Variables, states: dict, x):
        return (super().read_out(variables, states, x)
                * self.cfg.lm_head_multiplier)

    # ------------------------------------- what serve.Engine asks a model
    @property
    def ssm_layers(self) -> int:
        """Layers whose state a decode step updates (the engine's
        ``serve.ssm.*`` counters): every one."""
        return self.cfg.num_hidden_layers

    def cache_leaves(self, block_size: int, dtype, quantized: bool = False
                     ) -> list:
        """One entry a layer, of TWO parts: K and V rows ``(block_size,
        KVH*D)`` in the growing group; ``s`` (float32, whatever the
        pool's dtype: the state is summed into for a sequence's whole
        life, ``[H, N, P]``) and ``conv`` (the tail's ``d_conv - 1``
        inputs, oldest first, as rows of 128 lanes: what
        ``kda_conv_step`` copies) in the STATE group."""
        if quantized:
            raise ValueError(
                "kv_dtype='int8': neither a recurrent state nor a "
                "grouped-query row has a block quantizer")
        c = self.cfg
        taps = c.mamba_d_conv - 1
        lanes = 128 if c.conv_dim % 128 == 0 else c.conv_dim
        kv = (block_size, c.num_key_value_heads * c.head_dim)
        state = {"s": ((c.mamba_n_heads, c.mamba_d_state, c.mamba_d_head),
                       jnp.float32),
                 "conv": ((taps * c.conv_dim // lanes, lanes), dtype)}
        return [(("global", None, {"k": (kv, dtype), "v": (kv, dtype)}),
                 ("state", None, state))
                for _ in range(c.num_hidden_layers)]

    def caches_from_states(self, states: dict, prev: list) -> list:
        out = []
        for i in range(self.cfg.num_hidden_layers):
            st = states.get(f"h{i}", {})
            att = st.get("attn", {}).get("cache")
            ssm = st.get("mamba", {}).get("cache")
            out.append(prev[i] if att is None else {
                **att, "s": ssm["s"], "conv": ssm["conv"],
                "state_tables": ssm["tables"]})
        return out

    def prefill_scan_chunks(self, width: int) -> int:
        """Chunks a state layer's scan walks in a prefill bucket of
        ``width`` tokens (the ``serve.engine.prefill`` span's
        ``state_chunks``)."""
        return -(-width // self.cfg.mamba_chunk_size)


def falcon_h1(preset: str = "full", policy: Optional[Policy] = None,
              **overrides) -> FalconH1:
    """``models.decoder.build_preset``'s ``full`` / ``tiny``."""
    return build_preset(FalconH1, FalconH1Config, FULL_KW, TINY_KW, preset,
                        policy, overrides)
