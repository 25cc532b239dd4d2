"""Mistral-Small-4 (``model_type: mistral4``): latent attention (MLA), yarn
rotary positions, every layer a dropless expert layer with a shared expert.

Per layer, with ``x`` a token's hidden state::

    h = x + Attn(RMSNorm(x))
    y = h + Shared(RMSNorm(h)) + sum_{e in top-k, held} w_e E_e(RMSNorm(h))

**Attention.** ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb`` -> per head
``[q_nope | q_rope]``. ``[c_kv | k_r] = x W_kva``; ``c_kv = RMSNorm(c_kv)``;
``k_r = RoPE(k_r)`` is ONE rotary key shared by all heads. ``[k_nope | v]_h
= c_kv W_kvb,h``. What is cached is the row ``[c_kv | k_r]`` after norm and
rotation (``kv_lora_rank + qk_rope_head_dim`` values a token a layer, no
head axis). Two forms of the same function:

- *expanded* (prefill chunks, and the cache-less forward): keys and values
  re-expanded from the latent rows, ``s = (q_nope . k_nope + q_rope . k_r)
  * scale``;
- *absorbed* (single-token decode): ``q~_h = q_nope,h W_uk,h^T`` lives in
  the latent space, ``s = (q~_h . c_kv + q_rope,h . k_r) * scale``,
  ``o_h = (sum_t p_t c_kv,t) W_uv,h``, with ``W_uk,h`` / ``W_uv,h`` the
  two slices of ``W_kvb,h``: the cache is read once, as it lies.

**The chip's share.** ``experts_held`` (first, count) names the routed
experts whose weights live here, ``vocab_held`` the rows of the embedding
and the head. The router keeps its published width; what absent experts
would add is left out (``parallel.expert.DroplessMoE``). The ``full`` preset
is one chip's share of the deployment ``chipbench/configs/
mistral-small-4.json`` states; the text path only (no vision tower).

The model keeps the contract ``serve.Engine`` uses for GPT-2:
``apply(variables, tokens, cache=rows, pos=..., active=...)`` with a
prefill chunk at a traced scalar offset or one token a row at per-row
positions, block tables in each layer's cache dict; and it DECLARES its
per-layer cache leaves (:meth:`Mistral4.cache_leaves`), from which
``PagedSlotPool`` allocates.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from nezha_tpu import nn
from nezha_tpu.nn import initializers as init_lib
from nezha_tpu.nn.module import Module, Variables, child_vars, run_child
from nezha_tpu.ops import rotary
from nezha_tpu.ops.pallas import latent_decode_attention
from nezha_tpu.ops.pallas.common import NEG_BIG, pick_block
from nezha_tpu.parallel.expert import DroplessMoE, DroplessMoEConfig
from nezha_tpu.tensor.policy import DEFAULT_POLICY, Policy


@dataclasses.dataclass(frozen=True)
class Mistral4Config:
    # The published keys (mistralai/Mistral-Small-4-119B-2603 config.json;
    # ``rope_parameters`` flattened to ``rope_*`` / ``llama_4_scaling_beta``).
    vocab_size: int = 131072
    hidden_size: int = 4096
    num_hidden_layers: int = 36
    num_attention_heads: int = 32
    q_lora_rank: int = 1024
    kv_lora_rank: int = 256
    qk_nope_head_dim: int = 64
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 128
    num_experts_per_tok: int = 4
    n_shared_experts: int = 1
    moe_intermediate_size: int = 2048
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 1048576
    rope_theta: float = 10000.0
    rope_factor: float = 128.0
    rope_original_max: int = 8192
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    llama_4_scaling_beta: float = 0.1
    # The chip's share: routed experts (first, count) and vocabulary rows
    # whose weights are here. The defaults are the whole model.
    experts_held: Tuple[int, int] = (0, 128)
    vocab_held: int = 131072
    # How :class:`MLAttention` reads the paged latent cache at decode:
    # "kernel" is the paged decode kernel's latent form, which walks each
    # row's own live table entries once; "xla" gathers each row's whole
    # table as one view and attends it composed; "auto" is the kernel on
    # a TPU backend (what this model's cell runs) and the composed view
    # elsewhere (ServeConfig.decode_impl overrides it, as for GPT-2).
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
        return self.max_position_embeddings

    @property
    def latent_width(self) -> int:
        """Values cached a token a layer: ``[c_kv | k_r]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_row_width(self) -> int:
        """The cached row as it is stored: ``latent_width`` padded with
        zeros to whole 128-lane tiles (320 -> 384). A minor dimension
        that is not a multiple of 128 makes the TPU choose a device
        layout with the BLOCK axis minor, and every program then
        re-lays-out the whole pool twice a layer (PERF.md section 5)."""
        return -(-self.latent_width // 128) * 128

    @property
    def softmax_scale(self) -> float:
        m = rotary.yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m


# One chip's share of the stated deployment: six of the 36 layers (one
# pipeline stage), 32 of the 128 routed experts and a quarter of the
# vocabulary (one of the four chips that share each layer).
FULL_KW = dict(num_hidden_layers=6, experts_held=(0, 32), vocab_held=32768)
# CPU tests: every mechanism at widths a test can afford. The yarn ramp
# lies inside the 8 rotary pairs and the 4,096 positions.
TINY_KW = dict(
    vocab_size=512, vocab_held=512, hidden_size=64, num_hidden_layers=2,
    num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=16, v_head_dim=16,
    n_routed_experts=16, num_experts_per_tok=4, experts_held=(0, 4),
    moe_intermediate_size=32, max_position_embeddings=4096,
    rope_factor=8.0, rope_original_max=64, rope_beta_fast=4.0,
    rope_beta_slow=1.0)


# A prefill chunk attends a table of up to GATHERED_KEYS_MAX keys as ONE
# gathered view (scores ``f32[H, S, keys]``: 0.5 GB at 32 heads, 1,024
# queries and the 4,096 keys of Mistral-Small-4's deployment) and folds a
# longer table PREFILL_KEY_BLOCK keys at a time with an online softmax
# (scores ``f32[H, S, 512]`` whatever the length: the 16,384 keys of
# Kimi-Linear's deployment would be 2.1 GB gathered).
GATHERED_KEYS_MAX = 4096
PREFILL_KEY_BLOCK = 512


def _linear(n_in: int, n_out: int, policy: Policy) -> nn.Linear:
    return nn.Linear(n_in, n_out, use_bias=False,
                     kernel_init=init_lib.normal(0.02), policy=policy)


def _project_f32(linear: nn.Linear, variables: Variables, name: str, x):
    """``x @ W`` of a bias-free child linear layer with compute-dtype
    operands and the float32 accumulator as the result: a product that
    feeds a nonlinearity, the float32 residual stream or the sampler is
    not rounded to bf16 on the way."""
    cdt = linear.policy.compute_dtype
    w = child_vars(variables, name)["params"]["w"]
    return jnp.dot(x.astype(cdt), w.astype(cdt),
                   preferred_element_type=jnp.float32)


class GatedMLP(Module):
    """``(silu(x W_gate) * (x W_up)) W_down``: the shared expert.
    Returns float32."""

    def __init__(self, d_model: int, d_ff: int, policy: Policy):
        self.gate = _linear(d_model, d_ff, policy)
        self.up = _linear(d_model, d_ff, policy)
        self.down = _linear(d_ff, d_model, policy)

    def apply(self, variables: Variables, x, training: bool = False, rng=None):
        g = _project_f32(self.gate, variables, "gate", x)
        u = _project_f32(self.up, variables, "up", x)
        return _project_f32(self.down, variables, "down",
                            jax.nn.silu(g) * u), {}


class MLAttention(Module):
    """Multi-head latent attention over a paged latent cache, for any
    config with this module's keys (``Mistral4Config``,
    ``kimi_linear.KimiLinearConfig``). Two things are optional:

    - the low-rank query: ``q_lora_rank=None`` projects ``x`` straight to
      the heads (one ``q`` matrix; no ``q_a``, ``q_a_norm``, ``q_b``);
    - rotation: with ``mla_use_nope`` the ``qk_rope_head_dim`` dimensions
      of the query and of the shared key are kept and NOT rotated (and
      the position-dependent query scaling is off), so nothing here
      reads a position.

    ``kv_a`` / ``kv_a_norm`` / ``kv_b`` / ``o`` are always there. How a
    decode step reads the cache is the config's (``decode_impl``: the
    composed view or the paged kernel's latent form); how a prefill chunk
    does follows the table's length (``GATHERED_KEYS_MAX``).
    ``decode_kernel_name`` is the latent kernel call's name in a trace
    (``latent_decode_attention``'s ``name``): the block that builds this
    layer hands it down as it hands ``cfg``; nothing here reads it."""

    def __init__(self, cfg, policy: Policy,
                 decode_kernel_name: str = "nezha_decode_attention_latent"):
        self.cfg = cfg
        self.policy = policy
        self.decode_kernel_name = decode_kernel_name
        h, heads = cfg.hidden_size, cfg.num_attention_heads
        qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        self.rotates = not getattr(cfg, "mla_use_nope", False)
        if cfg.q_lora_rank is None:
            self.q = _linear(h, heads * qk, policy)
        else:
            self.q_a = _linear(h, cfg.q_lora_rank, policy)
            self.q_a_norm = nn.RMSNorm(cfg.q_lora_rank, cfg.rms_norm_eps,
                                       policy)
            self.q_b = _linear(cfg.q_lora_rank, heads * qk, policy)
        self.kv_a = _linear(h, cfg.latent_width, policy)
        self.kv_a_norm = nn.RMSNorm(cfg.kv_lora_rank, cfg.rms_norm_eps, policy)
        self.kv_b = _linear(cfg.kv_lora_rank,
                            heads * (cfg.qk_nope_head_dim + cfg.v_head_dim),
                            policy)
        self.o = _linear(heads * cfg.v_head_dim, h, policy)

    def _inv_freq(self):
        c = self.cfg
        return rotary.yarn_inv_freq(
            c.qk_rope_head_dim, c.rope_theta, c.rope_factor,
            c.rope_original_max, c.rope_beta_fast, c.rope_beta_slow)

    def project(self, variables: Variables, x, positions):
        """-> (q_nope [B,S,H,n], q_rope [B,S,H,r] rotated, latent
        [B,S,row]: the row that is cached, ``[c_kv | k_r | 0...]``).
        ``positions`` [B|1, S]."""
        c = self.cfg
        b, s, _ = x.shape
        heads, n, r = (c.num_attention_heads, c.qk_nope_head_dim,
                       c.qk_rope_head_dim)
        st: dict = {}
        if c.q_lora_rank is None:
            q = run_child(self.q, "q", variables, st, x)
        else:
            c_q = run_child(self.q_a_norm, "q_a_norm", variables, st,
                            run_child(self.q_a, "q_a", variables, st, x))
            q = run_child(self.q_b, "q_b", variables, st, c_q)
        q = q.reshape(b, s, heads, n + r)
        kv = run_child(self.kv_a, "kv_a", variables, st, x)
        c_kv = run_child(self.kv_a_norm, "kv_a_norm", variables, st,
                         kv[..., :c.kv_lora_rank])
        pad = jnp.zeros(kv.shape[:-1] + (c.latent_row_width
                                         - c.latent_width,), kv.dtype)
        if not self.rotates:
            return q[..., :n], q[..., n:], jnp.concatenate(
                [c_kv, kv[..., c.kv_lora_rank:], pad], axis=-1)
        inv_freq = self._inv_freq()
        if c.llama_4_scaling_beta:
            # 1 below the original context; grows with the logarithm of
            # how many original contexts deep the position lies.
            depth = jnp.floor(positions.astype(jnp.float32)
                              / c.rope_original_max)
            q = (q.astype(jnp.float32)
                 * (1.0 + c.llama_4_scaling_beta
                    * jnp.log1p(depth))[..., None, None]).astype(q.dtype)
        q_nope, q_rope = q[..., :n], q[..., n:]
        q_rope = rotary.apply_interleaved(q_rope, positions[..., None],
                                          inv_freq)
        k_r = rotary.apply_interleaved(kv[..., c.kv_lora_rank:], positions,
                                       inv_freq)
        return q_nope, q_rope, jnp.concatenate([c_kv, k_r, pad], axis=-1)

    def _w_kvb(self, variables: Variables):
        """``W_kvb`` as [kv_lora, H, nope + v] in the compute dtype."""
        c = self.cfg
        w = self.policy.cast_to_compute(
            child_vars(variables, "kv_b")["params"]["w"])
        return w.reshape(c.kv_lora_rank, c.num_attention_heads,
                         c.qk_nope_head_dim + c.v_head_dim)

    def expanded(self, variables, q_nope, q_rope, ctx, attendable):
        """Keys and values re-expanded from latent rows ``ctx`` [B,L,w];
        ``attendable`` [B|1, S, L] bool. -> [B, S, H*v]."""
        c = self.cfg
        w = self._w_kvb(variables)
        kv = jnp.einsum("blr,rhd->blhd", ctx[..., :c.kv_lora_rank], w)
        k_nope, v = kv[..., :c.qk_nope_head_dim], kv[..., c.qk_nope_head_dim:]
        k_r = ctx[..., c.kv_lora_rank:c.latent_width]
        s = (jnp.einsum("bshn,blhn->bhsl", q_nope, k_nope,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bshr,blr->bhsl", q_rope, k_r,
                          preferred_element_type=jnp.float32))
        s = jnp.where(attendable[:, None], s * c.softmax_scale, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        o = jnp.einsum("bhsl,blhv->bshv", p, v)
        return o.reshape(o.shape[0], o.shape[1], -1)

    def absorbed(self, variables, q_nope, q_rope, ctx, attendable):
        """One query a row (``q_*`` [B,1,H,*]) against latent rows ``ctx``
        [B,L,w] as they lie; ``attendable`` [B, L]. -> [B, 1, H*v]."""
        c = self.cfg
        q_cat = self.absorbed_query(variables, q_nope, q_rope)
        s = jnp.einsum("bhw,blw->bhl", q_cat, ctx,
                       preferred_element_type=jnp.float32)
        s = jnp.where(attendable[:, None], s * c.softmax_scale, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(ctx.dtype)
        o_lat = jnp.einsum("bhl,blr->bhr", p, ctx[..., :c.kv_lora_rank])
        return self.expanded_values(variables, o_lat)

    def absorbed_query(self, variables, q_nope, q_rope):
        """``[q_nope,h W_uk,h^T | q_rope,h | 0..]`` ``[B, H, row]``: each
        head's query as it meets a cached row (``q_*`` [B,1,H,*])."""
        c = self.cfg
        w_uk = self._w_kvb(variables)[..., :c.qk_nope_head_dim]
        q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], w_uk)
        # the stored row's zero lanes meet zeros in the query
        pad = jnp.zeros(q_lat.shape[:-1] + (c.latent_row_width
                                            - c.latent_width,), q_lat.dtype)
        return jnp.concatenate([q_lat, q_rope[:, 0], pad], axis=-1)

    def expanded_values(self, variables, o_lat):
        """``o_lat`` [B, H, kv_lora] (``sum_t p_t c_kv,t`` a head) through
        ``W_uv,h`` -> [B, 1, H*v]."""
        w_uv = self._w_kvb(variables)[..., self.cfg.qk_nope_head_dim:]
        o = jnp.einsum("bhr,rhv->bhv", o_lat, w_uv)
        return o.reshape(o.shape[0], 1, -1)

    def _gathered(self, pool, tab, dtype):
        """The rows' blocks as one ``[b, L, w]`` view (unbound entries
        gather scratch: always masked, at or past the length)."""
        b, m = tab.shape
        return pool[tab].reshape(b, m * pool.shape[1],
                                 pool.shape[-1]).astype(dtype)

    def _decode(self, variables, q_nope, q_rope, latent, cache, pos, active):
        """One token a row at its own depth: write its latent row
        (inactive rows write the scratch block, block 0, as for GPT-2),
        then attend absorbed: through the paged kernel's latent form,
        which reads the table as it lies, or composed over the gathered
        view (``decode_impl``). -> ([B, 1, H*v], the pool)."""
        c = self.cfg
        pool, tab = cache["latent"], cache["tables"]
        bs_kv, m = pool.shape[1], tab.shape[1]
        L = m * bs_kv
        pos_w = jnp.minimum(pos, L - 1)
        blk = jnp.take_along_axis(
            tab, jnp.clip(pos_w // bs_kv, 0, m - 1)[:, None], axis=1)[:, 0]
        off = pos_w % bs_kv
        if active is not None:
            blk = jnp.where(active, blk, 0)
            off = jnp.where(active, off, 0)
        pool = pool.at[blk, off, :].set(latent[:, 0, :].astype(pool.dtype))
        impl = c.decode_impl
        if impl == "kernel" or (impl == "auto"
                                and jax.default_backend() == "tpu"):
            # inactive rows attend nothing
            lengths = pos + 1 if active is None else jnp.where(
                active, pos + 1, 0)
            o_lat = latent_decode_attention(
                self.absorbed_query(variables, q_nope, q_rope), pool,
                lengths, tab, c.kv_lora_rank, c.softmax_scale,
                name=self.decode_kernel_name)
            return self.expanded_values(variables, o_lat[:, :, 0]), pool
        with jax.named_scope("nezha_mla_decode"):
            return self.absorbed(
                variables, q_nope, q_rope,
                self._gathered(pool, tab, latent.dtype),
                jnp.arange(L)[None, :] <= pos[:, None]), pool

    def _prefill_gathered(self, variables, q_nope, q_rope, latent, cache,
                          pos):
        """A chunk at a traced scalar offset, expanded over the gathered
        view of the whole table: pads past the prompt land in the row's
        own bound blocks and are overwritten by decode before any mask
        reaches them. -> ([1, S, H*v], the pool)."""
        pool, tab = cache["latent"], cache["tables"]
        bs_kv, m = pool.shape[1], tab.shape[1]
        L, s = m * bs_kv, latent.shape[1]
        ppos = jnp.minimum(pos + jnp.arange(s), L - 1)
        blk = tab[:, jnp.clip(ppos // bs_kv, 0, m - 1)]             # [b, s]
        pool = pool.at[blk, (ppos % bs_kv)[None, :], :].set(
            latent.astype(pool.dtype))
        attendable = (jnp.arange(L)[None, :]
                      <= (pos + jnp.arange(s))[:, None])[None]
        with jax.named_scope("nezha_mla_prefill"):
            return self.expanded(variables, q_nope, q_rope,
                                 self._gathered(pool, tab, latent.dtype),
                                 attendable), pool

    def _prefill_blocked(self, variables, q_nope, q_rope, latent, cache, pos):
        """A chunk of ``S`` tokens of one row at the traced offset
        ``pos``, of which the first ``cache["valid"]`` are real: write
        their rows (pads land on the scratch block), then fold the
        table's entries ``PREFILL_KEY_BLOCK`` keys at a time, expanded,
        up to the chunk's last query, with an online softmax: the scores
        of one fold are ``[H, S, key block]`` float32, whatever the
        table's length. -> ([1, S, H*v], the pool)."""
        c = self.cfg
        pool, tab = cache["latent"], cache["tables"]
        b, s = latent.shape[:2]
        bs_kv, m = pool.shape[1], tab.shape[1]
        local = jnp.arange(s)
        p_abs = pos + local
        keep = (local < cache.get("valid", s)) & (p_abs < m * bs_kv)
        blk = jnp.where(keep[None, :],
                        tab[:, jnp.clip(p_abs // bs_kv, 0, m - 1)], 0)
        off = jnp.where(keep, p_abs % bs_kv, 0)[None, :]
        pool = pool.at[blk, off, :].set(latent.astype(pool.dtype))
        e = pick_block(m, max(1, PREFILL_KEY_BLOCK // bs_kv))
        span = e * bs_kv
        w = self._w_kvb(variables)
        n, r = c.qk_nope_head_dim, c.kv_lora_rank

        def fold(i, carry):
            m_run, l_run, acc = carry
            ent = lax.dynamic_slice_in_dim(tab, i * e, e, axis=1)
            ctx = pool[ent].reshape(b, span, -1).astype(latent.dtype)
            kv = jnp.einsum("blr,rhd->blhd", ctx[..., :r], w)
            sc = (jnp.einsum("bshn,blhn->bhsl", q_nope, kv[..., :n],
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bshr,blr->bhsl", q_rope,
                               ctx[..., r:c.latent_width],
                               preferred_element_type=jnp.float32))
            visible = (i * span + jnp.arange(span))[None, :] \
                <= p_abs[:, None]
            sc = jnp.where(visible, sc * c.softmax_scale, NEG_BIG)
            m_new = jnp.maximum(m_run, sc.max(axis=-1, keepdims=True))
            p = jnp.where(visible, jnp.exp(sc - m_new), 0.0)
            corr = jnp.exp(m_run - m_new)
            acc = acc * corr + jnp.einsum(
                "bhsl,blhv->bhsv", p.astype(kv.dtype), kv[..., n:],
                preferred_element_type=jnp.float32)
            return m_new, l_run * corr + p.sum(axis=-1, keepdims=True), acc

        heads = c.num_attention_heads
        init = (jnp.full((b, heads, s, 1), NEG_BIG, jnp.float32),
                jnp.zeros((b, heads, s, 1), jnp.float32),
                jnp.zeros((b, heads, s, c.v_head_dim), jnp.float32))
        blocks = jnp.minimum((pos + s + span - 1) // span, m // e)
        _, l_run, acc = lax.fori_loop(0, blocks, fold, init)
        out = (acc / jnp.maximum(l_run, 1e-30)).astype(latent.dtype)
        return out.transpose(0, 2, 1, 3).reshape(b, s, -1), pool

    def apply(self, variables: Variables, x, training: bool = False, rng=None,
              cache=None, pos=None, prefill: bool = False, active=None):
        del training, rng, prefill
        b, s, _ = x.shape
        per_row = getattr(pos, "ndim", 0) == 1
        if per_row:
            positions = pos[:, None] + jnp.arange(s)[None, :]
        else:
            positions = (0 if pos is None else pos) + jnp.arange(s)[None, :]
        q_nope, q_rope, latent = self.project(variables, x, positions)
        states: dict = {}
        if cache is None:
            with jax.named_scope("nezha_mla_prefill"):
                causal = jnp.tril(jnp.ones((s, s), bool))[None]
                out = self.expanded(variables, q_nope, q_rope, latent, causal)
        else:
            if "tables" not in cache:
                raise ValueError(
                    "the latent cache is block-paged only: it takes a cache "
                    "with 'tables' (the serve engine's block tables)")
            if per_row and s > 1:
                raise ValueError(
                    "multi-token steps at per-row positions (speculative "
                    "verify) are not implemented for the latent cache")
            if per_row:
                out, pool = self._decode(variables, q_nope, q_rope, latent,
                                         cache, pos, active)
            elif (cache["tables"].shape[1] * cache["latent"].shape[1]
                  > GATHERED_KEYS_MAX):
                with jax.named_scope("nezha_mla_prefill"):
                    out, pool = self._prefill_blocked(
                        variables, q_nope, q_rope, latent, cache, pos)
            else:
                out, pool = self._prefill_gathered(
                    variables, q_nope, q_rope, latent, cache, pos)
            states["cache"] = {"latent": pool, "tables": cache["tables"]}
        return _project_f32(self.o, variables, "o", out), states


class Block(Module):
    def __init__(self, cfg: Mistral4Config, policy: Policy):
        h = cfg.hidden_size
        self.attn_norm = nn.RMSNorm(h, cfg.rms_norm_eps, policy)
        # the decode kernel's name in this model's traces: the one the
        # benchmark's ``kernel.mla_decode_*`` patterns were written for
        self.attn = MLAttention(cfg, policy,
                                decode_kernel_name="nezha_mla_decode_paged")
        self.mlp_norm = nn.RMSNorm(h, cfg.rms_norm_eps, policy)
        self.shared = GatedMLP(
            h, cfg.moe_intermediate_size * cfg.n_shared_experts, policy)
        self.moe = DroplessMoE(DroplessMoEConfig(
            d_model=h, d_ff=cfg.moe_intermediate_size,
            num_experts=cfg.n_routed_experts, top_k=cfg.num_experts_per_tok,
            experts_held=cfg.experts_held, norm_topk_prob=cfg.norm_topk_prob,
            routed_scaling_factor=cfg.routed_scaling_factor), policy)

    def apply(self, variables: Variables, x, training: bool = False, rng=None,
              cache=None, pos=None, prefill: bool = False, active=None):
        states: dict = {}
        b, s, h = x.shape
        a = run_child(self.attn, "attn", variables, states,
                      run_child(self.attn_norm, "attn_norm", variables,
                                states, x),
                      cache=cache, pos=pos, prefill=prefill, active=active)
        # The residual stream accumulates in float32 (the sublayers read
        # its norm in the compute dtype): a bf16 residual rounds a value of
        # the whole stream's magnitude at every add, the largest rounding
        # of the forward pass, for 16 KB a token of activation traffic.
        x = x.astype(jnp.float32) + a
        y = run_child(self.mlp_norm, "mlp_norm", variables, states, x)
        shared = run_child(self.shared, "shared", variables, states, y)
        # One token a row in a decode step: its rows' ``active`` mask
        # decides which pairs the expert-load counter counts.
        routed = run_child(
            self.moe, "moe", variables, states, y.reshape(b * s, h),
            active=active if (active is not None and s == 1) else None)
        return x + shared + routed.reshape(b, s, h), states


class Mistral4(Module):
    """Returns logits [B, S, vocab_held] (float32); untied head. The
    residual stream between the blocks is float32."""

    def __init__(self, cfg: Mistral4Config = Mistral4Config(),
                 policy: Policy = DEFAULT_POLICY):
        if not 1 <= cfg.vocab_held <= cfg.vocab_size:
            raise ValueError(f"vocab_held {cfg.vocab_held} outside the "
                             f"vocabulary of {cfg.vocab_size}")
        self.cfg = cfg
        self.policy = policy
        self.embed = nn.Embedding(cfg.vocab_held, cfg.hidden_size,
                                  policy=policy)
        self.h = [Block(cfg, policy) for _ in range(cfg.num_hidden_layers)]
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
        """One entry a layer, ``(group, window, leaves)``: every layer in
        the growing group, and the leaves of one pool block, name ->
        (trailing shape, dtype). One latent row a token, no head axis,
        the row (in whole 128-lane tiles) as the minor dimension."""
        if quantized:
            raise ValueError(
                "kv_dtype='int8': the latent cache has no block quantizer "
                "(its rows are a normed latent and a rotated key, not "
                "per-head K/V)")
        leaves = {"latent": ((block_size, self.cfg.latent_row_width), dtype)}
        return [("global", None, leaves)] * self.cfg.num_hidden_layers

    def caches_from_states(self, states: dict, prev: list) -> list:
        return [states.get(f"h{i}", {}).get("attn", {}).get("cache", prev[i])
                for i in range(self.cfg.num_hidden_layers)]

    def expert_load(self, states: dict):
        """[layers, experts held] int32: pairs computed per held expert
        in this forward pass."""
        return jnp.stack([states[f"h{i}"]["moe"]["load"]
                          for i in range(self.cfg.num_hidden_layers)])

    def expert_visits(self, states: dict):
        """[sparse layers, 2] int32: the (row tile, expert) visits the
        experts' kernel made in this forward pass and the held experts
        it touched (``ops/pallas/moe_experts.py::visit_plan``)."""
        return jnp.stack([states[f"h{i}"]["moe"]["visits"]
                          for i in range(self.cfg.num_hidden_layers)])

    def paged_prefill_uses_kernel(self) -> bool:
        return False


def mistral_small4(preset: str = "full", policy: Optional[Policy] = None,
                   **overrides) -> Mistral4:
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
    return Mistral4(Mistral4Config(**kw), policy=policy)
