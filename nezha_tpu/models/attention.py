"""The attention mixers the served decoders share, over the engine's
paged pool.

- :class:`MLAttention`: multi-head latent attention (Mistral-Small-4,
  Kimi-Linear's full layers, Xing4.0). One latent row a token a layer,
  in the growing group; decode reads it absorbed, a prefill chunk
  expanded.
- :class:`GQAttention`: grouped-query attention with QK-norm, on a full
  layer (the growing group) or a sliding one (a ring of blocks)
  (K-EXAONE; Falcon-H1 subclasses its projection). Per-head K/V rows,
  lane-dense: ``[N, bs, KVH*D]``.
- :class:`SparseAttention`: MiniCPM4's block-sparse layer (InfLLM v2,
  MiniCPM-SALA): the same K/V rows and a third leaf of compressed keys; a
  decode query reads only the blocks it selects.

Whether a decode step runs the paged kernels or their composed
``jax.numpy`` forms is the config's ``decode_impl``, resolved by
:func:`ops.pallas.common.use_kernels`.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from nezha_tpu import nn
from nezha_tpu.models.decoder import linear, project_f32
from nezha_tpu.nn.module import Module, Variables, child_vars, run_child
from nezha_tpu.ops import rotary
from nezha_tpu.ops.pallas import (flash_decode_attention,
                                  latent_decode_attention,
                                  paged_attention_composed)
from nezha_tpu.ops.pallas import sparse_attention as sparse_ops
from nezha_tpu.ops.pallas.common import NEG_BIG, pick_block, use_kernels
from nezha_tpu.tensor.policy import Policy

# A latent prefill chunk attends a table of up to GATHERED_KEYS_MAX keys
# as ONE gathered view (scores ``f32[H, S, keys]``: 0.5 GB at 32 heads,
# 1,024 queries and the 4,096 keys of Mistral-Small-4's deployment) and
# folds a longer table PREFILL_KEY_BLOCK keys at a time with an online
# softmax (scores ``f32[H, S, 512]`` whatever the length: the 16,384 keys
# of Kimi-Linear's deployment would be 2.1 GB gathered). A grouped-query
# chunk on a full layer always folds, PREFILL_KEY_BLOCK keys at a time.
GATHERED_KEYS_MAX = 4096
PREFILL_KEY_BLOCK = 512


class MLAttention(Module):
    """Multi-head latent attention over a paged latent cache, for any
    config with this module's keys (``Mistral4Config``,
    ``KimiLinearConfig``, ``Xing4Config``). Two things are optional:

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
            self.q = linear(h, heads * qk, policy)
        else:
            self.q_a = linear(h, cfg.q_lora_rank, policy)
            self.q_a_norm = nn.RMSNorm(cfg.q_lora_rank, cfg.rms_norm_eps,
                                       policy)
            self.q_b = linear(cfg.q_lora_rank, heads * qk, policy)
        self.kv_a = linear(h, cfg.latent_width, policy)
        self.kv_a_norm = nn.RMSNorm(cfg.kv_lora_rank, cfg.rms_norm_eps, policy)
        self.kv_b = linear(cfg.kv_lora_rank,
                           heads * (cfg.qk_nope_head_dim + cfg.v_head_dim),
                           policy)
        self.o = linear(heads * cfg.v_head_dim, h, policy)

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
        if use_kernels(c.decode_impl):
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
        return project_f32(self.o, variables, "o", out), states



def _fold(carry, sc, visible, vv):
    """One online-softmax fold of scores ``sc [..., S, L]`` (float32,
    scaled) and values ``vv [b, L, KVH, D]`` into ``(m, l, acc)``."""
    m, l, acc = carry
    sc = jnp.where(visible, sc, NEG_BIG)
    m_new = jnp.maximum(m, sc.max(axis=-1, keepdims=True))
    p = jnp.where(visible, jnp.exp(sc - m_new), 0.0)
    corr = jnp.exp(m - m_new)
    acc = acc * corr + jnp.einsum("bkgsl,blkd->bkgsd", p.astype(vv.dtype),
                                  vv, preferred_element_type=jnp.float32)
    return m_new, l * corr + p.sum(axis=-1, keepdims=True), acc


class GQAttention(Module):
    """Grouped-query attention of one layer; ``window`` None: a full
    layer (no rotation), else a sliding layer."""

    def __init__(self, cfg, window: Optional[int], policy: Policy):
        self.cfg, self.window, self.policy = cfg, window, policy
        h, d = cfg.hidden_size, cfg.head_dim
        self.q = linear(h, cfg.num_attention_heads * d, policy)
        self.k = linear(h, cfg.num_key_value_heads * d, policy)
        self.v = linear(h, cfg.num_key_value_heads * d, policy)
        self.q_norm = nn.RMSNorm(d, cfg.rms_norm_eps, policy)
        self.k_norm = nn.RMSNorm(d, cfg.rms_norm_eps, policy)
        self.o = linear(cfg.num_attention_heads * d, h, policy)

    def project(self, variables: Variables, x, positions):
        """-> q [B,S,H,D], k [B,S,KVH,D] (both normed; rotated on a
        sliding layer), v [B,S,KVH,D]. ``positions`` [B|1, S]."""
        c = self.cfg
        b, s, _ = x.shape
        st: dict = {}
        q = run_child(self.q, "q", variables, st, x).reshape(
            b, s, c.num_attention_heads, c.head_dim)
        k = run_child(self.k, "k", variables, st, x).reshape(
            b, s, c.num_key_value_heads, c.head_dim)
        v = run_child(self.v, "v", variables, st, x).reshape(
            b, s, c.num_key_value_heads, c.head_dim)
        q = run_child(self.q_norm, "q_norm", variables, st, q)
        k = run_child(self.k_norm, "k_norm", variables, st, k)
        if self.window is not None:
            inv_freq = c.rope_theta ** (
                -jnp.arange(0, c.head_dim, 2, dtype=jnp.float32)
                / c.head_dim)
            q = rotary.apply_half_split(q, positions[..., None], inv_freq)
            k = rotary.apply_half_split(k, positions[..., None], inv_freq)
        return q, k, v

    def _grouped(self, q):
        """[B,S,H,D] -> [B,KVH,G,S,D]: head ``h`` is (h // G, h % G)."""
        c = self.cfg
        b, s = q.shape[:2]
        kvh = c.num_key_value_heads
        return q.reshape(b, s, kvh, c.num_attention_heads // kvh,
                         c.head_dim).transpose(0, 2, 3, 1, 4)

    def _ungrouped(self, o):
        """[B,KVH,G,S,D] -> [B,S,H*D]."""
        b, kvh, g, s, d = o.shape
        return o.transpose(0, 3, 1, 2, 4).reshape(b, s, kvh * g * d)

    def _dense(self, q, k, v):
        """The cache-less forward: every key of the sequence."""
        s = q.shape[1]
        i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
        visible = j <= i
        if self.window is not None:
            visible &= i - j < self.window
        sc = jnp.einsum("bkgsd,blkd->bkgsl", self._grouped(q), k,
                        preferred_element_type=jnp.float32)
        sc = jnp.where(visible, sc * self.cfg.head_dim ** -0.5, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1).astype(v.dtype)
        return self._ungrouped(jnp.einsum("bkgsl,blkd->bkgsd", p, v))

    def _decode(self, q, k, v, cache, pos, active):
        """One token a row at its own depth through the paged pool."""
        kp, vp, tab = cache["k"], cache["v"], cache["tables"]
        b = q.shape[0]
        bs, m = kp.shape[1], tab.shape[1]
        if self.window is None:
            pos_w = jnp.minimum(pos, m * bs - 1)
            entry = pos_w // bs
        else:
            pos_w = pos
            entry = (pos // bs) % m
        blk = jnp.take_along_axis(tab, entry[:, None], axis=1)[:, 0]
        off = pos_w % bs
        lengths = pos + 1
        if active is not None:
            # inactive rows write the scratch block and attend nothing
            blk, off = jnp.where(active, blk, 0), jnp.where(active, off, 0)
            lengths = jnp.where(active, lengths, 0)
        kp = kp.at[blk, off, :].set(k.reshape(b, -1).astype(kp.dtype))
        vp = vp.at[blk, off, :].set(v.reshape(b, -1).astype(vp.dtype))
        attend = (flash_decode_attention if use_kernels(self.cfg.decode_impl)
                  else paged_attention_composed)
        out = attend(q.transpose(0, 2, 1, 3), kp, vp, lengths,
                     block_tables=tab, window=self.window)
        return out.reshape(b, 1, -1), kp, vp

    def _prefill(self, q, k, v, cache, pos):
        """A chunk of ``S`` tokens of one row at the traced offset
        ``pos``; only the first ``cache["valid"]`` are real."""
        kp, vp, tab = cache["k"], cache["v"], cache["tables"]
        b, s = q.shape[:2]
        bs, m = kp.shape[1], tab.shape[1]
        valid = cache.get("valid", s)
        scale = self.cfg.head_dim ** -0.5
        local = jnp.arange(s)
        p_abs = pos + local                                   # [S]
        k_rows = k.reshape(b, s, -1).astype(kp.dtype)
        v_rows = v.reshape(b, s, -1).astype(vp.dtype)
        qg = self._grouped(q)
        if self.window is None:
            # write the real tokens (pads land on the scratch block),
            # then fold the table's key blocks up to the last query
            keep = (local < valid) & (p_abs < m * bs)
            blk = jnp.where(keep[None, :],
                            tab[:, jnp.clip(p_abs // bs, 0, m - 1)], 0)
            off = jnp.where(keep, p_abs % bs, 0)[None, :]
            kp = kp.at[blk, off, :].set(k_rows)
            vp = vp.at[blk, off, :].set(v_rows)
            e = pick_block(m, max(1, PREFILL_KEY_BLOCK // bs))
            span = e * bs

            def fold(i, carry):
                ent = lax.dynamic_slice_in_dim(tab, i * e, e, axis=1)
                kk = kp[ent].reshape(b, span, *k.shape[2:]).astype(q.dtype)
                vv = vp[ent].reshape(b, span, *v.shape[2:]).astype(q.dtype)
                sc = jnp.einsum("bkgsd,blkd->bkgsl", qg, kk,
                                preferred_element_type=jnp.float32) * scale
                kpos = i * span + jnp.arange(span)
                return _fold(carry, sc, kpos[None, :] <= p_abs[:, None], vv)

            shape = qg.shape[:-1] + (1,)
            init = (jnp.full(shape, NEG_BIG, jnp.float32),
                    jnp.zeros(shape, jnp.float32),
                    jnp.zeros(qg.shape, jnp.float32))
            blocks = jnp.minimum((pos + s + span - 1) // span, m // e)
            _, l, acc = lax.fori_loop(0, blocks, fold, init)
            out = acc / jnp.maximum(l, 1e-30)
        else:
            w = self.window
            # the window before the chunk, out of the ring as it stands
            pp = pos - w + jnp.arange(w)
            ppc = jnp.maximum(pp, 0)
            pblk, poff = tab[:, (ppc // bs) % m], (ppc % bs)[None, :]
            keys = jnp.concatenate([kp[pblk, poff], k_rows], axis=1)
            vals = jnp.concatenate([vp[pblk, poff], v_rows], axis=1)
            # bands: a block of queries sees the keys from ``w`` before
            # its first query to its last one
            qb = w if s % w == 0 else s
            nq = s // qb
            idx = (jnp.arange(nq) * qb)[:, None] + jnp.arange(qb + w)[None]

            def bands(rows, heads_shape):
                """[b, w + S, KVH*D] -> [b, nq, qb + w, KVH, D]: static
                slices (a gather of the same rows costs a DMA a row)."""
                return jnp.stack([rows[:, n * qb:n * qb + qb + w]
                                  for n in range(nq)], axis=1).reshape(
                    b, nq, qb + w, *heads_shape)

            kk, vv = bands(keys, k.shape[2:]), bands(vals, v.shape[2:])
            qn = qg.reshape(*qg.shape[:3], nq, qb, qg.shape[-1])
            sc = jnp.einsum("bkgnqd,bnlkd->bkgnql", qn, kk.astype(q.dtype),
                            preferred_element_type=jnp.float32) * scale
            q_loc = idx[:, :qb, None]                         # [nq, qb, 1]
            k_loc = idx[:, None, :] - w                       # [nq, 1, L]
            visible = ((k_loc <= q_loc) & (q_loc - k_loc < w)
                       & (k_loc + pos >= 0))
            sc = jnp.where(visible, sc, -jnp.inf)
            p = jax.nn.softmax(sc, axis=-1).astype(q.dtype)
            out = jnp.einsum("bkgnql,bnlkd->bkgnqd", p, vv.astype(q.dtype),
                             preferred_element_type=jnp.float32)
            out = out.reshape(qg.shape)
            # write what later queries can see: the real tokens in the
            # ring's last ``m`` blocks (the rest, and the pads, land on
            # the scratch block)
            last_blk = (pos + valid - 1) // bs
            keep = (local < valid) & (p_abs // bs > last_blk - m)
            blk = jnp.where(keep[None, :], tab[:, (p_abs // bs) % m], 0)
            off = jnp.where(keep, p_abs % bs, 0)[None, :]
            kp = kp.at[blk, off, :].set(k_rows)
            vp = vp.at[blk, off, :].set(v_rows)
        return self._ungrouped(out.astype(q.dtype)), kp, vp

    def apply(self, variables: Variables, x, training: bool = False, rng=None,
              cache=None, pos=None, prefill: bool = False, active=None):
        del training, rng, prefill
        s = x.shape[1]
        per_row = getattr(pos, "ndim", 0) == 1
        if per_row:
            positions = pos[:, None] + jnp.arange(s)[None, :]
        else:
            positions = (0 if pos is None else pos) + jnp.arange(s)[None, :]
        q, k, v = self.project(variables, x, positions)
        states: dict = {}
        if cache is None:
            out = self._dense(q, k, v)
        else:
            if "tables" not in cache:
                raise ValueError(
                    "the grouped-query cache is block-paged only: it takes "
                    "a cache with 'tables' (the serve engine's block tables)")
            if per_row and s > 1:
                raise ValueError(
                    "multi-token steps at per-row positions (speculative "
                    "verify) are not implemented: a ring cannot take back "
                    "a rejected token's write")
            if per_row:
                out, kp, vp = self._decode(q, k, v, cache, pos, active)
            else:
                out, kp, vp = self._prefill(q, k, v, cache, pos)
            states["cache"] = {"k": kp, "v": vp, "tables": cache["tables"]}
        return project_f32(self.o, variables, "o", out), states



class SparseAttention(Module):
    """MiniCPM4's sparse layer (InfLLM v2): grouped-query attention with
    no position encoding and per-head RMSNorm on ``q`` and ``k``, whose
    decode query reads only the blocks it selects
    (``ops/pallas/sparse_attention.py`` has the rule), and a sigmoid
    output gate: ``W_o(o * sigmoid(x W_g))``. Its paged part holds three
    leaves in the growing group: ``k``, ``v`` ``[bs, KVH*D]`` and ``ck``
    ``[r*KVH, D]`` float32 (the compressed keys, ``r = bs / stride`` a
    block). The pool's block is the selection's block.

    Decode: ``sparse_score`` (every compressed key of the row, and the
    window its own token completes, scored), the selection in
    ``jax.numpy``, ``sparse_decode_paged`` over the selected list; the
    completed window's mean is then written into ``ck``. A prefill chunk
    (``jax.numpy``) writes its K/V rows and the windows it completes, then
    folds the table ``PREFILL_KEY_BLOCK`` keys at a time under each
    query's own selection, scored ``SCORE_QUERY_BLOCK`` queries at a time
    (a chunk that lies wholly at or below ``dense_len`` scores nothing)."""

    SCORE_QUERY_BLOCK = 256

    def __init__(self, cfg, policy: Policy):
        self.cfg, self.policy = cfg, policy
        h, d = cfg.hidden_size, cfg.head_dim
        heads, kvh = cfg.num_attention_heads, cfg.num_key_value_heads
        self.sizes = cfg.sparse_sizes
        self.q = linear(h, heads * d, policy)
        self.k = linear(h, kvh * d, policy)
        self.v = linear(h, kvh * d, policy)
        self.q_norm = nn.RMSNorm(d, cfg.rms_norm_eps, policy)
        self.k_norm = nn.RMSNorm(d, cfg.rms_norm_eps, policy)
        self.o_gate = linear(h, heads * d, policy)
        self.o = linear(heads * d, h, policy)

    def project(self, variables: Variables, x):
        """-> q [B,S,H,D], k [B,S,KVH,D] (both normed), v [B,S,KVH,D]."""
        c = self.cfg
        b, s, _ = x.shape
        st: dict = {}
        q = run_child(self.q, "q", variables, st, x).reshape(
            b, s, c.num_attention_heads, c.head_dim)
        k = run_child(self.k, "k", variables, st, x).reshape(
            b, s, c.num_key_value_heads, c.head_dim)
        v = run_child(self.v, "v", variables, st, x).reshape(
            b, s, c.num_key_value_heads, c.head_dim)
        q = run_child(self.q_norm, "q_norm", variables, st, q)
        k = run_child(self.k_norm, "k_norm", variables, st, k)
        return q, k, v

    def _scale(self):
        return self.cfg.head_dim ** -0.5

    def _query_masks(self, q, ckeys, lengths):
        """``q`` [Q, H, D] (one row's queries), ``ckeys`` [NC, KVH, D]
        float32 (every window of the row's table; a window's score is
        read only where it is complete at the query's ``lengths``) ->
        the selected blocks [KVH, Q, M]."""
        c, z = self.cfg, self.sizes
        kvh = c.num_key_value_heads
        qg = q.reshape(q.shape[0], kvh, -1, c.head_dim).astype(jnp.float32)
        logits = jnp.einsum("qghd,jgd->gqhj", qg * self._scale(), ckeys,
                            precision=lax.Precision.HIGHEST)
        n_c = sparse_ops.n_complete(lengths, z)              # [Q]
        valid = jnp.arange(ckeys.shape[0]) < n_c[:, None, None]
        m = jnp.max(jnp.where(valid, logits, -jnp.inf), -1, keepdims=True)
        p = jnp.where(valid, jnp.exp(logits - m), 0.0)
        p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
        scores = sparse_ops.block_scores(p.sum(2), n_c[None, :], z)
        return sparse_ops.select_mask(scores, lengths[None, :], z)

    def _chunk_masks(self, q, ckeys, lengths):
        """:meth:`_query_masks` ``SCORE_QUERY_BLOCK`` queries at a time
        (the scores of one block are ``[H, block, NC]`` float32)."""
        s = q.shape[0]
        qb = pick_block(s, self.SCORE_QUERY_BLOCK)
        masks = lax.map(
            lambda a: self._query_masks(a[0], ckeys, a[1]),
            (q.reshape(s // qb, qb, *q.shape[1:]), lengths.reshape(-1, qb)))
        return masks.transpose(1, 0, 2, 3).reshape(
            masks.shape[1], s, masks.shape[-1])

    def _attend_masked(self, q, keys, vals, visible):
        """``q`` [Q,H,D], ``keys``/``vals`` [T,KVH,D], ``visible``
        [KVH,Q,T] -> [Q,H*D]."""
        c = self.cfg
        kvh = c.num_key_value_heads
        qg = q.reshape(q.shape[0], kvh, -1, c.head_dim)
        sc = jnp.einsum("qghd,tgd->gqht", qg, keys.astype(q.dtype),
                        preferred_element_type=jnp.float32) * self._scale()
        sc = jnp.where(visible[:, :, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1).astype(vals.dtype)
        o = jnp.einsum("gqht,tgd->qghd", p, vals)
        return o.reshape(q.shape[0], -1)

    def _dense(self, q, k, v):
        """The cache-less forward: each query's own selection over the
        sequence's complete windows."""
        z = self.sizes
        b, s, kvh, d = k.shape
        m = -(-s // z.block)
        lengths = jnp.arange(1, s + 1)

        taps = (z.stride * jnp.arange(m * z.per_block)[:, None]
                + jnp.arange(z.kernel))

        def one(qr, kr, vr):
            kpad = jnp.pad(kr.astype(jnp.float32),
                           ((0, m * z.block + z.kernel - s), (0, 0), (0, 0)))
            ckeys = kpad[taps].mean(1)                         # [NC, KVH, D]
            masks = self._query_masks(qr, ckeys, lengths)      # [KVH,S,M]
            t = jnp.arange(s)
            visible = (jnp.repeat(masks, z.block, axis=-1)[..., :s]
                       & (t[None, None, :] <= t[None, :, None]))
            return self._attend_masked(qr, kr, vr, visible)

        return jax.vmap(one)(q, k, v)

    def _write_windows(self, kp, ck, tab, first, count, last_len):
        """Write the windows ``first .. first + count - 1`` (a static
        count; those at or past ``n_c(last_len)`` land on the scratch
        block) of ONE row, each the mean of its keys read back from the
        pool, into ``ck`` at their block's slot."""
        z = self.sizes
        bs, m = kp.shape[1], tab.shape[1]
        kvh, d = self.cfg.num_key_value_heads, self.cfg.head_dim
        j = first + jnp.arange(count)
        keep = j < sparse_ops.n_complete(last_len, z)
        p = jnp.clip(z.stride * j[:, None] + jnp.arange(z.kernel), 0,
                     m * bs - 1)
        rows = kp[tab[0, p // bs], p % bs].astype(jnp.float32)
        means = rows.mean(1).reshape(count, kvh, d)
        blk = jnp.where(keep, tab[0, jnp.clip(j // z.per_block, 0, m - 1)], 0)
        row = ((j % z.per_block)[:, None] * kvh + jnp.arange(kvh))
        return ck.at[blk[:, None], row].set(means)

    def _prefill(self, q, k, v, cache, pos):
        """A chunk of ``S`` tokens of one row at the traced offset
        ``pos``; only the first ``cache["valid"]`` are real."""
        z = self.sizes
        kp, vp, ck, tab = cache["k"], cache["v"], cache["ck"], cache["tables"]
        b, s = q.shape[:2]
        bs, m = kp.shape[1], tab.shape[1]
        valid = cache.get("valid", s)
        local = jnp.arange(s)
        p_abs = pos + local
        keep = (local < valid) & (p_abs < m * bs)
        blk = jnp.where(keep[None, :],
                        tab[:, jnp.clip(p_abs // bs, 0, m - 1)], 0)
        off = jnp.where(keep, p_abs % bs, 0)[None, :]
        kp = kp.at[blk, off, :].set(k.reshape(b, s, -1).astype(kp.dtype))
        vp = vp.at[blk, off, :].set(v.reshape(b, s, -1).astype(vp.dtype))
        # the windows this chunk completes
        ck = self._write_windows(kp, ck, tab, sparse_ops.n_complete(pos, z),
                                 s // z.stride + 1, pos + valid)
        lengths = p_abs + 1
        kvh, d = k.shape[2], k.shape[3]
        ckeys = ck[tab[0]].reshape(m * z.per_block, kvh, d)
        masks = lax.cond(
            pos + valid > z.dense_len,
            lambda: self._chunk_masks(q[0], ckeys, lengths),
            lambda: jnp.broadcast_to(
                jnp.arange(m)[None, None, :] * bs < lengths[None, :, None],
                (kvh, s, m)))
        e = pick_block(m, max(1, PREFILL_KEY_BLOCK // bs))
        span = e * bs
        qg = q.reshape(b, s, kvh, -1, d).transpose(0, 2, 3, 1, 4)

        def fold(i, carry):
            ent = lax.dynamic_slice_in_dim(tab, i * e, e, axis=1)
            kk = kp[ent].reshape(b, span, kvh, d).astype(q.dtype)
            vv = vp[ent].reshape(b, span, kvh, d).astype(q.dtype)
            sc = jnp.einsum("bkgsd,blkd->bkgsl", qg, kk,
                            preferred_element_type=jnp.float32) * self._scale()
            kpos = i * span + jnp.arange(span)
            sel = jnp.repeat(lax.dynamic_slice_in_dim(masks, i * e, e, axis=2),
                             bs, axis=2)                     # [KVH, S, span]
            visible = (kpos[None, :] <= p_abs[:, None])[None] & sel
            return _fold(carry, sc, visible[None, :, None], vv)

        shape = qg.shape[:-1] + (1,)
        init = (jnp.full(shape, NEG_BIG, jnp.float32),
                jnp.zeros(shape, jnp.float32),
                jnp.zeros(qg.shape, jnp.float32))
        blocks = jnp.minimum((pos + s + span - 1) // span, m // e)
        _, l, acc = lax.fori_loop(0, blocks, fold, init)
        out = (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)
        out = out.transpose(0, 3, 1, 2, 4).reshape(b, s, -1)
        return out, {"k": kp, "v": vp, "ck": ck}

    def _decode(self, q, k, v, cache, pos, active):
        """One token a row at its own depth: write its K/V rows, score,
        select, attend the selected list, write the window its token
        completed."""
        c, z = self.cfg, self.sizes
        kp, vp, ck, tab = cache["k"], cache["v"], cache["ck"], cache["tables"]
        b = q.shape[0]
        bs, m = kp.shape[1], tab.shape[1]
        kvh, d = c.num_key_value_heads, c.head_dim
        pos_w = jnp.minimum(pos, m * bs - 1)
        blk = jnp.take_along_axis(tab, (pos_w // bs)[:, None], axis=1)[:, 0]
        off = pos_w % bs
        lengths = pos + 1
        if active is not None:
            blk, off = jnp.where(active, blk, 0), jnp.where(active, off, 0)
            lengths = jnp.where(active, lengths, 0)
        kp = kp.at[blk, off, :].set(k.reshape(b, -1).astype(kp.dtype))
        vp = vp.at[blk, off, :].set(v.reshape(b, -1).astype(vp.dtype))
        kernels = use_kernels(c.decode_impl)
        score = (sparse_ops.sparse_score if kernels
                 else sparse_ops.sparse_score_reference)
        s, fresh = score(q[:, 0], ck, kp, tab, lengths, self._scale(), z)
        n_c = sparse_ops.n_complete(lengths, z)
        scores = sparse_ops.block_scores(s.transpose(0, 2, 1), n_c[:, None], z)
        mask = sparse_ops.select_mask(scores, lengths[:, None], z)
        ids, keys = sparse_ops.select_list(mask, lengths, z, z.list_len(m))
        attend = (sparse_ops.sparse_decode_paged if kernels
                  else sparse_ops.sparse_decode_reference)
        out = attend(q[:, 0], kp, vp, tab, ids, keys, self._scale())
        # the window this token completed, into its block's slot
        due = (lengths >= z.kernel) & ((lengths - z.kernel) % z.stride == 0)
        j = jnp.maximum(n_c - 1, 0)
        wblk = jnp.take_along_axis(
            tab, jnp.clip(j // z.per_block, 0, m - 1)[:, None], axis=1)[:, 0]
        wblk = jnp.where(due, wblk, 0)
        row = (j % z.per_block)[:, None] * kvh + jnp.arange(kvh)
        ck = ck.at[wblk[:, None], row].set(fresh.reshape(b, kvh, d))
        return out.reshape(b, 1, -1), {"k": kp, "v": vp, "ck": ck}

    def apply(self, variables: Variables, x, training: bool = False, rng=None,
              cache=None, pos=None, prefill: bool = False, active=None):
        del training, rng, prefill
        s = x.shape[1]
        q, k, v = self.project(variables, x)
        states: dict = {}
        if cache is None:
            out = self._dense(q, k, v)
        else:
            if getattr(pos, "ndim", 0) == 1:
                if s > 1:
                    raise ValueError(
                        "multi-token steps at per-row positions "
                        "(speculative verify) are not implemented")
                out, leaves = self._decode(q, k, v, cache, pos, active)
            else:
                out, leaves = self._prefill(q, k, v, cache, pos)
            states["cache"] = {**leaves, "tables": cache["tables"]}
        gate = jax.nn.sigmoid(project_f32(self.o_gate, variables, "o_gate", x))
        out = (out.astype(jnp.float32) * gate).astype(out.dtype)
        return project_f32(self.o, variables, "o", out), states
