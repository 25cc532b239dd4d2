"""Sequence-sharded prefill: one chunk's attention spread over the mesh.

``prefill_mode="sequence"`` (``nezha-serve --prefill-mode sequence``)
splits each prefill chunk's QUERY rows across the 1xM ``tp`` mesh so an
8k-32k document prompt stops monopolizing a replica for its whole
prefill — the long-context knob on top of the head-sharded pools PR 14
placed. Finished blocks land DIRECTLY in the head-sharded paged pool,
so decode proceeds completely unchanged.

Two layouts, selected by ``ServeConfig.seq_prefill_variant``:

- ``"ulysses"`` (the auto default whenever ``H % M == 0`` — always true
  under :class:`ShardedEngine`, which requires head-divisible pools):
  one ``lax.all_to_all`` reshards the chunk from the sequence domain to
  the head domain, each shard then runs the EXACT replicated prefill
  computation on its own ``H/M`` heads (the PR 18 flash-prefill kernel,
  fused int8 epilogue write included, or the composed masked mirror),
  and a reverse all-to-all restores the sequence layout. Per-head math
  is untouched and the all-to-alls only move data, so this variant is
  BIT-IDENTICAL to the replicated path — the parity gate the bench
  suite enforces.
- ``"ring"``: ``lax.ppermute`` neighbour hops, reusing
  ``parallel/ring.py``'s online-softmax hop fold. On float pools with
  the kernel available, the Q blocks circulate ("ring-q"): every hop
  runs ONE paged flash-prefill program on the traveling Q slice's own
  heads with a per-row global ``q_offsets`` operand — each (Q block,
  head group) pair is computed completely by exactly one shard, so no
  softmax merge is needed and the result is bitwise identical to the
  replicated kernel per row. The composed fallback ("ring-KV")
  circulates the gathered own-head paged PREFIX blocks instead and
  merges prefix and chunk attention by log-sum-exp
  (:func:`~nezha_tpu.parallel.ring.ring_attention_lse`); its reduction
  ORDER differs from the replicated composed path, so it carries a
  greedy-token parity guarantee rather than a bitwise one. Int8 pools
  under ring fall back to the composed per-shard
  ``_quant_prefill_write`` chain (the fused epilogue needs the full
  chunk's queries resident — prefer ulysses for int8, see RUNBOOK §8).

The module is a TRACE-TIME switch, not a runtime one:
:func:`seq_prefill_scope` is a contextvar scope (the
``auto_partitioner_scope`` idiom) that :class:`ShardedEngine` enters
while tracing its bucket programs; ``models/gpt2`` checks it through
``sys.modules`` (zero cost unless serving sequence mode ever imported
this module) and routes its paged prefill-chunk branch here. One
nested ``shard_map`` per bucket program — the frozen
``1 + len(buckets)`` program contract per (mesh, bucket) is untouched.
"""

from __future__ import annotations

import dataclasses
from contextlib import contextmanager
from contextvars import ContextVar
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

VARIANTS = ("auto", "ulysses", "ring")

_SEQ_PREFILL: ContextVar[Optional["SeqPrefillParams"]] = ContextVar(
    "nezha_seq_prefill", default=None)


@dataclasses.dataclass(frozen=True)
class SeqPrefillParams:
    """What the model needs to know to build the nested shard_map."""
    mesh: object          # the serve mesh (has a "tp" axis)
    variant: str          # "ulysses" | "ring" (resolved, never "auto")


@contextmanager
def seq_prefill_scope(mesh, variant: str):
    """Mark the dynamic extent of a prefill-program trace as
    sequence-sharded (``auto_partitioner_scope``'s contextvar idiom —
    composes with it; the sharded engine nests this inside). ``variant``
    must already be resolved (not ``"auto"``)."""
    if variant not in ("ulysses", "ring"):
        raise ValueError(
            f"seq_prefill_scope needs a resolved variant, got {variant!r}")
    token = _SEQ_PREFILL.set(SeqPrefillParams(mesh=mesh, variant=variant))
    try:
        yield
    finally:
        _SEQ_PREFILL.reset(token)


def seq_prefill_params() -> Optional[SeqPrefillParams]:
    """The active scope's params, or None outside any scope."""
    return _SEQ_PREFILL.get()


def _check_divisible(s: int, h: int, world: int):
    if s % world:
        raise ValueError(
            f"sequence-sharded prefill needs the chunk width ({s}) "
            f"divisible by the mesh size ({world}) — size prefill "
            f"buckets accordingly (ServeConfig validates this)")
    if h % world:
        raise ValueError(
            f"sequence-sharded prefill needs num_heads ({h}) divisible "
            f"by the mesh size ({world})")


def _composed_shard_attention(qh, k_pool, v_pool, tab, pos, scales,
                              *, L):
    """The replicated composed masked-attention expression, restricted
    to one shard's head slice — kept in lockstep with
    ``models/gpt2._apply_paged``'s composed branch so the ulysses
    mirror stays bit-identical to the single-device path."""
    from nezha_tpu import ops
    from nezha_tpu.models.gpt2 import _pool_view

    _, hh, s, _ = qh.shape
    if scales is not None:
        from nezha_tpu.ops.quant import dequantize_kv_rows
        ks, vs = scales
        k_all = dequantize_kv_rows(k_pool[tab], ks[tab], qh.dtype)
        v_all = dequantize_kv_rows(v_pool[tab], vs[tab], qh.dtype)
    else:
        k_all, v_all = k_pool[tab], v_pool[tab]
    k_all = _pool_view(k_all, hh)
    v_all = _pool_view(v_all, hh)
    abs_q = pos + jnp.arange(s)[:, None]
    attendable = jnp.arange(L)[None, :] <= abs_q
    mask = jnp.where(attendable, 0.0, -jnp.inf).astype(jnp.float32)
    return ops.dot_product_attention(qh, k_all.astype(qh.dtype),
                                     v_all.astype(qh.dtype), mask=mask)


def _float_scatter_write(kp, vp, tab, pos, kh, vh):
    """The replicated float chunk write (one XLA row scatter through
    the table a pool), per shard on its own heads — ``_apply_paged``'s
    own expression."""
    from nezha_tpu.models.gpt2 import _float_chunk_write
    return (_float_chunk_write(kp, tab, pos, kh),
            _float_chunk_write(vp, tab, pos, vh))


def seq_prefill_attention(q, k_chunk, v_chunk, k_pool, v_pool,
                          block_tables, starts, *, mesh,
                          variant: str = "ulysses",
                          use_kernel: bool = False,
                          block_scales=None,
                          scale: Optional[float] = None,
                          interpret: Optional[bool] = None):
    """Sequence-sharded paged prefill-chunk attention + pool write.

    Same operand contract as
    :func:`~nezha_tpu.ops.pallas.flash_prefill_attention`:
    ``q/k_chunk/v_chunk [B, H, S, D]`` fresh chunk projections (global
    values under the engine's auto-partitioner trace), lane-dense pools
    ``[N, bs, H*D]`` head-sharded along the lanes
    (``P(None, None, "tp")``) on ``mesh``,
    ``block_tables [B, M]`` / ``starts [B]`` replicated host
    bookkeeping. ``starts`` must be a per-row broadcast of the chunk's
    scalar offset (the engine's chunk programs guarantee it — the
    composed mirrors index with ``starts[0]``).

    Returns the UNIFORM 6-tuple
    ``(out, k_pool', v_pool', k_scales', v_scales', qerr)`` — float
    pools pass scales through as ``None`` with ``qerr=None``; int8
    pools return fresh scales and the max-abs requant error (already
    ``pmax``-reduced over the mesh).
    """
    from nezha_tpu.parallel._compat import shard_map

    axis = "tp"
    world = int(mesh.shape[axis])
    b, H, s, d = q.shape
    _check_divisible(s, H, world)
    hh = H // world
    s_loc = s // world
    bs_kv = k_pool.shape[1]
    m = block_tables.shape[1]
    L = m * bs_kv
    quant = block_scales is not None
    if variant not in ("ulysses", "ring"):
        raise ValueError(f"unknown seq-prefill variant {variant!r}")
    if variant == "ulysses" and H % world:
        raise ValueError(
            f"ulysses needs num_heads ({H}) divisible by mesh ({world})")

    sspec = P(None, None, axis, None)   # activations: sequence axis
    hspec = P(None, axis)               # scales [N, H]: head axis
    pspec = P(None, None, axis)         # pools [N, bs, H*D]: the lanes
    rep = P()

    def seq_to_heads(x):
        # [b, H, s/M, d] local -> [b, H/M, s, d]: the ulysses move.
        return lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                              tiled=True)

    def heads_to_seq(x):
        return lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                              tiled=True)

    tab32 = jnp.asarray(block_tables, jnp.int32)
    starts32 = jnp.asarray(starts, jnp.int32)

    if variant == "ulysses":
        return _ulysses(q, k_chunk, v_chunk, k_pool, v_pool, tab32,
                        starts32, block_scales, shard_map, mesh, axis,
                        sspec, hspec, pspec, rep, seq_to_heads,
                        heads_to_seq, use_kernel=use_kernel, scale=scale,
                        interpret=interpret, L=L)
    return _ring(q, k_chunk, v_chunk, k_pool, v_pool, tab32, starts32,
                 block_scales, shard_map, mesh, axis, sspec, hspec,
                 pspec, rep, world=world, hh=hh, s_loc=s_loc,
                 use_kernel=use_kernel, scale=scale,
                 interpret=interpret, L=L, d=d)


def _ulysses(q, k, v, kp, vp, tab, starts, block_scales, shard_map,
             mesh, axis, sspec, hspec, pspec, rep, seq_to_heads,
             heads_to_seq, *, use_kernel, scale, interpret, L):
    """All-to-all variant: per shard, the EXACT replicated computation
    on its own head group — bitwise parity by construction."""
    from nezha_tpu.ops.pallas import flash_prefill_attention

    if block_scales is not None:
        ks, vs = block_scales

        def body(q_, k_, v_, kp_, vp_, tab_, st_, ks_, vs_):
            qh, kh, vh = (seq_to_heads(q_), seq_to_heads(k_),
                          seq_to_heads(v_))
            if use_kernel:
                out, kp_n, vp_n, ks_n, vs_n, qerr = \
                    flash_prefill_attention(
                        qh, kh, vh, kp_, vp_, tab_, st_, scale=scale,
                        interpret=interpret, block_scales=(ks_, vs_))
            else:
                from nezha_tpu.models.gpt2 import _quant_prefill_write
                pos = st_[0]
                sc = kh.shape[2]
                kp_n, ks_n, ek = _quant_prefill_write(kp_, ks_, tab_,
                                                      pos, kh, sc)
                vp_n, vs_n, ev = _quant_prefill_write(vp_, vs_, tab_,
                                                      pos, vh, sc)
                qerr = jnp.maximum(ek, ev)
                out = _composed_shard_attention(
                    qh, kp_n, vp_n, tab_, pos, (ks_n, vs_n), L=L)
            return (heads_to_seq(out), kp_n, vp_n, ks_n, vs_n,
                    lax.pmax(qerr, axis))

        f = shard_map(body, mesh=mesh,
                      in_specs=(sspec, sspec, sspec, pspec, pspec, rep,
                                rep, hspec, hspec),
                      out_specs=(sspec, pspec, pspec, hspec, hspec,
                                 rep))
        out, kp_n, vp_n, ks_n, vs_n, qerr = f(q, k, v, kp, vp, tab,
                                              starts, ks, vs)
        return out, kp_n, vp_n, ks_n, vs_n, qerr

    def body(q_, k_, v_, kp_, vp_, tab_, st_):
        qh, kh, vh = (seq_to_heads(q_), seq_to_heads(k_),
                      seq_to_heads(v_))
        pos = st_[0]
        kp_n, vp_n = _float_scatter_write(kp_, vp_, tab_, pos, kh, vh)
        if use_kernel:
            out = flash_prefill_attention(qh, kh, vh, kp_n, vp_n, tab_,
                                          st_, scale=scale,
                                          interpret=interpret)
        else:
            out = _composed_shard_attention(qh, kp_n, vp_n, tab_, pos,
                                            None, L=L)
        return heads_to_seq(out), kp_n, vp_n

    f = shard_map(body, mesh=mesh,
                  in_specs=(sspec, sspec, sspec, pspec, pspec, rep,
                            rep),
                  out_specs=(sspec, pspec, pspec))
    out, kp_n, vp_n = f(q, k, v, kp, vp, tab, starts)
    return out, kp_n, vp_n, None, None, None


def _ring(q, k, v, kp, vp, tab, starts, block_scales, shard_map, mesh,
          axis, sspec, hspec, pspec, rep, *, world, hh, s_loc, use_kernel,
          scale, interpret, L, d):
    """Neighbour-hop variant. Float + kernel circulates Q blocks
    ("ring-q", bitwise); otherwise the gathered own-head paged prefix
    circulates and merges with the chunk's ring attention by
    log-sum-exp ("ring-KV", greedy parity)."""
    from nezha_tpu.ops.pallas import flash_prefill_attention
    from nezha_tpu.parallel.ring import _NEG_BIG, ring_attention_lse

    perm = [(i, (i + 1) % world) for i in range(world)]
    quant = block_scales is not None
    b = q.shape[0]
    s = q.shape[2]

    def head_domain(x):
        return lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                              tiled=True)

    if not quant and use_kernel:
        # ring-q: the traveling Q block meets each shard's resident
        # head group exactly once; q_offsets puts the kernel's causal
        # diagonal at the block's GLOBAL offset, so every (Q block,
        # head group) result is complete — no merge, bitwise parity.
        def body(q_, k_, v_, kp_, vp_, tab_, st_):
            idx = lax.axis_index(axis)
            kh, vh = head_domain(k_), head_domain(v_)
            pos = st_[0]
            kp_n, vp_n = _float_scatter_write(kp_, vp_, tab_, pos, kh,
                                              vh)

            def hop(i, carry):
                q_cur, o_cur = carry
                src = (idx - i) % world
                q_sl = lax.dynamic_slice(
                    q_cur, (0, idx * hh, 0, 0), (b, hh, s_loc, d))
                o_i = flash_prefill_attention(
                    q_sl, kh, vh, kp_n, vp_n, tab_, st_, scale=scale,
                    interpret=interpret,
                    q_offsets=st_ + src * s_loc)
                o_cur = lax.dynamic_update_slice(
                    o_cur, o_i.astype(o_cur.dtype), (0, idx * hh, 0, 0))
                # The collective stays OUTSIDE any conditional — every
                # rank participates every hop (ring.py's rule).
                return (lax.ppermute(q_cur, axis, perm),
                        lax.ppermute(o_cur, axis, perm))

            _, out = lax.fori_loop(0, world, hop,
                                   (q_, jnp.zeros_like(q_)))
            return out, kp_n, vp_n

        f = shard_map(body, mesh=mesh,
                      in_specs=(sspec, sspec, sspec, pspec, pspec, rep,
                                rep),
                      out_specs=(sspec, pspec, pspec))
        out, kp_n, vp_n = f(q, k, v, kp, vp, tab, starts)
        return out, kp_n, vp_n, None, None, None

    # ring-KV composed: write first (the replicated composed ordering),
    # ring the chunk's self-attention over fresh operands, ring the
    # gathered own-head prefix, merge by log-sum-exp.
    def body(q_, k_, v_, kp_, vp_, tab_, st_, *scargs):
        idx = lax.axis_index(axis)
        kh, vh = head_domain(k_), head_domain(v_)
        pos = st_[0]
        if quant:
            from nezha_tpu.models.gpt2 import _quant_prefill_write
            ks_, vs_ = scargs
            kp_n, ks_n, ek = _quant_prefill_write(kp_, ks_, tab_, pos,
                                                  kh, s)
            vp_n, vs_n, ev = _quant_prefill_write(vp_, vs_, tab_, pos,
                                                  vh, s)
            qerr = lax.pmax(jnp.maximum(ek, ev), axis)
            from nezha_tpu.ops.quant import dequantize_kv_rows
            kd = dequantize_kv_rows(kp_n[tab_], ks_n[tab_], q_.dtype)
            vd = dequantize_kv_rows(vp_n[tab_], vs_n[tab_], q_.dtype)
        else:
            kp_n, vp_n = _float_scatter_write(kp_, vp_, tab_, pos, kh,
                                              vh)
            kd = kp_n[tab_].astype(q_.dtype)
            vd = vp_n[tab_].astype(q_.dtype)
        # Own-head dense prefix view [b, hh, L, d] — this is the block
        # that circulates ("ring-passed paged K/V").
        from nezha_tpu.models.gpt2 import _pool_view
        kd = _pool_view(kd, hh)
        vd = _pool_view(vd, hh)

        # Chunk part: parallel/ring.py's online-softmax hop fold over
        # the fresh seq-sharded operands (all heads, local queries).
        out_c, lse_c = ring_attention_lse(q_, k_, v_, axis,
                                          causal=True, scale=scale,
                                          use_flash=False)

        sc = scale if scale is not None else 1.0 / (d ** 0.5)
        prefix_len = st_[:, None, None, None]                # [b,1,1,1]
        kpos = jnp.arange(L)[None, None, None, :]

        def hop(i, carry):
            mx, l, acc, kd_cur, vd_cur = carry
            # After i hops the resident block covers head group src.
            src = (idx - i) % world
            q_h = lax.dynamic_slice(q_, (0, src * hh, 0, 0),
                                    (b, hh, s_loc, d))
            scores = jnp.einsum(
                "bhqd,bhkd->bhqk", q_h, kd_cur,
                preferred_element_type=jnp.float32) * sc
            attendable = kpos < prefix_len
            scores = jnp.where(attendable, scores, _NEG_BIG)
            m_src = jnp.max(scores, axis=-1, keepdims=True)
            # Masked lanes zero EXPLICITLY: an empty prefix would
            # otherwise see exp(_NEG_BIG - _NEG_BIG) = 1 per lane.
            p = jnp.where(attendable, jnp.exp(scores - m_src), 0.0)
            l_src = jnp.sum(p, axis=-1, keepdims=True)
            acc_src = jnp.einsum("bhqk,bhkd->bhqd",
                                 p.astype(vd_cur.dtype), vd_cur,
                                 preferred_element_type=jnp.float32)
            at = (0, src * hh, 0, 0)
            mx = lax.dynamic_update_slice(mx, m_src, at)
            l = lax.dynamic_update_slice(l, l_src, at)
            acc = lax.dynamic_update_slice(acc, acc_src, at)
            return (mx, l, acc, lax.ppermute(kd_cur, axis, perm),
                    lax.ppermute(vd_cur, axis, perm))

        H_all = q_.shape[1]
        m0 = jnp.full((b, H_all, s_loc, 1), _NEG_BIG, jnp.float32)
        l0 = jnp.zeros((b, H_all, s_loc, 1), jnp.float32)
        a0 = jnp.zeros((b, H_all, s_loc, d), jnp.float32)
        mx, l, acc, _, _ = lax.fori_loop(0, world, hop,
                                         (m0, l0, a0, kd, vd))
        out_p = acc / jnp.maximum(l, 1e-30)
        lse_p = (mx + jnp.log(jnp.maximum(l, 1e-30)))[..., 0]

        # Log-sum-exp merge: an empty prefix carries lse_p ~ -1e30, so
        # its weight underflows to exactly zero.
        lse_t = jnp.logaddexp(lse_p, lse_c)
        w_p = jnp.exp(lse_p - lse_t)[..., None]
        w_c = jnp.exp(lse_c - lse_t)[..., None]
        out = (out_p * w_p
               + out_c.astype(jnp.float32) * w_c).astype(q_.dtype)
        if quant:
            return out, kp_n, vp_n, ks_n, vs_n, qerr
        return out, kp_n, vp_n

    if quant:
        ks, vs = block_scales
        f = shard_map(body, mesh=mesh,
                      in_specs=(sspec, sspec, sspec, pspec, pspec, rep,
                                rep, hspec, hspec),
                      out_specs=(sspec, pspec, pspec, hspec, hspec,
                                 rep))
        out, kp_n, vp_n, ks_n, vs_n, qerr = f(q, k, v, kp, vp, tab,
                                              starts, ks, vs)
        return out, kp_n, vp_n, ks_n, vs_n, qerr
    f = shard_map(body, mesh=mesh,
                  in_specs=(sspec, sspec, sspec, pspec, pspec, rep,
                            rep),
                  out_specs=(sspec, pspec, pspec))
    out, kp_n, vp_n = f(q, k, v, kp, vp, tab, starts)
    return out, kp_n, vp_n, None, None, None
