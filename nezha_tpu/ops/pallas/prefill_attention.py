"""Paged flash-prefill: chunked prompt attention against a block pool,
with the int8 cache write fused into the kernel epilogue.

The serving prefill path processes one bucket-width chunk of prompt at a
traced offset ``start``: its queries attend the row's cached prefix
``[0, start)`` (earlier chunks / a shared-prefix hit, reached through
the block table) plus the chunk itself causally. The composed path
gathers the WHOLE pool per layer (``k_pool[tab]`` — ``M * bs``
bandwidth whatever the prefix depth), materializes an ``[S, L]`` mask,
and on int8 pools pays a separate gather→dequant→insert→requant→scatter
chain per written block (``models/gpt2._quant_prefill_write``). This
kernel is built for the actual access pattern:

- the pool is LANE-DENSE, ``[N, bs, H*D]`` (a position a row, head
  ``h`` in lanes ``h*D .. (h+1)*D``), and a TPU block's minor dimension
  is whole 128-lane tiles: a grid step holds the ``g`` heads of one
  lane block (two 64-wide heads for GPT-2; :func:`_heads_per_step`)
  and takes each head's lanes by a static slice;
- grid ``(B, H / g, Q-tiles, M + 1)`` with the KV axis sequential: steps
  ``t < M`` fold pool block ``t`` (gathered through the scalar-
  prefetched block table, exactly the decode kernel's index map) into
  the shared online-softmax scratch, masked to the PREFIX ``[0, start)``
  and skipped entirely once ``t*bs >= start`` — prefix work tracks the
  row's real depth, not the table capacity; the final step folds the
  chunk's own K/V causally from the fresh operands (the pool is never
  read at chunk positions, so the attention is independent of whether
  the chunk write landed yet);
- ``start`` rides per-row as a second scalar-prefetch operand, so
  chunked continuation and shared-prefix partial prefills (nonzero
  start) are the SAME compiled program as a cold start — the engine's
  one-program-per-bucket contract;
- on int8 pools the block write FUSES into the epilogue: during the
  last Q-tile sweep each touched pool block is merged in-VMEM (old
  dequantized content below ``start`` — the block was just gathered for
  prefix attention anyway — chunk values in ``[start, start+S)``, zeros
  after: stale previous-occupant garbage must never set the new absmax),
  requantized with a fresh per-(block, head) fp32 scale, and scattered
  through a table-indexed OUTPUT BlockSpec aliased onto the pool.
  Non-writing grid steps route the output index map to the scratch
  block (block 0 — the same over-cover routing
  ``_quant_prefill_write`` uses) with zeroed content and unit scale.
  The whole ``_quant_prefill_write`` chain collapses into the
  attention kernel: one program, no pool-sized gather/scatter round
  trip.

The quantization policy is ``ops.quant.quantize_kv_block`` verbatim
(sanitize → absmax/127 with the zero guard → round/clip), and the
max-abs dequant error over the written span comes back as a
``[B, H]`` output so the engine keeps feeding ``serve.kv.quant_error``.

Aliased-write ordering: writes happen only in the LAST Q-tile sweep,
each touched block is read (for the old-content merge) at the same
sequential step that writes it, and the KV axis only moves forward —
no step ever re-reads a block a previous step wrote. Rows of one call
must not share touched blocks (the engine prefills one row per
program; prefix blocks are read-only and may be shared freely).

``interpret=None`` auto-selects the Pallas interpreter off-TPU, so CPU
tests exercise the same kernel code that compiles on hardware.
Inference-only: no VJP. ``models/gpt2.py`` routes its paged
prefill-chunk branch here behind ``GPT2Config.prefill_impl``
(``"xla"`` is the composed masked path).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from nezha_tpu.ops.pallas.common import (
    LANES,
    NEG_BIG,
    block_scale,
    block_step,
    gather_row_scales,
    pick_block,
    resolve_interpret,
    scratch_init,
    softmax_block_update,
)
from nezha_tpu.ops.quant import QMAX, SATURATE_MAX

_Q_TILE_TARGET = 256   # q rows per tile (divisor-clamped to the chunk)
_KC_TILE_TARGET = 256  # chunk-KV rows per self-attention tile

_PREFILL_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary"))


def _tile_max(x):
    """Max over a 2-D tile as ``[1, 1]`` (lanes, then sublanes): Mosaic
    keeps reductions as vectors — a scalar cannot be stored to VMEM."""
    return jnp.max(jnp.max(x, axis=1, keepdims=True), axis=0, keepdims=True)


def _heads_per_step(h: int, d: int) -> int:
    """Heads one grid step holds: those of ONE LANE BLOCK of the
    lane-dense pool ``[N, bs, H*D]``. A TPU block's minor dimension is
    whole 128-lane tiles or the whole array's, so a step takes the
    fewest whole heads that fill 128 lanes (GPT-2: two 64-wide heads),
    one head when ``D`` is itself whole tiles, and every head where the
    heads do not tile 128 lanes (the tiny CPU models; three heads of a
    four-way head shard)."""
    if d % LANES == 0:
        return 1
    if LANES % d == 0 and (h * d) % LANES == 0:
        return LANES // d
    return h


def _head_scratch(scratch, g):
    """The online-softmax scratch of a step's ``g`` heads as ``g``
    ``(m, l, acc)`` triples. Each head has 2-D buffers of its own: a
    view of one head out of a ``(g, rows, D)`` buffer is a slice below
    a lane tile where ``D`` is under 128, which Mosaic refuses."""
    return [tuple(scratch[k * g + j] for k in range(3)) for j in range(g)]


def _finalize_head(o_ref, j, scr):
    """``common.softmax_finalize`` for head ``j`` of the step's output
    block ``(1, g, bq, D)`` from the head's ``(m, l, acc)`` scratch (no
    lse: inference only)."""
    _, l_scr, acc_scr = scr
    denom = jnp.maximum(l_scr[:, :1], 1e-30)
    o_ref[0, j] = (acc_scr[:] / denom).astype(o_ref.dtype)


def _head_lanes(tile, j, d):
    """Head ``j``'s ``D`` lanes of a pool tile ``[bs, g*D]`` (a static
    lane slice)."""
    return tile[:, j * d:(j + 1) * d]


def _chunk_self_attention(j, qi, q_ref, kc_ref, vc_ref, m_scr, l_scr,
                          acc_scr, *, scale, block_q, block_kc, s_chunk,
                          cast_dtype, qoff=None):
    """Fold the chunk's own K/V causally for head ``j`` of the step
    (chunk-local positions — the shared ``start`` offset cancels out of
    the causal comparison). ``cast_dtype`` routes the fresh tiles
    through the pool's storage dtype first so a bf16 pool attends
    exactly the values the composed path reads back after its write.
    ``qi`` is passed in (program ids must be read at kernel top level,
    outside any ``pl.when`` body); the scratch refs are head ``j``'s.

    ``qoff`` (traced per-row scalar, or None) shifts the queries by a
    GLOBAL offset relative to the chunk's start: query ``i`` sits at
    chunk-local position ``i + qoff``, so the causal comparison runs in
    global coordinates — the sequence-sharded prefill path hands each
    mesh shard a SLICE of the chunk's queries against the full chunk
    K/V. ``None`` keeps the statically-skipped diagonal."""
    q = q_ref[0, j]                                          # [bq, d]
    for kj in range(s_chunk // block_kc):
        if qoff is None:
            # Tiles strictly above this q tile's causal diagonal are
            # skipped at TRACE time — a static Python bool.
            run = kj * block_kc <= qi * block_q + block_q - 1
        else:
            # The diagonal moves with the traced offset: the skip is a
            # per-program predicate, still zero work for future tiles.
            run = kj * block_kc <= qi * block_q + block_q - 1 + qoff

        @pl.when(run)
        def _tile(kj=kj):
            k = kc_ref[0, j, kj * block_kc:(kj + 1) * block_kc, :]
            v = vc_ref[0, j, kj * block_kc:(kj + 1) * block_kc, :]
            if cast_dtype is not None:
                k = k.astype(cast_dtype)
                v = v.astype(cast_dtype)
            k = k.astype(q.dtype)
            v = v.astype(q.dtype)
            s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            qpos = qi * block_q + lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            if qoff is not None:
                qpos = qpos + qoff
            kpos = kj * block_kc + lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(kpos <= qpos, s, NEG_BIG)
            softmax_block_update(s, v, m_scr, l_scr, acc_scr)


def _prefill_kernel(tab_ref, start_ref, *refs, scale, s_chunk, block_q,
                    block_kc, bs, m, cast_dtype, with_qoff=False):
    """bf16/float pool variant: attention only (the float chunk write is
    a single cheap XLA scatter the caller keeps). One step holds the
    ``g`` heads of one lane block of the pool (:func:`_heads_per_step`):
    ``q_ref``/``o_ref`` ``(1, g, bq, D)``, the chunk ``(1, g, S, D)``,
    a pool block ``(1, bs, g*D)``.

    ``with_qoff``: a third scalar-prefetch operand carries PER-ROW
    GLOBAL QUERY OFFSETS: query ``i`` of row ``b`` sits at absolute
    position ``qoffs[b] + i`` while the chunk K/V operands occupy
    ``[starts[b], starts[b] + s_chunk)`` and the pool prefix
    ``[0, starts[b])``. Requires ``qoffs >= starts`` (every query
    postdates the whole prefix, so the prefix fold needs no extra
    mask). This is the sequence-sharded prefill building block: one
    mesh shard's slice of the chunk's queries runs ONE program against
    the full chunk + its local pool shard, per (mesh, bucket)."""
    qoff_ref = None
    if with_qoff:
        qoff_ref, *refs = refs
    q_ref, kc_ref, vc_ref, kp_ref, vp_ref, o_ref, *scratch = refs
    heads = _head_scratch(scratch, q_ref.shape[1])
    b_ = pl.program_id(0)
    qi = pl.program_id(2)
    t = pl.program_id(3)
    start = start_ref[b_]
    # chunk-local offset of query 0
    qoff = qoff_ref[b_] - start if with_qoff else None
    d = q_ref.shape[3]

    @pl.when(t == 0)
    def _init():
        for scr in heads:
            scratch_init(*scr)

    # Prefix pool block: masked to [0, start) and skipped entirely once
    # the block starts at/past the row's prefix depth.
    @pl.when((t < m) & (t * bs < start))
    def _prefix():
        kp, vp = kp_ref[0], vp_ref[0]                        # [bs, g*D]
        for j, scr in enumerate(heads):
            block_step(q_ref[0, j], _head_lanes(kp, j, d),
                       _head_lanes(vp, j, d), start, t, *scr,
                       scale=scale, block_k=bs)

    @pl.when(t == m)
    def _chunk():
        for j, scr in enumerate(heads):
            _chunk_self_attention(j, qi, q_ref, kc_ref, vc_ref, *scr,
                                  scale=scale, block_q=block_q,
                                  block_kc=block_kc, s_chunk=s_chunk,
                                  cast_dtype=cast_dtype, qoff=qoff)
            _finalize_head(o_ref, j, scr)


def _quant_merge(wpos, start, s_chunk, old_deq, stage, ci, bs):
    """Merge one touched block of one head (old prefix / fresh chunk /
    stale-zero) and requantize with a fresh absmax scale —
    ``ops.quant.quantize_kv_block`` verbatim. Returns ``(q, scale,
    err)``: the int8-valued fp32 tile ``[bs, D]``, and as ``[1, 1]``
    tiles the block's new scale and the max-abs dequant error over the
    written span (``serve.kv.quant_error``'s sample)."""
    fresh = stage[pl.ds(ci, bs), :]
    merged = jnp.where(wpos < start, old_deq, fresh)         # [bs, d]
    merged = jnp.nan_to_num(merged, nan=0.0, posinf=SATURATE_MAX,
                            neginf=-SATURATE_MAX)
    amax = _tile_max(jnp.abs(merged))
    sc = jnp.where(amax > 0, amax / QMAX, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(merged / sc), -QMAX, QMAX)
    err = jnp.abs(merged - q * sc)
    return q, sc, _tile_max(jnp.where(wpos < start + s_chunk, err, 0.0))


def _quant_prefill_kernel(tab_ref, start_ref, q_ref, kc_ref, vc_ref,
                          kp_ref, vp_ref, ks_ref, vs_ref, o_ref,
                          kp_out, vp_out, ks_out, vs_out, qerr_ref,
                          *scratch, scale, s_chunk, block_q, block_kc,
                          bs, m):
    """Int8 pool variant: prefix blocks dequantize in the block loop
    (``ops.quant.dequantize_kv_rows``'s expression — kernel and XLA
    fallback see identical tiles) and the chunk write fuses into the
    epilogue. A step holds the ``g`` heads of one lane block of the
    pool, so a written tile ``(1, bs, g*D)`` is touched by one step
    only; the scales ride ``(1, 1, g, M)``. Scratch: the heads'
    softmax triples (:func:`_head_scratch`), then ``g`` K and ``g`` V
    stage buffers, then the qerr row."""
    b_ = pl.program_id(0)
    qi = pl.program_id(2)
    t = pl.program_id(3)
    nq = pl.num_programs(2)
    start = start_ref[b_]
    last_q = qi == nq - 1
    g, d = q_ref.shape[1], q_ref.shape[3]
    heads = _head_scratch(scratch[:3 * g], g)
    k_stage, v_stage = scratch[3 * g:4 * g], scratch[4 * g:5 * g]
    qerr_scr = scratch[5 * g]

    @pl.when(t == 0)
    def _init():
        for scr in heads:
            scratch_init(*scr)

    @pl.when((qi == 0) & (t == 0))
    def _row_init():
        qerr_scr[:] = jnp.zeros_like(qerr_scr)
        # The new-scale rows start as the old ones; write steps replace
        # the touched lanes (the block stays resident for the whole
        # (row, head group)).
        ks_out[0, 0] = ks_ref[0, 0]
        vs_out[0, 0] = vs_ref[0, 0]

    @pl.when(last_q & (t == 0))
    def _stage():
        # The chunk staged fp32 into a zero-padded buffer: touched
        # blocks slice their rows at a traced offset, and rows past the
        # chunk end read the stale-position zeros for free.
        for chunk_ref, stages in ((kc_ref, k_stage), (vc_ref, v_stage)):
            for j, stage in enumerate(stages):
                stage[:] = jnp.zeros_like(stage)
                stage[bs:bs + s_chunk, :] = chunk_ref[0, j].astype(
                    jnp.float32)

    def dequant(pool_ref, scale_ref):
        """The step's pool tile per head: ``g`` fp32 ``[bs, D]`` tiles."""
        tile = pool_ref[0].astype(jnp.float32)               # [bs, g*D]
        sc = block_scale(scale_ref, t)                       # [g, 1]
        return [_head_lanes(tile, j, d) * sc[j:j + 1] for j in range(g)]

    @pl.when((t < m) & (t * bs < start))
    def _prefix():
        ks, vs = dequant(kp_ref, ks_ref), dequant(vp_ref, vs_ref)
        for j, scr in enumerate(heads):
            q = q_ref[0, j]
            block_step(q, ks[j].astype(q.dtype), vs[j].astype(q.dtype),
                       start, t, *scr, scale=scale, block_k=bs)

    wb0 = start // bs
    wb1 = (start + s_chunk - 1) // bs
    writing = last_q & (t < m) & (t >= wb0) & (t <= wb1)

    @pl.when(writing)
    def _write():
        wpos = t * bs + lax.broadcasted_iota(jnp.int32, (bs, 1), 0)
        ci = t * bs - start + bs                 # stage offset, >= 0
        lane = lax.broadcasted_iota(jnp.int32, ks_out[0, 0].shape, 1)
        head = lax.broadcasted_iota(jnp.int32, ks_out[0, 0].shape, 0)
        err = qerr_scr[:]
        for pool_ref, scale_ref, stages, pool_out, scale_out in (
                (kp_ref, ks_ref, k_stage, kp_out, ks_out),
                (vp_ref, vs_ref, v_stage, vp_out, vs_out)):
            tiles = []
            new_scales = scale_out[0, 0]                     # [g, M]
            for j, old in enumerate(dequant(pool_ref, scale_ref)):
                q, sc, e = _quant_merge(wpos, start, s_chunk, old,
                                        stages[j], ci, bs)
                tiles.append(q)
                new_scales = jnp.where((lane == t) & (head == j), sc,
                                       new_scales)
                err = jnp.maximum(err, e)
            pool_out[0] = jnp.concatenate(tiles, axis=-1).astype(
                pool_out.dtype)
            scale_out[0, 0] = new_scales
        qerr_scr[:] = err

    @pl.when(~writing)
    def _scratch_route():
        # Non-writing steps land on the scratch block (the output index
        # map routed them there) with zero content — what
        # _quant_prefill_write's over-cover rows scatter.
        kp_out[0] = jnp.zeros_like(kp_out[0])
        vp_out[0] = jnp.zeros_like(vp_out[0])

    @pl.when(t == m)
    def _chunk():
        for j, scr in enumerate(heads):
            _chunk_self_attention(j, qi, q_ref, kc_ref, vc_ref, *scr,
                                  scale=scale, block_q=block_q,
                                  block_kc=block_kc, s_chunk=s_chunk,
                                  cast_dtype=None)
            _finalize_head(o_ref, j, scr)
        # The qerr output's index never moves within (b, head group):
        # the last write before the flush — the final q sweep's — wins.
        qerr_ref[0, 0] = qerr_scr[:]


def _prefill_call(q, k_chunk, v_chunk, k_pool, v_pool, block_tables,
                  starts, scale, interpret, block_scales=None,
                  q_offsets=None):
    """Build and run one of the three programs: float pool (attention
    only), float pool with per-row global query offsets (``q_offsets``:
    the query extent ``S_q`` may then differ from the chunk-K/V extent
    ``S_kc`` — a sequence shard holds ``S_kc / world`` queries against
    the full chunk — keyed by its own (S_q, S_kc, M, bs, D) signature),
    int8 pool with the fused block write."""
    b, h, s_q, d = q.shape
    s_chunk = k_chunk.shape[2]
    bs = k_pool.shape[1]
    m = block_tables.shape[1]
    g = _heads_per_step(h, d)
    nq_block = pick_block(s_q, _Q_TILE_TARGET)
    nkc_block = pick_block(s_chunk, _KC_TILE_TARGET)
    nq = s_q // nq_block
    quant = block_scales is not None

    tab = jnp.asarray(block_tables, jnp.int32)
    starts32 = jnp.asarray(starts, jnp.int32)
    prefetch = [tab, starts32]
    if q_offsets is not None:
        prefetch.append(jnp.asarray(q_offsets, jnp.int32))

    # Index maps take (b, head group, q tile, t, *scalar-prefetch refs).
    q_spec = pl.BlockSpec((1, g, nq_block, d),
                          lambda b_, g_, qi, t, *_: (b_, g_, qi, 0))
    chunk_spec = pl.BlockSpec((1, g, s_chunk, d),
                              lambda b_, g_, qi, t, *_: (b_, g_, 0, 0))
    pool_spec = pl.BlockSpec(
        (1, bs, g * d), lambda b_, g_, qi, t, tab, *_:
        (tab[b_, jnp.minimum(t, m - 1)], 0, g_))
    scratch = ([pltpu.VMEM((nq_block, LANES), jnp.float32)] * (2 * g)
               + [pltpu.VMEM((nq_block, d), jnp.float32)] * g)
    grid = (b, h // g, nq, m + 1)
    static = dict(scale=scale, s_chunk=s_chunk, block_q=nq_block,
                  block_kc=nkc_block, bs=bs, m=m)

    if not quant:
        kernel = functools.partial(
            _prefill_kernel, cast_dtype=k_pool.dtype,
            with_qoff=q_offsets is not None, **static)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=grid,
            in_specs=[q_spec, chunk_spec, chunk_spec, pool_spec,
                      pool_spec],
            out_specs=q_spec,
            scratch_shapes=scratch,
        )
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            compiler_params=_PREFILL_PARAMS,
            interpret=interpret,
            name=("nezha_prefill_attention_qoff" if q_offsets is not None
                  else "nezha_prefill_attention_paged"),
        )(*prefetch, q, k_chunk, v_chunk, k_pool, v_pool)

    ks, vs = (jnp.asarray(sc, jnp.float32) for sc in block_scales)
    kernel = functools.partial(_quant_prefill_kernel, **static)

    def _write_blk(b_, qi, t, tab, starts):
        start = starts[b_]
        wb0 = start // bs
        wb1 = (start + s_chunk - 1) // bs
        touched = ((qi == nq - 1) & (t < m) & (t >= wb0) & (t <= wb1))
        return jnp.where(touched, tab[b_, jnp.minimum(t, m - 1)], 0)

    # Per-(row, head group) lane vectors: the row's M block scales in,
    # the row's M (possibly rewritten) block scales out, and the qerr
    # sample.
    row_spec = pl.BlockSpec((1, 1, g, m),
                            lambda b_, g_, qi, t, *_: (b_, g_, 0, 0))
    qerr_spec = pl.BlockSpec((1, 1, 1, LANES),
                             lambda b_, g_, qi, t, *_: (b_, g_, 0, 0))
    pool_out_spec = pl.BlockSpec(
        (1, bs, g * d), lambda b_, g_, qi, t, tab, starts:
        (_write_blk(b_, qi, t, tab, starts), 0, g_))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[q_spec, chunk_spec, chunk_spec, pool_spec, pool_spec,
                  row_spec, row_spec],
        out_specs=[q_spec, pool_out_spec, pool_out_spec, row_spec,
                   row_spec, qerr_spec],
        scratch_shapes=(
            scratch
            + [pltpu.VMEM((s_chunk + 2 * bs, d), jnp.float32)] * (2 * g)
            + [pltpu.VMEM((1, LANES), jnp.float32)]),
    )
    rows_shape = jax.ShapeDtypeStruct((b, h // g, g, m), jnp.float32)
    out, kp_new, vp_new, ks_rows, vs_rows, qerr = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
            jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype),
            rows_shape, rows_shape,
            jax.ShapeDtypeStruct((b, h // g, 1, LANES), jnp.float32),
        ],
        # Operand order: tab(0) starts(1) q(2) kc(3) vc(4) kp(5) vp(6):
        # the pools alias their outputs so the fused write is in place
        # (untouched blocks keep their data).
        input_output_aliases={5: 1, 6: 2},
        compiler_params=_PREFILL_PARAMS,
        interpret=interpret,
        name="nezha_prefill_attention_paged_int8",
    )(tab, starts32, q, k_chunk, v_chunk, k_pool, v_pool,
      gather_row_scales(ks, tab, g), gather_row_scales(vs, tab, g))

    # Scatter the touched blocks' new scales into the [N, H] buffers: a
    # STATIC window of table entries from the first written block — the
    # ceil(S/bs)+1 a chunk can touch plus one that never is. Untouched
    # entries route to the scratch block, which the kernel zeroed on
    # every non-writing step and which therefore always takes unit scale.
    n_win = min((s_chunk - 1) // bs + 2, m) + 1
    tbi = (starts32 // bs)[:, None] + jnp.arange(n_win)[None, :]  # [B, T]
    touched = tbi <= ((starts32 + s_chunk - 1) // bs)[:, None]
    tbi = jnp.clip(tbi, 0, m - 1)
    blks = jnp.where(touched, jnp.take_along_axis(tab, tbi, axis=1), 0)

    def scatter(scales, rows):
        new = jnp.take_along_axis(rows.reshape(b, h, m), tbi[:, None, :],
                                  axis=2)                    # [B, H, T]
        new = jnp.where(touched[:, None, :], new, 1.0)
        return scales.at[blks].set(new.transpose(0, 2, 1))

    return (out, kp_new, vp_new, scatter(ks, ks_rows),
            scatter(vs, vs_rows), jnp.max(qerr))


def flash_prefill_attention(q, k_chunk, v_chunk, k_pool, v_pool,
                            block_tables, starts,
                            scale: Optional[float] = None,
                            interpret: Optional[bool] = None,
                            block_scales=None, q_offsets=None):
    """Paged prefill-chunk attention (+ fused int8 write).

    ``q``/``k_chunk``/``v_chunk`` ``[B, H, S, D]`` are the fresh
    chunk's projections; ``k_pool``/``v_pool`` ``[N, bs, H*D]`` the
    row's lane-dense KV block pools (a position a row, head ``h`` in
    lanes ``h*D .. (h+1)*D``) reached through ``block_tables [B, M]``
    int32;
    ``starts [B]`` int32 is each row's chunk offset (query ``i`` sits
    at absolute position ``starts[b] + i`` and attends the cached
    prefix ``[0, starts[b])`` plus the chunk causally).

    Float pools -> ``out [B, H, S, D]``: attention only — the caller
    keeps its one-scatter chunk write (the fresh tiles are routed
    through the pool dtype in-kernel, so the output matches the
    composed gather-after-write path bit-for-bit in what it attends).

    Int8 pools (``block_scales=(k_scales, v_scales)`` ``[N, H]`` fp32)
    -> ``(out, k_pool', v_pool', k_scales', v_scales', qerr)``: the
    chunk write is FUSED — touched blocks are merged (old prefix below
    ``start``, chunk values, stale positions zeroed), requantized with
    fresh per-(block, head) absmax scales (``ops.quant.quantize_kv_block``
    policy verbatim, sanitize included) and scattered in-kernel through
    an aliased table-indexed output; ``qerr`` is the scalar max-abs
    dequant error over the written span. Rows must not share touched
    blocks (prefix blocks may be shared — they are read-only here).

    ``starts + S`` must fit the table capacity ``M * bs``. One compiled
    program serves every ``start`` at a given (S, M, bs, D) — the
    engine's frozen program-count contract.

    ``q_offsets [B]`` int32 (float pools only) decouples the QUERY
    origin from the chunk origin: query ``i`` of row ``b`` sits at
    absolute position ``q_offsets[b] + i`` while the chunk K/V still
    occupy ``[starts[b], starts[b] + S_kc)``. ``q`` may then carry
    fewer rows than the chunk (``S_q != S_kc``) — the sequence-sharded
    prefill hands each mesh shard its slice of the chunk's queries
    against the full chunk. Requires ``starts[b] <= q_offsets[b]``
    per row (queries never predate the prefix boundary). One compiled
    program per (S_q, S_kc, M, bs, D) — chunked continuation and
    shared-prefix starts stay traced scalars.
    """
    b, h, s_chunk, d = q.shape
    if q_offsets is not None:
        if block_scales is not None:
            raise ValueError(
                "q_offsets is a read-layout feature of the float path; "
                "int8 pools fuse the block write and need the full "
                "chunk's queries resident (use the per-shard fused "
                "write on head-resharded operands instead)")
        if k_chunk.shape[:2] != q.shape[:2] \
                or k_chunk.shape[3] != d \
                or v_chunk.shape != k_chunk.shape:
            raise ValueError(
                f"chunk k/v {k_chunk.shape}/{v_chunk.shape} do not "
                f"match q {q.shape} on (B, H, D)")
    elif k_chunk.shape != q.shape or v_chunk.shape != q.shape:
        raise ValueError(
            f"chunk k/v {k_chunk.shape}/{v_chunk.shape} do not match q "
            f"{q.shape}")
    if k_pool.shape != v_pool.shape or k_pool.ndim != 3 \
            or k_pool.shape[2] != h * d:
        raise ValueError(
            f"paged k/v pools {k_pool.shape}/{v_pool.shape} do not "
            f"match q {q.shape}: want [num_blocks, block_size, H*D]")
    if block_tables.shape[0] != b:
        raise ValueError(
            f"block_tables {block_tables.shape} does not match batch "
            f"{b}")
    if block_scales is not None:
        ks, vs = block_scales
        want = (k_pool.shape[0], h)
        if tuple(ks.shape) != want or tuple(vs.shape) != want:
            raise ValueError(
                f"block_scales {ks.shape}/{vs.shape} must be "
                f"[num_blocks, H] = {want}")
    interpret = resolve_interpret(interpret)
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    return _prefill_call(q, k_chunk, v_chunk, k_pool, v_pool,
                         block_tables, starts, scale, interpret,
                         block_scales=block_scales, q_offsets=q_offsets)


def flash_prefill_attention_sharded(q, k_chunk, v_chunk, k_pool, v_pool,
                                    block_tables, starts, mesh, *,
                                    scale: Optional[float] = None,
                                    block_scales=None,
                                    interpret: Optional[bool] = None,
                                    q_offsets=None):
    """:func:`flash_prefill_attention` PER SHARD under a nested
    ``shard_map`` over the mesh's ``tp`` (head) axis — the sharded
    serve engine's prefill path, same idiom as
    ``flash_decode_attention_sharded``: heads are embarrassingly
    parallel (each head's online softmax and each head's block write
    touch only its own H slice), so q/chunks/scales shard on H and the
    lane-dense pools ``[N, bs, H*D]`` on the LANE axis (``H / tp``
    contiguous heads a shard) while the block table and per-row starts
    REPLICATE (block identities are mesh-invariant host bookkeeping). ``scale`` defaults
    per shard to ``1/sqrt(D)`` — D is untouched by head sharding."""
    from jax.sharding import PartitionSpec as P

    from nezha_tpu.parallel._compat import shard_map

    hspec = P(None, "tp")
    pspec = P(None, None, "tp")     # a paged pool: heads live in lanes
    rep = P()

    if block_scales is not None:
        ks, vs = block_scales

        def body_q(q_, kc_, vc_, kp_, vp_, t_, st_, ks_, vs_):
            out, kp_n, vp_n, ks_n, vs_n, qerr = flash_prefill_attention(
                q_, kc_, vc_, kp_, vp_, t_, st_, scale=scale,
                interpret=interpret, block_scales=(ks_, vs_))
            # Each shard's qerr covers only its own heads; the scalar
            # the engine observes is the max across the head axis.
            return out, kp_n, vp_n, ks_n, vs_n, lax.pmax(qerr, "tp")

        f = shard_map(body_q, mesh=mesh,
                      in_specs=(hspec, hspec, hspec, pspec, pspec, rep,
                                rep, hspec, hspec),
                      out_specs=(hspec, pspec, pspec, hspec, hspec,
                                 rep))
        out, kp_new, vp_new, ks_new, vs_new, qerr = f(
            q, k_chunk, v_chunk, k_pool, v_pool, block_tables, starts,
            ks, vs)
        return out, kp_new, vp_new, ks_new, vs_new, qerr

    if q_offsets is not None:
        def body_off(q_, kc_, vc_, kp_, vp_, t_, st_, qo_):
            return flash_prefill_attention(
                q_, kc_, vc_, kp_, vp_, t_, st_, scale=scale,
                interpret=interpret, q_offsets=qo_)

        f = shard_map(body_off, mesh=mesh,
                      in_specs=(hspec, hspec, hspec, pspec, pspec, rep,
                                rep, rep),
                      out_specs=hspec)
        return f(q, k_chunk, v_chunk, k_pool, v_pool, block_tables,
                 starts, q_offsets)

    def body(q_, kc_, vc_, kp_, vp_, t_, st_):
        return flash_prefill_attention(
            q_, kc_, vc_, kp_, vp_, t_, st_, scale=scale,
            interpret=interpret)

    f = shard_map(body, mesh=mesh,
                  in_specs=(hspec, hspec, hspec, pspec, pspec, rep,
                            rep),
                  out_specs=hspec)
    return f(q, k_chunk, v_chunk, k_pool, v_pool, block_tables, starts)
