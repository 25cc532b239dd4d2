"""Flash-decode: batched single-token attention against a pooled KV cache.

The serving decode step asks one question per row: attend ONE query token
over that row's cache prefix ``[0, length)``. The composed path answers it
by materializing a ``[B, 1, 1, L_max]`` additive mask and running dense
attention over the FULL pool — every row pays ``L_max`` bandwidth whatever
its depth, and the softmax round-trips a score matrix through HBM. This
kernel is built for the actual access pattern (the "Harnessing HPC
Kernels" argument from PAPERS.md: shape-specialized hot loops deserve a
kernel, not a generic lowering):

- the KV walk is sequential — the split-K layout: each step folds one
  KV block into VMEM running ``(max, sum, acc)`` scratch via online
  softmax, merged at the row's end (no score matrix, no mask tensor).
  The dense layout runs a grid ``(B, H, L / block_k)``. The paged
  layout (a lane-dense pool ``[N, bs, KVH*D]``: a block is ``bs`` whole
  rows of all heads) folds all heads of ``c`` table entries at a time
  and has TWO iteration spaces, chosen by the pool's lane width in
  :func:`_paged_call`: a grid of ONE step a row with a LOOP over the
  row's own live entries inside it, fed by manual DMA (whole 128-lane
  tiles: the serving cells), or a grid ``(B, M / c)`` (any other
  width);
- per-row ``lengths`` ride as a SCALAR-PREFETCH operand (SMEM — the TPU
  lowering refuses a ``(1, 128)`` VMEM block over ``[B, 128]``). The
  dense and the grid forms SKIP a block that starts at or past its
  row's length (``@pl.when``), but their grids do not shrink with the
  rows: every step costs the scalar core its bookkeeping whether it
  moves bytes or not (about 0.7 us a step of 8 entries on a v5e: a
  paged call of 256 rows x 64 entries read 1.45 ms with every row
  EMPTY, 1.86 ms at the serving cell's ~25 live entries a row). The
  loop form's trip count is the row's own ``ceil(length / span)``: a
  call costs what its rows hold, and short rows and inactive rows
  (``length == 0``) cost one grid step (PERF.md section 6, PR 31);
- Q·Kᵀ and P·V accumulate fp32 over the caches' native dtype (bf16 pool
  dots run at the doubled MXU rate; the softmax statistics and the
  accumulator stay fp32 throughout);
- ``interpret=None`` auto-selects the Pallas interpreter off-TPU, so CPU
  tests exercise the same kernel code that compiles on hardware
  (tests/test_tpu_compile.py compiles every variant for a v5e).

Decode is inference-only, so there is no VJP; ``models/gpt2.py`` routes
its single-token cache branch here behind the ``attn_impl="auto"``
resolution (``GPT2Config.decode_impl="xla"`` is the composed masked
path).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The online-softmax core (scratch init / block fold / finalize) is
# shared with flash_attention.py and prefill_attention.py — see
# ops/pallas/common.py. The aliases keep this module's kernel bodies
# reading as before; the math is bit-identical to the pre-factoring
# inline version.
from nezha_tpu.ops.pallas.common import (
    LANES as _LANES,
    NEG_BIG,
    block_step as _block_step,
    gather_row_scales,
    pick_block as _pick_block,
    resolve_interpret,
    scratch_init as _scratch_init,
    softmax_finalize,
)

_DECODE_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _finalize(o_ref, l_scr, acc_scr):
    """Write the normalized accumulator at the last KV block (decode
    emits no lse residual — inference only)."""
    softmax_finalize(o_ref, None, l_scr, acc_scr)


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, scale: float, block_k: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        _scratch_init(m_scr, l_scr, acc_scr)

    length = len_ref[pl.program_id(0)]
    # The block-skip that the dense masked path cannot see: blocks at or
    # past this row's length never load K/V or touch the MXU. A row with
    # length == 0 (inactive slot) runs no block at all and finalizes to
    # an all-zero output.
    run = ki * block_k < length

    @pl.when(run)
    def _block():
        _block_step(q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], length, ki,
                    m_scr, l_scr, acc_scr, scale=scale,
                    block_k=block_k)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _final():
        _finalize(o_ref, l_scr, acc_scr)


# Table entries folded into one iteration of the paged kernels (one
# ``c * block_size``-position block of the online softmax): a loop
# iteration's ``2 c`` copies, or the grid form's ``c`` K and ``c`` V
# operands. A table whose length it does not divide takes the largest
# divisor below it. Chosen on the chip with the loop alone at
# gpt2-124m.batch-gen's shapes (256 rows, ~6,400 live entries; PERF.md
# section 6, PR 31): c = 4 / 8 / 16 / 32 read 1.16 / 0.81 / 0.69 / 0.74
# ms a call (the grid form: 1.86; `out_tok_s` 6,855-6,918 -> 10,807-
# 10,937 at 16). An iteration costs ~36-41 ns a copy and a 0.6-0.75 us
# chain of matmul, reductions and exp that does not shorten with the
# span (copies alone 0.59 ms at 8 and at 16, folds alone 0.54 / 0.38):
# fewer, larger iterations win until the copies past a row's last live
# entry (they repeat it: half an iteration a row on average, a quarter
# of all copies at 16) cost more than the iterations saved. K-EXAONE's
# global call (128 rows, 128 KB entries, bound by its bytes) read 2.63 /
# 2.66 / 2.84 ms at c = 4 / 8 / 16 against the grid's 3.53: 0.2 ms of a
# 29 ms step, left for the sake of one constant.
_ENTRIES_PER_STEP = 16


def _fold_entries(q, k, v, length, ki, m_scr, l_scr, acc_scr, *,
                  scale: float, block_size: int, entries: int,
                  window: int = 0, ring: int = 0, scales=None, last=None):
    """Fold iteration ``ki`` of a row, table entries ``ki * c ..
    ki * c + c - 1`` stacked as ``k`` / ``v`` ``[span, KVH*D]`` (``span
    = c * bs``), into the row's running ``(max, sum, acc)``: THE body of
    both iteration spaces of the paged decode kernel.

    No lane is sliced per head. ``q`` is the query laid out
    BLOCK-DIAGONALLY, ``[H, KVH*D]``: row ``h`` holds head ``h``'s ``D``
    values in the lanes of its K/V head and zeros elsewhere, so ONE
    matmul ``q_bd @ K^T`` gives every head's scores ``[H, span]`` (the
    zeros add nothing: exact), and ``p @ V`` gives ``[H, KVH*D]`` whose
    diagonal ``D``-lane blocks are the heads' outputs (the off-diagonal
    blocks are finite junk that is never read). The MXU takes each K/V
    byte once, as per-head ``M = 1`` matmuls did; on a v5e this form
    read 1.85 ms a call at 256 rows x ~6,470 live entries against 3.04
    for per-head 64-lane slices and 3.09 for a ``(k * q) @ E`` head-sum
    on the VPU (PERF.md section 6, PR 27).

    ``scales`` (an INT8 pool): the row's per-block fp32 K and V scales,
    two ``[H, M]`` tiles (:func:`gather_row_scales`). A head's scale is
    constant over its own lanes, and row ``h`` of the two products reads
    only head ``h``'s lanes, so K's scale multiplies the SCORES
    (``[H, span]``, after the int8 x bf16 dot: int8 is exact in bf16)
    and V's scale multiplies ``p`` before its dot: the same quantity as
    ``ops.quant.dequantize_kv_rows`` followed by the dots, without
    rounding the dequantized tile to bf16. ``last`` (the loop form) is
    the row's last live entry: a slot past it holds that entry again
    and takes its scale. Softmax statistics and the accumulator stay
    fp32.

    A WINDOW call (``window`` > 0) reads a RING of ``ring`` table
    entries: position ``p`` lives in entry ``(p // bs) % ring``, so
    entry ``e`` holds block ``cur - (cur - e) % ring`` of the row's
    positions (``cur`` the block of its last position) and a key is
    visible iff ``length - window <= kpos < length``."""
    c, span = entries, entries * block_size
    heads = q.shape[0]

    def entry_scales(rows):
        """``[H, span]``: the scale of the entry each position sits in.
        Entry ``ki * c + j`` is lane ``ki * c + j`` of the row's scale
        tile: a masked lane reduction (Mosaic has no dynamic lane index
        into VMEM), one selected element plus zeros."""
        lane = lax.broadcasted_iota(jnp.int32, rows.shape, 1)
        entry = lax.broadcasted_iota(
            jnp.int32, (heads, span), 1) // block_size
        out = jnp.zeros((heads, span), jnp.float32)
        for j in range(c):
            e = ki * c + j if last is None else jnp.minimum(ki * c + j, last)
            sj = jnp.sum(jnp.where(lane == e, rows, 0.0),
                         axis=-1, keepdims=True)             # [H, 1]
            out = jnp.where(entry == j, sj, out)
        return out

    def key_positions(shape):
        """The position of each of the iteration's ``span`` keys."""
        lane = lax.broadcasted_iota(jnp.int32, shape, 1)
        if not ring:
            return ki * span + lane
        cur = (length - 1) // block_size
        entry = lane // block_size
        blk = jnp.zeros(shape, jnp.int32)
        for j in range(c):
            e = ki * c + j
            blk = jnp.where(entry == j,
                            cur - lax.rem(cur - e + ring, ring), blk)
        return (blk - entry) * block_size + lane

    s = lax.dot_general(q, k.astype(q.dtype), (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
    if scales is not None:
        s = s * entry_scales(scales[0])
    kpos = key_positions(s.shape)
    visible = kpos < length
    if window:
        visible &= kpos >= jnp.maximum(length - window, 0)
    s = jnp.where(visible, s, NEG_BIG)                       # [H, span]
    m_prev = m_scr[:, :1]
    l_prev = l_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = corr * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    if scales is not None:
        p = p * entry_scales(scales[1])
    acc_scr[:] = acc_scr[:] * corr + lax.dot_general(
        p.astype(q.dtype), v.astype(q.dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                  # [H, KVH*D]
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)


def _write_heads(o_ref, l_scr, acc_scr, group: int):
    """common.softmax_finalize over every head's row (no lse: inference
    only); a row that folded nothing stays exactly zero. GROUPED QUERY
    HEADS (``group`` query heads read one K/V head): head ``h``'s output
    is the ``D``-lane block of K/V head ``h // group`` in its row."""
    heads, d = o_ref.shape[1], o_ref.shape[3]
    out = acc_scr[:] / jnp.maximum(l_scr[:, :1], 1e-30)
    for h in range(heads):
        g = h // group
        o_ref[0, h] = out[h:h + 1, g * d:(g + 1) * d].astype(o_ref.dtype)


def _paged_decode_kernel(tab_ref, len_ref, q_ref, k_hbm, *refs,
                         scale: float, block_size: int, entries: int,
                         quant: bool, group: int = 1, latent: int = 0):
    """THE LOOP FORM (a full table): one grid step = one row, ALL
    heads, and inside it a loop over the row's own live table entries,
    ``entries`` of them an iteration: ``ceil(length / span)`` iterations
    (``span = entries * bs``; none for an inactive row, which finalizes
    to exact zeros).

    Both pools stay in HBM (``pl.ANY``). An iteration's K and V blocks
    come by ``2 * entries`` copies of one pool block each (``(bs,
    KVH*D)``: whole rows of whole 128-lane tiles, contiguous in HBM)
    into one of two VMEM slots, gathered through the RAW table in SMEM;
    iteration ``i + 1``'s copies start before iteration ``i``'s are
    waited on, and a row's LAST iteration starts the NEXT row's first
    (scratch, semaphores and the slot counter persist across grid
    steps, so the row axis is ``"arbitrary"``): no row waits a DMA
    latency at its start, as the grid form's pipeline arranged for
    free. Every one of an iteration's slots is filled from a block the
    row owns: past the row's last live entry the copy repeats that
    entry (its positions lie at or past ``length`` and are masked), so
    no buffer row is ever left as VMEM found it or as another row left
    it (``0 * NaN`` in ``p @ V``) and no block the row does not own is
    read. Guarding each copy past the last live entry instead read
    0.81 against 0.69 ms a call: the guards cost the scalar core more
    than the repeated copies cost the DMA engine (PERF.md section 6,
    PR 31).

    THE LATENT FORM (``latent`` > 0: the width of a cached row's value
    part): ONE pool of latent rows (``(bs, W)``, ``[c_kv | k_r | 0..]``)
    and no V pool. An entry is copied once and serves as keys over all
    ``W`` lanes (against the absorbed query ``[q~ | q_r | 0..]``) and as
    values over its first ``latent`` lanes; every query head reads the
    one row (``group = H`` over one "head" of ``latent`` lanes), so the
    accumulator and the result are ``latent`` wide."""
    v_hbm = None
    if not latent:
        v_hbm, *refs = refs
    ks_ref = vs_ref = None
    if quant:
        ks_ref, vs_ref, *refs = refs
    o_ref, k_buf, *refs = refs
    v_buf = None
    if not latent:
        v_buf, *refs = refs
    sem, slot_ref, m_scr, l_scr, acc_scr = refs
    pools = ((k_hbm, k_buf),) if latent else ((k_hbm, k_buf), (v_hbm, v_buf))
    c, bs = entries, block_size
    span = c * bs
    b, rows = pl.program_id(0), pl.num_programs(0)

    def iterations(row):
        return (len_ref[row] + span - 1) // span

    def last_entry(row):
        return (len_ref[row] + bs - 1) // bs - 1

    def start(row, it, slot):
        """Start the copies of iteration ``it`` of ``row`` into ``slot``."""
        last = last_entry(row)
        for j in range(c):
            blk = tab_ref[row, jnp.minimum(it * c + j, last)]
            for hbm, buf in pools:
                pltpu.make_async_copy(
                    hbm.at[blk], buf.at[slot, pl.ds(j * bs, bs)],
                    sem.at[slot]).start()

    def wait(slot):
        """Wait for all ``2 c`` copies into ``slot``: a DMA semaphore
        counts bytes, so one descriptor the shape of a pool's whole
        slot waits for that pool's ``c`` copies."""
        for _, buf in pools:
            pltpu.make_async_copy(buf.at[slot], buf.at[slot],
                                  sem.at[slot]).wait()

    n = iterations(b)
    nxt = jnp.minimum(b + 1, rows - 1)
    next_row_is_live = (b + 1 < rows) & (iterations(nxt) > 0)

    @pl.when(b == 0)
    def _first_row():
        slot_ref[0] = 0

    slot0 = slot_ref[0]
    length = len_ref[b]
    _scratch_init(m_scr, l_scr, acc_scr)

    # Nobody has started this row's first iteration (it is the first
    # row), or this row has none and the next row's must start here.
    @pl.when(((b == 0) & (n > 0)) | ((n == 0) & next_row_is_live))
    def _():
        start(jnp.where(n > 0, b, nxt), 0, slot0)

    def iteration(i, carry):
        slot = lax.rem(slot0 + i, 2)
        more = i + 1 < n

        @pl.when(more | next_row_is_live)
        def _():
            start(jnp.where(more, b, nxt), jnp.where(more, i + 1, 0),
                  1 - slot)

        wait(slot)
        _fold_entries(
            q_ref[0], k_buf[slot],
            k_buf[slot, :, :latent] if latent else v_buf[slot],
            length, i, m_scr, l_scr,
            acc_scr, scale=scale, block_size=bs, entries=c,
            last=last_entry(b),
            scales=(ks_ref[0, 0], vs_ref[0, 0]) if quant else None)
        return carry

    lax.fori_loop(0, n, iteration, 0)
    slot_ref[0] = lax.rem(slot0 + n, 2)
    _write_heads(o_ref, l_scr, acc_scr, group)


def _paged_decode_grid_kernel(tab_ref, len_ref, q_ref, *refs, scale: float,
                              block_size: int, entries: int, quant: bool,
                              group: int = 1, window: int = 0,
                              ring: int = 0):
    """THE GRID FORM: one grid step = ``entries`` consecutive table
    entries of one row, ALL heads. Each of the ``entries`` K and V refs
    is one pool block ``(1, bs, KVH*D)`` (the index maps of
    :func:`_paged_call` gathered them through the table); stacked they
    are one block of :func:`_fold_entries`. Steps at or past the row's
    length do nothing: their index maps repeat the blocks of the row's
    last live step, so the pipeline issues no DMA for them either, but
    the scalar core still walks them (about 0.7 us a step on a v5e). A
    ring (``window``) is walked as it stands, ``ring / entries`` steps
    a row whatever the length and every one of them live (K-EXAONE's
    rings are 3 entries: one step a row)."""
    c = entries
    k_refs, v_refs, refs = refs[:c], refs[c:2 * c], refs[2 * c:]
    ks_ref = vs_ref = None
    if quant:
        ks_ref, vs_ref, *refs = refs
    o_ref, m_scr, l_scr, acc_scr = refs
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        _scratch_init(m_scr, l_scr, acc_scr)

    length = len_ref[pl.program_id(0)]

    # A row with length == 0 (inactive slot) runs no step at all and
    # finalizes to an all-zero output.
    @pl.when((length > 0) if ring else (ki * c * block_size < length))
    def _block():
        _fold_entries(
            q_ref[0],
            jnp.concatenate([r[0] for r in k_refs], axis=0),
            jnp.concatenate([r[0] for r in v_refs], axis=0),
            length, ki, m_scr, l_scr, acc_scr, scale=scale,
            block_size=block_size, entries=c, window=window, ring=ring,
            scales=(ks_ref[0, 0], vs_ref[0, 0]) if quant else None)

    @pl.when(ki == pl.num_programs(1) - 1)
    def _final():
        _write_heads(o_ref, l_scr, acc_scr, group)


def _visited_entries(tab, lens, block_size: int, entries: int):
    """``block_tables [B, M]`` as the GRID form walks it: entry ``e``
    of a row is operand ``e % entries`` of grid step ``e // entries``.
    Up to the row's last live entry the table stands as it is; every
    entry past it names a block the row's last live step already holds
    in the same operand (or the last live block itself), so a skipped
    step repeats the block indices of the step before it — the pipeline
    issues no DMA — and a row with live entries names no block it does
    not own. A row with ``length == 0`` names its entry 0 throughout
    (fetched once, never folded)."""
    m = tab.shape[1]
    last = jnp.maximum((lens + block_size - 1) // block_size - 1, 0)[:, None]
    e = jnp.arange(m, dtype=jnp.int32)[None, :]
    step = jnp.minimum(e // entries, last // entries)
    ent = jnp.minimum(step * entries + e % entries, last)
    return jnp.take_along_axis(tab, ent, axis=1)


# Jitted so that a model's layers share ONE trace and ONE lowering of the
# kernel body: traced inline, a 12-layer step program traced it 12 times on
# every process start, before any compile cache is consulted, and the loop
# form's unrolled copies made that 20 s of a serving cell's set-up (PERF.md
# section 6, PR 31).
@functools.partial(jax.jit, static_argnames=("scale", "interpret", "window"))
def _paged_call(q, k, v, lengths, block_tables, scale, interpret,
                block_scales=None, window=None):
    """Paged layout: k/v are LANE-DENSE BLOCK POOLS ``[N, bs, KVH*D]``
    and ``block_tables [B, M]`` maps row b's KV block ki to pool block
    ``block_tables[b, ki]``. Table and lengths ride as SCALAR-PREFETCH
    operands (pltpu.PrefetchScalarGridSpec). A pool block is ``bs``
    whole rows of ``KVH*D`` lanes, contiguous in HBM, so one DMA brings
    all heads of an entry. The query goes in block-diagonally
    (``[B, H, KVH*D]``, built here by one small XLA fusion; see
    :func:`_fold_entries`). With ``block_scales`` (int8 pools) the
    row's per-block fp32 scales are pre-gathered through the table
    (:func:`gather_row_scales`) and ride as a per-row VMEM block.

    WHICH ITERATION SPACE a call gets is decided here, by what the
    operands show (the same rule compiled and interpreted):

    - a full table over a pool whose ``KVH*D`` is a multiple of 128
      (GPT-2's 768, K-EXAONE's 1,024): the LOOP form
      (:func:`_paged_decode_kernel`), grid ``(B,)``, the pools in
      ``pl.ANY`` and the RAW table: a call costs what its rows' live
      entries cost. It keeps the names the benchmark and the compile
      tests read: ``nezha_decode_attention_paged`` / ``_paged_int8``.
    - any other width (a four-way head shard of GPT-2's pool is 192
      lanes; the tests' 48), and a ring (``window``): the GRID form
      (:func:`_paged_decode_grid_kernel`), grid ``(B, M / c)``, each
      pool passed ``c`` times with operand ``j`` gathering table entry
      ``ki * c + j`` in its index map (a full table first rewritten by
      :func:`_visited_entries`), named with a ``_grid`` suffix
      (``nezha_decode_attention_window_grid``). Mosaic refuses the
      loop's copies at such a width: "Slice shape along dimension 2
      must be aligned to tiling (128), but is 192". A ring has no step
      that moves nothing for the loop to save (K-EXAONE's: 3 entries,
      one live step a row, bound by the MXU taking K and V as weights):
      on the loop its call read 0.271 against 0.262 ms alone and 4.6%
      more in the cell's trace, so it stays as it was (PERF.md section
      6, PR 31)."""
    b, h, _, d = q.shape
    bs, hd = k.shape[1], k.shape[2]
    kvh = hd // d
    group = h // kvh
    m = block_tables.shape[1]
    c = _pick_block(m, _ENTRIES_PER_STEP)
    quant = block_scales is not None
    loop = hd % _LANES == 0 and not window
    if loop:
        kernel = functools.partial(
            _paged_decode_kernel, scale=scale, block_size=bs, entries=c,
            quant=quant, group=group)
    else:
        kernel = functools.partial(
            _paged_decode_grid_kernel, scale=scale, block_size=bs,
            entries=c, quant=quant, group=group, window=window or 0,
            ring=m if window else 0)
    tab = jnp.asarray(block_tables, jnp.int32)
    if window:
        lens = jnp.maximum(jnp.asarray(lengths, jnp.int32), 0)
    else:
        lens = jnp.clip(jnp.asarray(lengths, jnp.int32), 0, m * bs)
        if not loop:
            tab = _visited_entries(tab, lens, bs, c)
    # row h: head h's values in the lanes of K/V head h // group
    lanes_of = (jnp.arange(h)[:, None] // group
                == jnp.arange(kvh)[None, :]).astype(q.dtype)
    q_bd = (q[:, :, 0, None, :] * lanes_of[None, :, :, None]).reshape(
        b, h, hd)
    row = lambda b_, *_: (b_, 0, 0)                          # noqa: E731
    q_spec = pl.BlockSpec((1, h, hd), row)
    out_spec = pl.BlockSpec((1, h, 1, d), lambda b_, *_: (b_, 0, 0, 0))
    softmax_scratch = [pltpu.VMEM((h, _LANES), jnp.float32),
                       pltpu.VMEM((h, _LANES), jnp.float32),
                       pltpu.VMEM((h, hd), jnp.float32)]
    if loop:
        grid = (b,)
        in_specs = [q_spec] + [pl.BlockSpec(memory_space=pl.ANY)] * 2
        operands = [q_bd, k, v]
        scratch = [pltpu.VMEM((2, c * bs, hd), k.dtype),
                   pltpu.VMEM((2, c * bs, hd), v.dtype),
                   pltpu.SemaphoreType.DMA((2,)),
                   pltpu.SMEM((1,), jnp.int32)] + softmax_scratch
        semantics = ("arbitrary",)
    else:
        grid = (b, m // c)
        kv_specs = [pl.BlockSpec((1, bs, hd),
                                 lambda b_, ki, tab, lens, j=j:
                                 (tab[b_, ki * c + j], 0, 0))
                    for j in range(c)]
        in_specs = [q_spec] + kv_specs * 2
        operands = [q_bd] + [k] * c + [v] * c
        scratch = softmax_scratch
        semantics = ("parallel", "arbitrary")
    if quant:
        in_specs += [pl.BlockSpec((1, 1, h, m),
                                  lambda b_, *_: (b_, 0, 0, 0))] * 2
        operands += [gather_row_scales(sc, tab, h) for sc in block_scales]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=grid, in_specs=in_specs,
        out_specs=out_spec, scratch_shapes=scratch)
    name = ("nezha_decode_attention_paged_int8" if quant
            else "nezha_decode_attention_window" if window
            else "nezha_decode_attention_paged")
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        interpret=interpret,
        name=name if loop else name + "_grid",
    )(tab, lens, *operands)


@functools.partial(jax.jit, static_argnames=("scale", "value_width",
                                             "interpret", "name"))
def _latent_call(q, pool, lengths, block_tables, scale, value_width,
                 interpret, name):
    """The loop form over ONE leaf (see :func:`_paged_decode_kernel`):
    ``q`` ``[B, H, W]`` is the absorbed query as it meets a cached row,
    ``pool`` ``[N, bs, W]`` the latent rows, ``W`` a whole number of
    128-lane tiles. -> ``[B, H, 1, value_width]``. ``name`` is the
    call's name in a trace and nothing else."""
    b, h, w = q.shape
    bs, m = pool.shape[1], block_tables.shape[1]
    c = _pick_block(m, _ENTRIES_PER_STEP)
    kernel = functools.partial(
        _paged_decode_kernel, scale=scale, block_size=bs, entries=c,
        quant=False, group=h, latent=value_width)
    lens = jnp.clip(jnp.asarray(lengths, jnp.int32), 0, m * bs)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(b,),
        in_specs=[pl.BlockSpec((1, h, w), lambda b_, *_: (b_, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, h, 1, value_width),
                               lambda b_, *_: (b_, 0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, c * bs, w), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((1,), jnp.int32),
                        pltpu.VMEM((h, _LANES), jnp.float32),
                        pltpu.VMEM((h, _LANES), jnp.float32),
                        pltpu.VMEM((h, value_width), jnp.float32)])
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, 1, value_width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=name,
    )(jnp.asarray(block_tables, jnp.int32), lens, q, pool)


def latent_attention_composed(q, pool, lengths, block_tables, value_width,
                              scale: float):
    """What :func:`latent_decode_attention` computes, composed from
    ``jax.numpy`` over the gathered view of each row's table (``[B,
    M*bs, W]``): the path where no kernel runs and the other side of the
    kernel's interpret-mode tests."""
    b, h, w = q.shape
    bs, m = pool.shape[1], block_tables.shape[1]
    ctx = pool[block_tables].reshape(b, m * bs, w).astype(q.dtype)
    visible = (jnp.arange(m * bs)[None, :]
               < jnp.asarray(lengths, jnp.int32)[:, None])[:, None]
    sc = jnp.einsum("bhw,blw->bhl", q, ctx,
                    preferred_element_type=jnp.float32) * scale
    sc = jnp.where(visible, sc, NEG_BIG)
    p = jnp.where(visible, jnp.exp(sc - sc.max(axis=-1, keepdims=True)), 0.0)
    p = p / jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bhl,blr->bhr", p.astype(ctx.dtype),
                     ctx[..., :value_width])
    return out[:, :, None, :]


def latent_decode_attention(q, pool, lengths, block_tables, value_width: int,
                            scale: float, interpret: Optional[bool] = None,
                            name: str = "nezha_decode_attention_latent"):
    """Single-token decode over a paged LATENT cache (multi-head latent
    attention, absorbed): ``q`` ``[B, H, W]`` is every head's query
    already in the cached row's space (``[q_nope W_uk | q_rope | 0..]``),
    ``pool`` ``[N, bs, W]`` holds one row a token (``[c_kv | k_r |
    0..]``, ``W`` a multiple of 128), ``block_tables`` ``[B, M]`` and
    ``lengths`` as for :func:`flash_decode_attention`. Scores run over
    all ``W`` lanes; the values are the rows' first ``value_width`` lanes.
    -> ``[B, H, 1, value_width]`` (the caller expands it through
    ``W_uv``). A row with ``length == 0`` reads nothing and comes back
    exactly zero. The kernel is the paged decode kernel's loop form with
    one pool. ``name`` is the ``pallas_call``'s, the one thing of a model
    that reaches a v5e trace (a ``jax.named_scope`` does not): a LABEL
    for whoever reads the trace, by which two models' calls of this one
    body are told apart there (``nezha_decode_attention_latent`` unless
    the caller says otherwise). Nothing branches on it."""
    b, h, w = q.shape
    if (pool.ndim != 3 or pool.shape[2] != w or w % _LANES
            or not 0 < value_width <= w or block_tables.shape[0] != b):
        raise ValueError(
            f"latent pool {pool.shape} / tables {block_tables.shape} do "
            f"not match q {q.shape}: want [num_blocks, block_size, W] with "
            f"W a multiple of {_LANES} and value_width <= W")
    return _latent_call(q, pool, lengths, block_tables, float(scale),
                        int(value_width), resolve_interpret(interpret), name)


def ring_entries(window: int, block_size: int) -> int:
    """Table entries of a window layer's ring: the blocks a window can
    straddle plus the one being written, ``ceil(window / bs) + 1``."""
    return -(-window // block_size) + 1


def paged_attention_composed(q, k, v, lengths, block_tables,
                             scale: Optional[float] = None,
                             window: Optional[int] = None):
    """What the paged kernels compute, composed from ``jax.numpy`` over
    the gathered view of each row's table (``[B, M*bs, KVH*D]``): the
    path a model takes where no kernel runs, and the other side of the
    kernels' interpret-mode tests. Same operands and conventions as
    :func:`flash_decode_attention` with ``block_tables`` (grouped query
    heads, a ring with ``window``); ``q`` may hold several queries a
    row (``[B, H, S, D]``, query ``i`` at position ``lengths - S + i``).
    -> ``[B, H, S, D]``."""
    b, h, s, d = q.shape
    bs, hd = k.shape[1], k.shape[2]
    kvh, m = hd // d, block_tables.shape[1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    lens = jnp.asarray(lengths, jnp.int32)
    entry = jnp.arange(m, dtype=jnp.int32)[None, :]
    if window:
        cur = (jnp.maximum(lens, 1) - 1)[:, None] // bs
        blk = cur - jnp.remainder(cur - entry, m)        # [B, M]
    else:
        blk = jnp.broadcast_to(entry, (b, m))
    kpos = (blk[:, :, None] * bs + jnp.arange(bs)[None, None, :]).reshape(
        b, 1, m * bs)
    qpos = (lens[:, None] - s + jnp.arange(s)[None, :])[:, :, None]
    visible = (kpos >= 0) & (kpos <= qpos) & (qpos >= 0)
    if window:
        visible &= qpos - kpos < window
    kk = k[block_tables].reshape(b, m * bs, kvh, d).astype(q.dtype)
    vv = v[block_tables].reshape(b, m * bs, kvh, d).astype(q.dtype)
    qg = q.reshape(b, kvh, h // kvh, s, d)
    sc = jnp.einsum("bkgsd,blkd->bkgsl", qg, kk,
                    preferred_element_type=jnp.float32) * scale
    sc = jnp.where(visible[:, None, None], sc, NEG_BIG)
    p = jnp.exp(sc - sc.max(axis=-1, keepdims=True))
    p = jnp.where(visible[:, None, None], p, 0.0)
    p = p / jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bkgsl,blkd->bkgsd", p.astype(vv.dtype), vv)
    return out.reshape(b, h, s, d)


def flash_decode_attention(q, k, v, lengths,
                           scale: Optional[float] = None,
                           block_k: Optional[int] = None,
                           interpret: Optional[bool] = None,
                           block_tables=None, block_scales=None,
                           window: Optional[int] = None):
    """q ``[B, H, 1, D]``, k/v ``[B, H, L, D]``, lengths ``[B]`` int32
    -> ``[B, H, 1, D]``.

    ``lengths[b]`` is the number of attendable cache positions for row
    ``b`` (the decode convention: ``pos + 1``, the query's own position
    included). ``lengths[b] == 0`` marks an inactive row: every KV block
    is skipped and the output row is exactly zero (callers discard it —
    the serve engine freezes inactive rows host-side). Lengths are
    clamped to ``[0, L]``.

    With ``block_tables`` (``[B, M]`` int32 — the paged serving
    layout), k/v are instead LANE-DENSE BLOCK POOLS shaped
    ``[num_blocks, block_size, H*D]`` (a position a row, head ``h`` in
    lanes ``h*D .. (h+1)*D``): row ``b``'s positions
    ``[ki*block_size, (ki+1)*block_size)`` live in pool block
    ``block_tables[b, ki]``, and the kernel gathers KV blocks through
    the table via a scalar-prefetch index map. The per-row length skip
    is preserved verbatim. ``block_k`` is ignored (a grid step folds a
    fixed number of whole pool blocks, all heads at once).

    With ``block_scales`` (paged only — a ``(k_scales, v_scales)`` pair
    of ``[num_blocks, H]`` fp32 arrays) the pools are INT8 and the
    scales are applied INSIDE the block loop, to the scores and to
    ``p`` (a head's scale is constant over its lanes): the quantity
    ``ops.quant.dequantize_kv_rows`` + dots computes, which is what
    the composed XLA fallback does, without rounding the dequantized
    tile to bf16; dots run in the query's dtype over the int8 values
    (exact in bf16), softmax statistics and the accumulator stay
    fp32.

    GROUPED QUERY HEADS (paged only): pools of ``KVH*D`` lanes with
    ``H`` a multiple of ``KVH``; query head ``h`` reads K/V head
    ``h // (H / KVH)``. With ``window`` (paged only) the table is a
    RING (:func:`ring_entries` entries or more): position ``p`` lives
    in entry ``(p // block_size) % M``, ``lengths`` is not bounded by
    the table, and query ``lengths - 1`` sees the ``window`` keys that
    end with itself.

    ``block_k`` defaults to the largest divisor of ``L`` that is <= 256
    (KV pools are padded to power-of-two-ish capacities, so real shapes
    get real blocks). ``interpret=None`` auto-selects: compiled on TPU,
    interpreter elsewhere.
    """
    b, h, s_q, d = q.shape
    if s_q != 1:
        raise ValueError(
            f"flash_decode_attention is the single-token kernel; got "
            f"s_q={s_q} (use flash_attention for prefill/training)")
    interpret = resolve_interpret(interpret)
    if block_scales is not None and block_tables is None:
        raise ValueError("block_scales requires block_tables (int8 is "
                         "a paged-pool format)")
    if window is not None and block_tables is None:
        raise ValueError("window requires block_tables (a ring of blocks)")
    if block_tables is not None:
        if (k.shape != v.shape or k.ndim != 3 or k.shape[2] % d
                or h % (k.shape[2] // d)):
            raise ValueError(
                f"paged k/v pools {k.shape}/{v.shape} do not match q "
                f"{q.shape}: want [num_blocks, block_size, KVH*D] with "
                f"H a multiple of KVH")
        kvh = k.shape[2] // d
        if window is not None and not (
                window >= 1 and block_tables.shape[1]
                >= ring_entries(window, k.shape[1])):
            raise ValueError(
                f"a window of {window} over blocks of {k.shape[1]} needs "
                f"a ring of {ring_entries(max(window, 1), k.shape[1])} "
                f"entries, got a table of {block_tables.shape[1]}")
        if block_scales is not None and (kvh != h or window is not None):
            raise ValueError(
                "int8 pools (block_scales) have no grouped-query or "
                "window form: the scales are per (block, query head)")
        if block_tables.shape[0] != b:
            raise ValueError(
                f"block_tables {block_tables.shape} does not match "
                f"batch {b}")
        if block_scales is not None:
            ks, vs = block_scales
            want = (k.shape[0], h)
            if tuple(ks.shape) != want or tuple(vs.shape) != want:
                raise ValueError(
                    f"block_scales {ks.shape}/{vs.shape} must be "
                    f"[num_blocks, H] = {want}")
        scale = scale if scale is not None else 1.0 / (d ** 0.5)
        return _paged_call(q, k, v, lengths, block_tables, scale,
                           interpret, block_scales=block_scales,
                           window=window)
    if k.shape != v.shape or k.shape[:2] != (b, h) or k.shape[3] != d:
        raise ValueError(f"k/v {k.shape}/{v.shape} do not match q {q.shape}")
    L = k.shape[2]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    bk = _pick_block(L, block_k or min(L, 256))

    kernel = functools.partial(_decode_kernel, scale=scale, block_k=bk)
    lens = jnp.clip(jnp.asarray(lengths, jnp.int32), 0, L)
    q_spec = pl.BlockSpec((1, 1, 1, d),
                          lambda b_, h_, ki, lens: (b_, h_, 0, 0))
    kv_spec = pl.BlockSpec((1, 1, bk, d),
                           lambda b_, h_, ki, lens: (b_, h_, ki, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, h, L // bk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((1, _LANES), jnp.float32),
                        pltpu.VMEM((1, _LANES), jnp.float32),
                        pltpu.VMEM((1, d), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=_DECODE_PARAMS,
        interpret=interpret,
        name="nezha_decode_attention_dense",
    )(lens, q, k, v)


def flash_decode_attention_sharded(q, k, v, lengths, mesh, *,
                                   scale: Optional[float] = None,
                                   block_tables=None, block_scales=None,
                                   interpret: Optional[bool] = None):
    """:func:`flash_decode_attention` PER SHARD under a nested
    ``shard_map`` over the mesh's ``tp`` (head) axis — the sharded
    serve engine's decode-attention path.

    Heads are embarrassingly parallel in decode attention (each head's
    online softmax reads only its own K/V slice), so sharding
    ``q [B, H, 1, D]`` and the per-(block, head) scale rows ``[N, H]``
    on the H axis and the paged K/V pools ``[N, bs, H*D]`` on the LANE
    axis (``H / tp`` contiguous heads a shard; the dense layout's
    ``[B, H, L, D]`` on H) runs the Mosaic kernel device-locally on an
    ``H / tp`` slice — the GSPMD
    auto-partitioner (which cannot partition a Pallas custom call)
    never sees it, exactly the ``_tp_sharded_flash`` idiom the
    training path proved. The per-row ``lengths`` and the
    scalar-prefetched ``block_tables`` REPLICATE: block identities are
    mesh-invariant host bookkeeping (see serve/sharded/pool.py — and
    the ``mesh-host-side-tables`` lint rule that keeps it so).

    ``scale`` defaults per shard to ``1/sqrt(D)`` — D is untouched by
    head sharding, so per-shard defaulting equals the unsharded
    kernel's. Output is ``[B, H, 1, D]`` sharded on H, matching the
    enclosing program's head-sharded activations."""
    from jax.sharding import PartitionSpec as P

    from nezha_tpu.parallel._compat import shard_map

    hspec = P(None, "tp")
    pspec = P(None, None, "tp")     # a paged pool: heads live in lanes
    rep = P()

    if block_scales is not None:
        ks, vs = block_scales

        def body_q(q_, k_, v_, l_, t_, ks_, vs_):
            return flash_decode_attention(
                q_, k_, v_, l_, scale=scale, interpret=interpret,
                block_tables=t_, block_scales=(ks_, vs_))

        f = shard_map(body_q, mesh=mesh,
                      in_specs=(hspec, pspec, pspec, rep, rep, hspec,
                                hspec),
                      out_specs=hspec)
        return f(q, k, v, lengths, block_tables, ks, vs)
    if block_tables is not None:
        def body_t(q_, k_, v_, l_, t_):
            return flash_decode_attention(
                q_, k_, v_, l_, scale=scale, interpret=interpret,
                block_tables=t_)

        f = shard_map(body_t, mesh=mesh,
                      in_specs=(hspec, pspec, pspec, rep, rep),
                      out_specs=hspec)
        return f(q, k, v, lengths, block_tables)

    def body(q_, k_, v_, l_):
        return flash_decode_attention(q_, k_, v_, l_, scale=scale,
                                      interpret=interpret)

    f = shard_map(body, mesh=mesh,
                  in_specs=(hspec, hspec, hspec, rep), out_specs=hspec)
    return f(q, k, v, lengths)
