"""InfLLM-v2 block-sparse attention over the paged K/V table: a decode
query reads only the 64-token blocks it selects (MiniCPM4's sparse layer,
``models/attention.py::SparseAttention``).

Per K/V head ``g`` (query heads ``h`` of its group), with ``K`` a row's
cached keys (after the per-head norm), ``L`` the visible length (the
query's own token included), ``r = block / stride`` compressed keys a
block and the sizes ``kernel`` (32), ``stride`` (16), ``block`` (64)::

    c_j     = mean(K[stride j : stride j + kernel])   complete windows only:
              j < n_c(L) = max(0, (L - kernel) // stride + 1)
    p_{h,j} = softmax_j(q_h . c_j * scale)            over j < n_c(L)
    s_{g,j} = sum_{h in g} p_{h,j}
    S_{g,b} = max s_{g,j} over the windows j < n_c(L) that overlap block b
    I_g     = {blocks < init} U {the blocks of positions L - window .. L-1}
              U top_k(S_{g,b}) of the other visible blocks (ties: lower id)
    o_h     = softmax over the visible tokens of I_g (causal)

A row whose ``L <= dense_len`` selects every visible block.

The compressed keys live in the pool's third leaf of the sparse layer,
``ck`` ``[N, r * KVH, D]`` float32: block ``b`` of a row holds windows
``r b .. r b + r - 1`` of every K/V head, row ``slot * KVH + g`` (so a
block is ``r * KVH`` sublanes of ``D`` lanes: one f32 tile at the
published 4 x 2 x 128). A window whose span crosses a block edge is
written when it is complete, into the block where it starts.

Two kernels and their ``jax.numpy`` twins:

- :func:`sparse_score` (``nezha_sparse_score``): a row's query heads
  against every compressed key of its table, ``s_{g,j}``; and the mean of
  the row's last ``kernel`` keys (the window its own token completes,
  which no one has written yet: it is scored from that mean, and the
  caller writes it into ``ck``). Grid: one step a row; the row's ``ck``
  blocks and the one or two K blocks of its last window come by manual DMA
  through the table in SMEM.
- :func:`sparse_decode_paged` (``nezha_sparse_decode_paged``): one query
  group (16 heads) against a per-(row, K/V head) LIST of table entries:
  the selected blocks, the row's current block last, so that the list's
  ``keys`` first positions are exactly the visible tokens of ``I_g``. One
  grid step a (row, K/V head), a loop over the list ``ENTRIES_PER_STEP``
  entries at a time, each entry one DMA of that head's ``[block, D]``
  lanes of K and of V, double-buffered across steps (as
  ``decode_attention._paged_decode_kernel``'s loop form), online softmax.

The selection between them (:func:`block_scores`, :func:`select_mask`,
:func:`select_list`) is ``jax.numpy`` over at most ``max_len / block``
block scores a (row, K/V head): the top-k by bisection over the scores'
bits (:func:`top_k_mask`), the list by a compare-and-sum; no sort.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from nezha_tpu.ops.pallas.common import LANES, NEG_BIG, resolve_interpret

_HIGHEST = lax.Precision.HIGHEST
# Entries of the selected list folded into one iteration of the decode
# kernel: 8 x 64 = 512 keys, 16 copies a start.
ENTRIES_PER_STEP = 8


class SparseSizes(NamedTuple):
    """The sparse layer's published ``sparse_config`` (MiniCPM4)."""
    kernel: int = 32
    stride: int = 16
    block: int = 64
    topk: int = 64
    init_blocks: int = 1
    window: int = 2048
    dense_len: int = 8192

    @property
    def per_block(self) -> int:
        """Compressed keys a block (``r``)."""
        return self.block // self.stride

    def list_len(self, table_entries: int) -> int:
        """Entries of a (row, K/V head) selected list: a dense row's
        whole visible table, or init + window (one more where it is not
        block-aligned) + top-k; never more than the table."""
        sparse = (self.init_blocks + self.window // self.block + 1
                  + self.topk)
        return min(table_entries, max(self.dense_len // self.block, sparse))

    def validate(self) -> None:
        if (self.block % self.stride or self.kernel % self.stride
                or self.kernel > self.block):
            raise ValueError(
                f"sparse sizes {self}: block and kernel are whole strides "
                f"and a window lies in at most two blocks")


def n_complete(lengths, sizes: SparseSizes):
    """Complete compression windows at visible length ``lengths``."""
    return jnp.maximum((lengths - sizes.kernel) // sizes.stride + 1, 0)


# ------------------------------------------------------------- selection
def block_scores(s, n_c, sizes: SparseSizes):
    """``s`` ``[..., NC]`` (window scores, ``NC = M r``) and ``n_c``
    ``[...]`` -> ``S`` ``[..., M]``: each block's largest score over the
    complete windows that overlap it (``-inf`` where none does). Window
    ``j`` covers ``[stride j, stride j + kernel)``: block ``b`` meets
    windows ``r b - (kernel / stride - 1) .. r b + r - 1``."""
    r = sizes.per_block
    back = sizes.kernel // sizes.stride - 1
    nc = s.shape[-1]
    s = jnp.where(jnp.arange(nc) < n_c[..., None], s, -jnp.inf)
    by_block = s.reshape(*s.shape[:-1], nc // r, r)       # [..., M, r]
    out = by_block.max(-1)
    # the windows that start in block b - 1 and reach into block b: its
    # last ``back`` slots, shifted one block on
    edge = jnp.full(out.shape[:-1] + (1,), -jnp.inf, s.dtype)
    for o in range(1, back + 1):
        out = jnp.maximum(out, jnp.concatenate(
            [edge, by_block[..., :-1, r - o]], axis=-1))
    return out


def top_k_mask(scores, k: int):
    """The ``k`` largest of ``scores`` ``[..., M]`` (each >= 0, or
    ``-inf`` for no candidate), of equal scores the lower index: a bool
    mask ``[..., M]``, exactly what ``lax.top_k``'s indices mark, without
    its sort. The ``k``-th largest is found by bisection over the scores'
    bit patterns (order-preserving for non-negative floats: 31 counting
    passes over ``M`` lanes), ties at it taken lowest index first."""
    cand = scores > -jnp.inf
    keys = jnp.where(cand, lax.bitcast_convert_type(
        jnp.maximum(scores, 0.0).astype(jnp.float32), jnp.int32), -1)
    want = jnp.minimum(cand.sum(-1, keepdims=True), k)

    def bisect(_, lo_hi):
        lo, hi = lo_hi                   # count(keys >= lo) >= want
        mid = lo + (hi - lo) // 2 + 1    # no int32 overflow
        ok = ((keys >= mid).sum(-1, keepdims=True) >= want) & (lo < hi)
        return jnp.where(ok, mid, lo), jnp.where(ok | (lo == hi), hi,
                                                 mid - 1)

    lo = jnp.zeros_like(want)
    lo, _ = lax.fori_loop(0, 31, bisect,
                          (lo, jnp.full_like(want, 2 ** 31 - 1)))
    above = keys > lo
    ties = keys == lo
    room = want - above.sum(-1, keepdims=True)
    return cand & (above | (ties & (jnp.cumsum(ties, -1) <= room)))


def select_mask(scores, lengths, sizes: SparseSizes):
    """``scores`` ``[..., M]`` (:func:`block_scores`), ``lengths``
    ``[...]`` -> the selected blocks ``[..., M]`` bool: every visible
    block at ``L <= dense_len``; else the first ``init_blocks``, the
    blocks of the last ``window`` positions and the ``topk`` best of the
    others (:func:`top_k_mask`: of equal scores the lower block id)."""
    m = scores.shape[-1]
    b = jnp.arange(m)
    L = lengths[..., None]
    visible = b * sizes.block < L
    forced = visible & ((b < sizes.init_blocks) | (
        b >= jnp.maximum(L - sizes.window, 0) // sizes.block))
    chosen = top_k_mask(jnp.where(visible & ~forced, scores, -jnp.inf),
                        min(sizes.topk, m))
    return jnp.where(L <= sizes.dense_len, visible, forced | chosen)


def select_list(mask, lengths, sizes: SparseSizes, list_len: int):
    """``mask`` ``[B, KVH, M]`` -> (``ids`` ``[B, KVH, list_len]`` the
    selected blocks in ascending order, so the row's current block comes
    last, padded with that block; ``keys`` ``[B, KVH]``: the visible
    tokens those entries hold, the list's first positions). Entry ``i``
    is the selected block with ``i`` selected blocks before it: a
    compare-and-sum over the blocks (no sort, no scatter)."""
    m = mask.shape[-1]
    count = mask.sum(-1)
    slot = jnp.where(mask, jnp.cumsum(mask, -1) - 1, -1)     # [B, KVH, M]
    i = jnp.minimum(jnp.arange(list_len), jnp.maximum(count - 1, 0)[..., None])
    ids = jnp.sum(jnp.where(slot[..., None, :] == i[..., :, None],
                            jnp.arange(m), 0), axis=-1)
    L = lengths[:, None]
    cur = (L - 1) // sizes.block
    keys = jnp.where(count > 0, (count - 1) * sizes.block + L
                     - cur * sizes.block, 0)
    return ids.astype(jnp.int32), keys.astype(jnp.int32)


# ---------------------------------------------------------------- score
def window_mean_reference(k_pool, tab, lengths, sizes: SparseSizes):
    """The mean of each row's last ``kernel`` keys ``[B, KVH*D]`` float32
    (zeros where ``L < kernel``), gathered through the table."""
    bs = k_pool.shape[1]
    p = lengths[:, None] - sizes.kernel + jnp.arange(sizes.kernel)
    pc = jnp.maximum(p, 0)
    rows = k_pool[jnp.take_along_axis(tab, pc // bs, axis=1), pc % bs]
    rows = jnp.where((p >= 0)[..., None], rows.astype(jnp.float32), 0.0)
    return rows.sum(1) / sizes.kernel


def _score_rows(q, ckeys, fresh, lengths, heads_per_kv: int,
                sizes: SparseSizes):
    """The score of one or more rows, the kernel's and the twin's
    arithmetic: ``q`` ``[..., H, D]`` float32 (scaled), ``ckeys``
    ``[..., W, D]`` (column ``j * KVH + g``), ``fresh`` ``[..., KVH,
    D]``, ``lengths`` ``[...]`` -> ``[..., W]``: ``s_{g,j}`` at column
    ``j * KVH + g``."""
    kvh = fresh.shape[-2]
    logits = jnp.einsum("...hd,...wd->...hw", q, ckeys, precision=_HIGHEST)
    col = lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    head = lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 2)
    g = col % kvh
    L = jnp.asarray(lengths)[..., None, None]
    nc = n_complete(L, sizes)
    # the window the query's own token completes is scored from ``fresh``
    due = (L >= sizes.kernel) & ((L - sizes.kernel) % sizes.stride == 0)
    fresh_l = None
    for i in range(kvh):
        fl = jnp.sum(q * fresh[..., i:i + 1, :], axis=-1, keepdims=True)
        fresh_l = fl if fresh_l is None else jnp.where(g == i, fl, fresh_l)
    logits = jnp.where(due & (col // kvh == nc - 1), fresh_l, logits)
    mask = (g == head // heads_per_kv) & (col // kvh < nc)
    m = jnp.max(jnp.where(mask, logits, -jnp.inf), axis=-1, keepdims=True)
    p = jnp.where(mask, jnp.exp(logits - m), 0.0)
    z = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    return jnp.sum(p / z, axis=-2)


def sparse_score_reference(q, ck_pool, k_pool, tab, lengths, scale: float,
                           sizes: SparseSizes):
    """What :func:`sparse_score` computes, composed: the row's compressed
    keys gathered through the table."""
    b, h, d = q.shape
    kvh = k_pool.shape[-1] // d
    fresh = window_mean_reference(k_pool, tab, lengths, sizes)
    ckeys = ck_pool[tab].reshape(b, -1, d)
    s = _score_rows(q.astype(jnp.float32) * scale, ckeys,
                    fresh.reshape(b, kvh, d), lengths, h // kvh, sizes)
    return s.reshape(b, -1, kvh), fresh


def _score_kernel(tab_ref, len_ref, q_ref, ck_hbm, k_hbm, s_ref, fresh_ref,
                  ck_buf, k_buf, sem, *, sizes: SparseSizes,
                  heads_per_kv: int):
    """One grid step = one row: its ``ck`` blocks holding complete
    windows and the K blocks of its last ``kernel`` positions by DMA,
    then :func:`_score_rows` over the whole buffer (entries past the
    row's windows are masked, whatever they hold)."""
    b = pl.program_id(0)
    L = len_ref[b]
    bs, kvh_d = k_buf.shape[1], k_buf.shape[2]
    d = q_ref.shape[2]
    kvh = kvh_d // d
    r = sizes.per_block
    nblk = (n_complete(L, sizes) + r - 1) // r

    def ck_copy(i):
        return pltpu.make_async_copy(ck_hbm.at[tab_ref[b, i]], ck_buf.at[i],
                                     sem.at[0])

    first = jnp.maximum(L - sizes.kernel, 0) // bs
    last = jnp.maximum(L - 1, 0) // bs
    k_copies = [pltpu.make_async_copy(k_hbm.at[tab_ref[b, e]], k_buf.at[i],
                                      sem.at[1])
                for i, e in enumerate((first, last))]

    def start(i, c):
        ck_copy(i).start()
        return c

    lax.fori_loop(0, nblk, start, 0)
    for cp in k_copies:
        cp.start()

    def wait(i, c):
        ck_copy(0).wait()
        return c

    lax.fori_loop(0, nblk, wait, 0)
    for cp in k_copies:
        cp.wait()
    # the last window's keys: positions L - kernel .. L - 1, each once
    pos = lax.broadcasted_iota(jnp.int32, (bs, kvh_d), 0)
    take0 = (first * bs + pos >= L - sizes.kernel) & (first * bs + pos < L)
    take1 = ((last * bs + pos >= L - sizes.kernel) & (last * bs + pos < L)
             & (last != first))
    total = (jnp.sum(jnp.where(take0, k_buf[0].astype(jnp.float32), 0.0),
                     axis=0, keepdims=True)
             + jnp.sum(jnp.where(take1, k_buf[1].astype(jnp.float32), 0.0),
                       axis=0, keepdims=True))               # [1, KVH*D]
    fresh = total / sizes.kernel
    fresh_ref[0] = fresh
    m = ck_buf.shape[0]
    ckeys = ck_buf[...].reshape(m * ck_buf.shape[1], d)
    fresh_g = jnp.concatenate([fresh[:, g * d:(g + 1) * d]
                               for g in range(kvh)], axis=0)  # [KVH, D]
    s_ref[0] = _score_rows(q_ref[0], ckeys, fresh_g, L, heads_per_kv,
                           sizes)[None]


@functools.partial(jax.jit, static_argnames=("scale", "sizes", "interpret"))
def _score_call(q, ck_pool, k_pool, tab, lengths, scale, sizes, interpret):
    b, h, d = q.shape
    m = tab.shape[1]
    rows_ck = ck_pool.shape[1]
    kvh_d = k_pool.shape[2]
    w = m * rows_ck
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(b,),
        in_specs=[pl.BlockSpec((1, h, d), lambda b_, *_: (b_, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[pl.BlockSpec((1, 1, w), lambda b_, *_: (b_, 0, 0)),
                   pl.BlockSpec((1, 1, kvh_d), lambda b_, *_: (b_, 0, 0))],
        scratch_shapes=[pltpu.VMEM((m, rows_ck, d), ck_pool.dtype),
                        pltpu.VMEM((2, k_pool.shape[1], kvh_d), k_pool.dtype),
                        pltpu.SemaphoreType.DMA((2,))])
    s, fresh = pl.pallas_call(
        functools.partial(_score_kernel, sizes=sizes,
                          heads_per_kv=h // (kvh_d // d)),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, 1, w), jnp.float32),
                   jax.ShapeDtypeStruct((b, 1, kvh_d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # a row's compressed keys (4 MiB at 65,536 positions) and
            # its [H, W] float32 scores with their temporaries
            vmem_limit_bytes=64 * 2 ** 20),
        interpret=interpret,
        name="nezha_sparse_score",
    )(jnp.asarray(tab, jnp.int32), jnp.asarray(lengths, jnp.int32),
      (q.astype(jnp.float32) * scale), ck_pool, k_pool)
    return s.reshape(b, -1, kvh_d // d), fresh.reshape(b, kvh_d)


def sparse_score(q, ck_pool, k_pool, tab, lengths, scale: float,
                 sizes: SparseSizes, interpret: Optional[bool] = None):
    """``q`` ``[B, H, D]`` (normed, unscaled), ``ck_pool`` ``[N, r*KVH,
    D]`` float32, ``k_pool`` ``[N, block, KVH*D]``, ``tab`` ``[B, M]``,
    ``lengths`` ``[B]`` (0: an inactive row). -> (``s`` ``[B, M*r,
    KVH]`` float32: ``s_{g,j}``, 0 past ``n_c``; ``fresh`` ``[B,
    KVH*D]`` float32: the mean of the row's last ``kernel`` keys)."""
    return _score_call(q, ck_pool, k_pool, tab, lengths, scale, sizes,
                       resolve_interpret(interpret))


# --------------------------------------------------------------- decode
def sparse_decode_reference(q, k_pool, v_pool, tab, ids, keys, scale: float):
    """What :func:`sparse_decode_paged` computes, composed: ``q`` ``[B,
    H, D]``, ``ids`` ``[B, KVH, n]`` (table entries), ``keys`` ``[B,
    KVH]`` -> ``[B, H, D]`` float32 (zeros where ``keys == 0``)."""
    b, h, d = q.shape
    bs = k_pool.shape[1]
    kvh = k_pool.shape[2] // d
    n = ids.shape[-1]
    blk = jnp.take_along_axis(tab[:, None, :], ids, axis=-1)   # [B,KVH,n]

    def head_rows(pool):
        rows = pool[blk].reshape(b, kvh, n * bs, kvh, d)
        return jnp.stack([rows[:, g, :, g] for g in range(kvh)], axis=1)

    kk, vv = head_rows(k_pool), head_rows(v_pool)           # [B,KVH,T,D]
    qg = q.reshape(b, kvh, h // kvh, d).astype(jnp.float32)
    sc = jnp.einsum("bgqd,bgtd->bgqt", qg, kk.astype(jnp.float32),
                    precision=_HIGHEST) * scale
    vis = jnp.arange(n * bs) < keys[..., None, None]
    sc = jnp.where(vis, sc, NEG_BIG)
    p = jnp.where(vis, jnp.exp(sc - sc.max(-1, keepdims=True)), 0.0)
    o = jnp.einsum("bgqt,bgtd->bgqd", p, vv.astype(jnp.float32),
                   precision=_HIGHEST)
    o = o / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    return o.reshape(b, h, d)


def _sparse_decode_kernel(blk_ref, keys_ref, q_ref, k_hbm, v_hbm, o_ref,
                          k_buf, v_buf, sem, slot_ref, m_scr, l_scr, acc_scr,
                          *, scale: float, block_size: int, entries: int,
                          kv_heads: int):
    """One grid step = one (row, K/V head) ``r``: a loop over its list,
    ``entries`` entries an iteration (``ceil(keys / span)`` iterations),
    each entry one DMA of the head's ``[block, D]`` lanes of K and of V
    into one of two VMEM slots; iteration ``i + 1``'s copies start before
    iteration ``i``'s are waited on, and a step's last iteration starts
    the NEXT step's first (the slot counter persists, so the axis is
    ``"arbitrary"``). Past the list's last live entry a copy repeats it
    (its positions are masked), so no buffer row is left as VMEM found
    it. A step with ``keys == 0`` (an inactive row) writes zeros."""
    c, bs = entries, block_size
    span = c * bs
    r, steps = pl.program_id(0), pl.num_programs(0)
    d = q_ref.shape[2]

    def iterations(row):
        return (keys_ref[row] + span - 1) // span

    def start(row, it, slot):
        last = (keys_ref[row] + bs - 1) // bs - 1
        lane = pl.ds(pl.multiple_of(lax.rem(row, kv_heads) * d, d), d)
        for j in range(c):
            blk = blk_ref[row, jnp.minimum(it * c + j, last)]
            for hbm, buf in ((k_hbm, k_buf), (v_hbm, v_buf)):
                pltpu.make_async_copy(
                    hbm.at[blk, :, lane], buf.at[slot, pl.ds(j * bs, bs)],
                    sem.at[slot]).start()

    def wait(slot):
        for buf in (k_buf, v_buf):
            pltpu.make_async_copy(buf.at[slot], buf.at[slot],
                                  sem.at[slot]).wait()

    n = iterations(r)
    nxt = jnp.minimum(r + 1, steps - 1)
    next_is_live = (r + 1 < steps) & (iterations(nxt) > 0)

    @pl.when(r == 0)
    def _first():
        slot_ref[0] = 0

    slot0 = slot_ref[0]
    keys = keys_ref[r]
    m_scr[:] = jnp.full(m_scr.shape, NEG_BIG, jnp.float32)
    l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
    acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(((r == 0) & (n > 0)) | ((n == 0) & next_is_live))
    def _():
        start(jnp.where(n > 0, r, nxt), 0, slot0)

    q = q_ref[0]                                             # [G, D]

    def iteration(i, carry):
        slot = lax.rem(slot0 + i, 2)
        more = i + 1 < n

        @pl.when(more | next_is_live)
        def _():
            start(jnp.where(more, r, nxt), jnp.where(more, i + 1, 0),
                  1 - slot)

        wait(slot)
        k = k_buf[slot]
        s = lax.dot_general(q, k.astype(q.dtype), (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        kpos = i * span + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < keys, s, NEG_BIG)
        m_prev, l_prev = m_scr[:, :1], l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        acc_scr[:] = acc_scr[:] * corr + lax.dot_general(
            p.astype(q.dtype), v_buf[slot].astype(q.dtype),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(corr * l_prev + jnp.sum(
            p, axis=-1, keepdims=True), l_scr.shape)
        return carry

    lax.fori_loop(0, n, iteration, 0)
    slot_ref[0] = lax.rem(slot0 + n, 2)
    o_ref[0] = (acc_scr[:] / jnp.maximum(l_scr[:, :1], 1e-30)).astype(
        o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _decode_call(q, k_pool, v_pool, tab, ids, keys, scale, interpret):
    b, h, d = q.shape
    bs, kvh_d = k_pool.shape[1], k_pool.shape[2]
    kvh = kvh_d // d
    group = h // kvh
    n = ids.shape[-1]
    c = min(ENTRIES_PER_STEP, n)
    blk = jnp.take_along_axis(tab[:, None, :], ids, axis=-1).reshape(
        b * kvh, n)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(b * kvh,),
        in_specs=[pl.BlockSpec((1, group, d), lambda r, *_: (r, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, group, d), lambda r, *_: (r, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, c * bs, d), k_pool.dtype),
                        pltpu.VMEM((2, c * bs, d), v_pool.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((1,), jnp.int32),
                        pltpu.VMEM((group, LANES), jnp.float32),
                        pltpu.VMEM((group, LANES), jnp.float32),
                        pltpu.VMEM((group, d), jnp.float32)])
    out = pl.pallas_call(
        functools.partial(_sparse_decode_kernel, scale=scale, block_size=bs,
                          entries=c, kv_heads=kvh),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * kvh, group, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="nezha_sparse_decode_paged",
    )(blk, jnp.asarray(keys, jnp.int32).reshape(b * kvh),
      q.reshape(b * kvh, group, d), k_pool, v_pool)
    return out.reshape(b, h, d)


def sparse_decode_paged(q, k_pool, v_pool, tab, ids, keys, scale: float,
                        interpret: Optional[bool] = None):
    """``q`` ``[B, H, D]`` (head ``h`` reads K/V head ``h // (H/KVH)``),
    ``k_pool`` / ``v_pool`` ``[N, block, KVH*D]``, ``tab`` ``[B, M]``,
    ``ids`` ``[B, KVH, n]`` (table entries, :func:`select_list`),
    ``keys`` ``[B, KVH]``. -> ``[B, H, D]`` in ``q``'s dtype."""
    return _decode_call(q, k_pool, v_pool, tab, ids, keys, scale,
                        resolve_interpret(interpret))
