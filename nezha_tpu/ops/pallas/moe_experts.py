"""The routed experts of a dropless layer as ONE grouped-matmul kernel.

``parallel.expert.DroplessMoE`` sorts a call's token-expert pairs by held
expert and hands over the pair rows ``xs [R, d]``, the group sizes
``sizes [held]`` and the three stacked weights as they are stored
(``w_gate``, ``w_up`` ``[held, d, d_ff]``, ``w_down`` ``[held, d_ff, d]``).
:func:`moe_experts` computes, for the rows of each group ``e``::

    out = ((silu(x @ w_gate[e]) * (x @ w_up[e])).astype(x.dtype)) @ w_down[e]

with ``gate``, ``up`` and ``out`` accumulated in float32: the rounding
points of three ``jax.lax.ragged_dot`` calls with the activation between
them (:func:`moe_experts_reference`, the other side of the tests), in one
``pallas_call`` named ``nezha_moe_experts``. An expert sees 4-8 rows in a
decode step and 32-64 in a 1,024-token chunk, so a call is bound by reading
weights, and the kernel is built so that a touched expert's ``3 * d * d_ff``
weights cross HBM once:

- The grid is ``(visits, d_ff / tf)``. A VISIT is a (row tile, expert)
  pair that holds at least one row; :func:`visit_plan` lists them from
  ``sizes`` (experts in order, an expert's tiles in order) and the lists
  reach the index maps by scalar prefetch, as
  ``jax.experimental.pallas.ops.tpu.megablox.gmm`` does it. An expert with
  no row is in no visit and costs no DMA; grid steps past the last visit
  repeat its blocks (no DMA either) and skip the body.
- A step holds the gate and up COLUMN tiles ``[d, tf]`` and the matching
  down ROW tile ``[tf, d]`` of the visit's expert (double-buffered by the
  pipeline); ``h``'s ``[window, tf]`` tile never leaves VMEM; the output
  accumulates over the ``d_ff`` tiles in the row tile's float32 output
  block, which stays in VMEM while consecutive visits name the same tile.
- The row tile ``[tm, d]`` is large (every pair row of a decode step where
  that fits), so that a group is rarely cut by a tile's edge and
  visits = touched experts; inside it a visit computes only WINDOWS of
  ``window`` rows that start at the group's first row rounded down to 16
  (a bf16 tile's sublanes), masked to the group's own rows: the matmul
  unit streams the group's few rows, not the tile's hundreds.

Tile sizes are constants of the static shapes (:func:`tile_sizes`): ``tm``
and ``tf`` the largest that keep the row tile's buffers and the weight
tiles' under ``_VMEM_ROWS`` / ``_VMEM_WEIGHTS`` each; ``window`` is one
constant. Rows past ``sum(sizes)`` (the pairs of
absent experts) are never computed: inside a visited tile they come back
as zeros, in a tile no visit names they are whatever the buffer held, and
the caller weighs both by 0 through a ``where``. Widths under 128 lanes
(the tiny test presets) run the same code: a block then spans the whole
width, which the TPU lowering accepts as it does a multiple of 128.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from nezha_tpu.ops.pallas.common import pick_block, resolve_interpret

# VMEM given to a row tile's buffers (x and the float32 output, two of
# each) and to the three weight tiles' (two of each): 40 MiB apiece of a
# v5e core's 128, the call's limit raised to match. At 32 MiB K-EXAONE's
# tiles are 256 rows x 256 columns and its 1,024-token chunk reads 2.84 ms
# a call alone on the chip against 2.55 ms at 512 x 512 (20 visits of 16
# touched experts against 18; its decode step 1.72 against 1.71 ms; the
# other two widths have the same tiles under both: PERF.md section 6, PR 33).
_VMEM_ROWS = 40 * 2 ** 20
_VMEM_WEIGHTS = 40 * 2 ** 20
_SUBLANES = 16      # rows of one bf16 tile: where a window may start
# Rows a window computes. Alone on the chip 32 / 64 / 128 read within 0.5%
# of each other at the three cells' decode steps and 1,024-token chunks
# (groups of 4-80 rows), and 1.49 / 1.44 / 1.46 ms with one group of 543
# rows (PERF.md section 6, PR 33): the weights' DMA hides all three.
_WINDOW = 64


def _pow2_floor(n: int) -> int:
    return 1 << (max(n, 1).bit_length() - 1)


def tile_sizes(rows: int, d: int, d_ff: int,
               itemsize: int) -> Tuple[int, int, int]:
    """``(tm, tf, window)`` for ``rows`` pair rows (a multiple of 16).
    ``tm``: the largest power of two of rows whose x and
    float32 output blocks, double-buffered, fit ``_VMEM_ROWS`` (512 / 512 /
    1,024 rows at d = 4,096 / 6,144 / 2,304 in bf16), at most ``rows``.
    ``tf``: the largest divisor of ``d_ff`` in whole 128-lane tiles whose
    three weight tiles, double-buffered, fit ``_VMEM_WEIGHTS`` (512 / 512 /
    1,024 there); all of ``d_ff`` where it is not whole lane tiles.
    ``window``: ``_WINDOW`` rows, or the tile's where that is smaller."""
    tm = min(_pow2_floor(_VMEM_ROWS // (2 * d * (itemsize + 4))), rows)
    tm = max(tm // _SUBLANES * _SUBLANES, _SUBLANES)
    tf = d_ff
    if d_ff % 128 == 0:     # in lane tiles: the largest divisor that fits
        fit = _VMEM_WEIGHTS // (2 * 3 * d * itemsize * 128)
        tf = 128 * pick_block(d_ff // 128, max(fit, 1))
    return tm, tf, min(_WINDOW, tm)


def visit_plan(sizes, rows: int, tm: int):
    """The (row tile, expert) pairs a call computes, from ``sizes [held]``
    int32: -> ``(offsets [held + 1], expert_of [V], tile_of [V], stats
    [2])``, all int32, with ``V = held + tiles - 1`` (every tile edge cuts
    at most one group). Visit ``v < stats[0]`` is expert ``expert_of[v]``
    on rows ``tile_of[v] * tm ...``; the entries after it repeat the last
    visit. ``stats = (visits, touched experts)``: their ratio is how many
    times a touched expert's weights are read."""
    held = sizes.shape[0]
    tiles = -(-rows // tm)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    touched = sizes > 0
    first = starts // tm
    spans = jnp.where(touched, (ends - 1) // tm - first + 1, 0)
    before = jnp.cumsum(spans) - spans          # visits of earlier experts
    visits = jnp.sum(spans)
    v = jnp.minimum(jnp.arange(held + tiles - 1), jnp.maximum(visits - 1, 0))
    expert_of = jnp.minimum(
        jnp.searchsorted(before + spans, v, side="right"), held - 1)
    tile_of = jnp.minimum(first[expert_of] + v - before[expert_of], tiles - 1)
    offsets = jnp.concatenate([jnp.zeros((1,), ends.dtype), ends])
    stats = jnp.stack([visits, jnp.sum(touched)])
    return tuple(a.astype(jnp.int32)
                 for a in (offsets, expert_of, tile_of, stats))


def moe_experts_reference(xs, sizes, w_gate, w_up, w_down):
    """What :func:`moe_experts` computes on the rows before
    ``sum(sizes)``, as the compiler's three grouped matmuls."""
    f32 = dict(preferred_element_type=jnp.float32)
    gate = lax.ragged_dot(xs, w_gate, sizes, **f32)
    up = lax.ragged_dot(xs, w_up, sizes, **f32)
    h = (jax.nn.silu(gate) * up).astype(xs.dtype)
    return lax.ragged_dot(h, w_down, sizes, **f32)


def _kernel(off_ref, eid_ref, tid_ref, stats_ref, x_ref, wg_ref, wu_ref,
            wd_ref, o_ref, *, tm: int, window: int):
    v, f = pl.program_id(0), pl.program_id(1)
    live = v < stats_ref[0]
    e, tile = eid_ref[v], tid_ref[v]
    opens = (v == 0) | (tid_ref[jnp.maximum(v - 1, 0)] != tile)

    @pl.when(live & opens & (f == 0))
    def _():    # the tile's first visit: rows no group claims read zero
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live)
    def _():
        base = tile * tm
        lo = jnp.maximum(off_ref[e], base) - base     # the group's rows
        hi = jnp.minimum(off_ref[e + 1], base + tm) - base    # in the tile
        first = lo // _SUBLANES * _SUBLANES
        dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32)

        def one_window(i, carry):
            # window i answers for rows [own, own + window) of the group;
            # at the tile's end it slides back over rows of the window
            # before it, which the mask leaves to that window
            own = first + i * window
            at = pl.multiple_of(jnp.minimum(own, tm - window), _SUBLANES)
            x = x_ref[pl.ds(at, window), :]
            gate, up = dot(x, wg_ref[0]), dot(x, wu_ref[0])
            h = (gate * jax.nn.sigmoid(gate) * up).astype(x.dtype)
            row = at + lax.broadcasted_iota(jnp.int32, (window, 1), 0)
            mine = (row >= jnp.maximum(lo, own)) & (row < hi)
            o_ref[pl.ds(at, window), :] += jnp.where(
                mine, dot(h, wd_ref[0]), 0.0)
            return carry

        lax.fori_loop(0, (hi - first + window - 1) // window, one_window, 0)


@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def _call(xs, sizes, w_gate, w_up, w_down, tiles, interpret):
    rows, d = xs.shape
    d_ff = w_gate.shape[2]
    padded = -(-rows // _SUBLANES) * _SUBLANES
    if padded != rows:      # odd test shapes; a serve program's are whole
        xs = jnp.pad(xs, ((0, padded - rows), (0, 0)))
    itemsize = xs.dtype.itemsize
    tm, tf, window = tiles or tile_sizes(padded, d, d_ff, itemsize)
    nf = d_ff // tf
    offsets, expert_of, tile_of, stats = visit_plan(sizes, padded, tm)

    # Past the last visit every index map names the last visit's blocks
    # again: a step that computes nothing also copies nothing.
    def f_of(v, f, stats):
        return jnp.where(v < stats[0], f, nf - 1)

    def rows_map(v, f, off, eid, tid, stats):
        return tid[v], 0

    def cols_map(v, f, off, eid, tid, stats):     # gate / up: [d, tf]
        return eid[v], 0, f_of(v, f, stats)

    def down_map(v, f, off, eid, tid, stats):     # down: [tf, d]
        return eid[v], f_of(v, f, stats), 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=(expert_of.shape[0], nf),
        in_specs=[pl.BlockSpec((tm, d), rows_map),
                  pl.BlockSpec((1, d, tf), cols_map),
                  pl.BlockSpec((1, d, tf), cols_map),
                  pl.BlockSpec((1, tf, d), down_map)],
        out_specs=pl.BlockSpec((tm, d), rows_map))
    buffers = 2 * tm * d * (itemsize + 4) + 2 * 3 * d * tf * itemsize
    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((padded, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # the pipeline's buffers and as much again for a window's
            # float32 temporaries and the compiler's own
            vmem_limit_bytes=min(buffers + 32 * 2 ** 20, 112 * 2 ** 20)),
        interpret=interpret,
        name="nezha_moe_experts",
    )(offsets, expert_of, tile_of, stats, xs, w_gate, w_up, w_down)
    return out[:rows], stats


def moe_experts(xs, sizes, w_gate, w_up, w_down,
                tiles: Optional[Tuple[int, int, int]] = None,
                interpret: Optional[bool] = None):
    """``xs [R, d]`` (the compute dtype): the pair rows sorted by held
    expert, the rows of absent experts last; ``sizes [held]`` int32;
    ``w_gate``, ``w_up`` ``[held, d, d_ff]`` and ``w_down`` ``[held, d_ff,
    d]`` in ``xs``'s dtype. -> (``out [R, d]`` float32, ``stats [2]``
    int32 = the call's (visits, touched experts), :func:`visit_plan`).
    Rows past ``sum(sizes)`` are not computed (module docstring).
    ``tiles``: ``(tm, tf, window)`` in place of :func:`tile_sizes`'s, for
    the tests (tile edges at sizes a test can afford) and the timing
    script (``experiments/moe_experts_alone.py``); no caller in the
    program passes it."""
    if not (xs.dtype == w_gate.dtype == w_up.dtype == w_down.dtype):
        raise ValueError(
            "the pair rows and the three weights share one dtype, got "
            f"{xs.dtype}, {w_gate.dtype}, {w_up.dtype}, {w_down.dtype}")
    return _call(xs, jnp.asarray(sizes, jnp.int32), w_gate, w_up, w_down,
                 tiles, resolve_interpret(interpret))
