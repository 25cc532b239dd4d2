"""The two halves of a manifold-constrained hyper-connection
(``nn/hyper_connections.py`` has the equations) as Pallas kernels, one body
each for a decode step's rows and a prefill chunk's tokens, tiled over
tokens.

- :func:`mhc_pre` (``nezha_mhc_pre``) reads a tile of streams ``[TM, n*C]``
  ONCE and gives the sublayer's input ``u`` and the token's maps. In the
  tile: the ``n*C``-wide mean square; ``phi x`` as ``[maps, TM]`` (tokens
  in lanes, a float32 product on the matmul unit); the three maps a row of
  lanes each, Sinkhorn's rounds elementwise over ``n*n`` such rows (no
  reduction crosses a lane: a token's 4 x 4 matrix lies in 16 vregs'
  same lane); the maps turned to a column a token (a product with the
  identity) and ``u = sum_i H_pre,i X_i``.
- :func:`mhc_post` (``nezha_mhc_post``) reads the streams, the sublayer's
  output and the maps and writes ``H_res X + H_post^T y`` over the streams
  it read (``input_output_aliases``).

**Inside a serve program is not alone.** The first form of
:func:`mhc_post` stored each stream's result before the next was summed. It
matched the composed form to 1e-6 on the chip when called alone and read
40-100 bf16 ulps off INSIDE a compiled block or step: there the compiler
keeps a small call's streams (a decode step's 1.8 MB) in VMEM (``S(1)`` in
the HLO), the aliased output IS its input's memory, and stream ``i``'s
store fed stream ``i + 1``'s sum. Every result is now formed before any
store. Reverted alone on the chip, the store order reproduces the fault at
a step's 32 rows (a block's streams 8.7 times their own rms from the
composed path's, against 0.004 as it is) and not at 256 tokens, whose tiles
are copied in and out of VMEM apart; the other change made with it (``a``
and ``b`` as a ``[maps, 128]`` VMEM operand in place of SMEM scalars) reads
the same either way and stayed because every measured run ran it (PERF.md
section 6, PR 36). Interpret mode, the compile-only rehearsal and a
kernel-alone run show none of this: ``chip_smoke.py``'s ``mhc_block`` phase
compares a whole block, kernel path against composed path, inside a
compiled program on the chip (``experiments/mhc_alone.py --by-layer
--forms`` has the first forms).

Least bytes a sublayer: the streams read twice and written once, ``u`` and
``y`` once each way, ``phi`` once, the maps ``MAP_LANES`` float32 a token
each way. Everything is float32. Off the TPU the composed forms of
``nn/hyper_connections.py`` run instead (they are also the oracle of the
interpret-mode tests).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from nezha_tpu.nn.hyper_connections import MAP_LANES, map_scales
from nezha_tpu.ops.pallas.common import resolve_interpret

_HIGHEST = lax.Precision.HIGHEST
TOKEN_TILE = 128        # tokens a grid step (a shorter call is one tile)
_NT = (((1,), (1,)), ((), ()))      # a[m, k] . b[n, k] -> [m, n]


def _pre_kernel(x_ref, phi_ref, ab_ref, u_ref, maps_ref, rows_scr, *, n: int,
                iters: int, eps: float, clamp: Tuple[float, float],
                norm_eps: float):
    """``ab_ref`` ``[maps, 128]``: a map entry's scalar ``a`` in lane 0 and
    its bias in lane 1: a VMEM operand like the others.
    ``rows_scr`` ``[MAP_LANES, TM]``: the maps a row each, tokens in
    lanes."""
    f32 = jnp.float32
    tm, nc = x_ref.shape
    c = nc // n
    x = x_ref[...]
    r_col = lax.rsqrt(jnp.sum(x * x, axis=1, keepdims=True) / nc + norm_eps)
    eye = (lax.broadcasted_iota(jnp.int32, (tm, tm), 0)
           == lax.broadcasted_iota(jnp.int32, (tm, tm), 1))
    # the column of norms as a row: the diagonal, summed over sublanes
    r_row = jnp.sum(jnp.where(eye, r_col, 0.0), axis=0, keepdims=True)
    proj = lax.dot_general(phi_ref[...], x, _NT, precision=_HIGHEST,
                           preferred_element_type=f32)          # [maps, TM]
    ab = ab_ref[...]
    h = proj * r_row * ab[:, 0:1] + ab[:, 1:2]

    rows_scr[...] = jnp.zeros_like(rows_scr)
    rows_scr[0:n, :] = jax.nn.sigmoid(h[0:n, :])
    rows_scr[n:2 * n, :] = 2.0 * jax.nn.sigmoid(h[n:2 * n, :])
    m = tuple(jnp.exp(jnp.clip(h[2 * n + k:2 * n + k + 1, :], clamp[0],
                               clamp[1]))
              for k in range(n * n))            # m[i * n + j]: row i, col j

    def one_round(_, m):
        out = []
        for i in range(n):                      # rows / (row sums + eps)
            inv = 1.0 / (sum(m[i * n + j] for j in range(n)) + eps)
            out += [m[i * n + j] * inv for j in range(n)]
        inv = [1.0 / (sum(out[i * n + j] for i in range(n)) + eps)
               for j in range(n)]               # columns / (column sums + eps)
        return tuple(out[i * n + j] * inv[j]
                     for i in range(n) for j in range(n))

    m = lax.fori_loop(0, iters, one_round, m)
    for k in range(n * n):
        rows_scr[2 * n + k:2 * n + k + 1, :] = m[k]
    # [MAP_LANES, TM] -> [TM, MAP_LANES]: a product with the identity
    maps = lax.dot_general(eye.astype(f32), rows_scr[...], _NT,
                           precision=_HIGHEST, preferred_element_type=f32)
    maps_ref[...] = maps
    u = maps[:, 0:1] * x[:, 0:c]
    for i in range(1, n):
        u = u + maps[:, i:i + 1] * x[:, i * c:(i + 1) * c]
    u_ref[...] = u


def _post_kernel(x_ref, y_ref, maps_ref, o_ref, *, n: int):
    """Every stream's result is formed before any is written: the output
    aliases the input, and where the compiler keeps a small call's streams
    in VMEM ``o_ref`` may be ``x_ref``'s own memory, so stream ``i``'s
    result must not be able to reach stream ``i + 1``'s sum."""
    c = y_ref.shape[1]
    maps = maps_ref[...]
    y = y_ref[...]
    streams = [x_ref[:, j * c:(j + 1) * c] for j in range(n)]
    out = []
    for i in range(n):
        acc = maps[:, n + i:n + i + 1] * y
        for j in range(n):
            k = 2 * n + i * n + j
            acc = acc + maps[:, k:k + 1] * streams[j]
        out.append(acc)
    for i in range(n):
        o_ref[:, i * c:(i + 1) * c] = out[i]


def _tiles(t: int) -> Tuple[int, int]:
    """-> (tile, padded token count): one tile of a multiple of 8 tokens
    for a short call, ``TOKEN_TILE`` otherwise."""
    if t <= TOKEN_TILE:
        tm = -(-t // 8) * 8
        return tm, tm
    return TOKEN_TILE, -(-t // TOKEN_TILE) * TOKEN_TILE


def _pad_rows(a, t_pad: int):
    return a if a.shape[0] == t_pad else jnp.pad(
        a, ((0, t_pad - a.shape[0]), (0, 0)))


_VMEM_LIMIT = 96 * 2 ** 20      # a 128-token tile of 4 x 3,584 streams is
                                # 7.3 MB, in and out, double-buffered


@functools.partial(jax.jit, static_argnames=(
    "n", "iters", "eps", "clamp", "norm_eps", "interpret"))
def _pre_call(x, phi, alpha, b, *, n, iters, eps, clamp, norm_eps, interpret):
    t, nc = x.shape
    c = nc // n
    tm, t_pad = _tiles(t)
    f32 = jnp.float32
    xp = _pad_rows(x.astype(f32), t_pad)
    ab = jnp.zeros((n * (n + 2), 128), f32).at[:, 0].set(
        map_scales(alpha, n)).at[:, 1].set(b.astype(f32))
    tile = lambda w: pl.BlockSpec((tm, w), lambda i: (i, 0))   # noqa: E731
    whole = lambda a: pl.BlockSpec(a.shape, lambda i: (0, 0))  # noqa: E731
    u, maps = pl.pallas_call(
        functools.partial(_pre_kernel, n=n, iters=iters, eps=eps,
                          clamp=clamp, norm_eps=norm_eps),
        grid=(t_pad // tm,),
        in_specs=[tile(nc), whole(phi), whole(ab)],
        out_specs=[tile(c), tile(MAP_LANES)],
        out_shape=[jax.ShapeDtypeStruct((t_pad, c), f32),
                   jax.ShapeDtypeStruct((t_pad, MAP_LANES), f32)],
        scratch_shapes=[pltpu.VMEM((MAP_LANES, tm), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="nezha_mhc_pre",
    )(xp, phi.astype(f32), ab)
    return u[:t], maps[:t]


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def _post_call(x, y, maps, *, n, interpret):
    t, nc = x.shape
    c = nc // n
    tm, t_pad = _tiles(t)
    f32 = jnp.float32
    tile = lambda w: pl.BlockSpec((tm, w), lambda i: (i, 0))   # noqa: E731
    out = pl.pallas_call(
        functools.partial(_post_kernel, n=n),
        grid=(t_pad // tm,),
        in_specs=[tile(nc), tile(c), tile(MAP_LANES)],
        out_specs=tile(nc),
        out_shape=jax.ShapeDtypeStruct((t_pad, nc), f32),
        # the new streams over the ones read: a tile is read whole before
        # it is written, and no other tile's rows are touched
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="nezha_mhc_post",
    )(_pad_rows(x.astype(f32), t_pad), _pad_rows(y.astype(f32), t_pad),
      _pad_rows(maps.astype(f32), t_pad))
    return out[:t]


def mhc_pre(x, phi, alpha, b, *, n: int, iters: int, eps: float,
            clamp: Tuple[float, float], norm_eps: float,
            interpret: Optional[bool] = None):
    """``x`` [T, n*C] float32 streams, ``phi`` [n*n + 2n, n*C], ``alpha``
    [3], ``b`` [n*n + 2n] -> (``u`` [T, C], ``maps`` [T, MAP_LANES]), both
    float32."""
    return _pre_call(x, phi, alpha, b, n=n, iters=iters, eps=float(eps),
                     clamp=(float(clamp[0]), float(clamp[1])),
                     norm_eps=float(norm_eps),
                     interpret=resolve_interpret(interpret))


def mhc_post(x, y, maps, *, n: int, interpret: Optional[bool] = None):
    """``x`` [T, n*C], ``y`` [T, C], ``maps`` [T, MAP_LANES] -> the new
    streams [T, n*C] float32, written over ``x`` where the caller lets go
    of it."""
    return _post_call(x, y, maps, n=n, interpret=resolve_interpret(interpret))
