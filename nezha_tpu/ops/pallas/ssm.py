"""The Mamba-2 selective state-space recurrence (SSD): a cache of one
matrix a head a sequence, with ONE scalar decay a head a token.

Per head ``h`` of ``P`` channels, in group ``g = h // (H / G)`` (``B`` and
``C`` are shared by the heads of a group), with state ``S_h`` in
``R^{N x P}`` (d_state x head channels, float32, zero at a sequence's
start; the transpose of the published ``[P, N]``, so that the head's
channels lie in lanes), per token ``t`` (``dt_t > 0`` the step after
softplus, ``a_h = -exp(A_log_h) < 0``)::

    S_t = exp(dt_t a_h) S_{t-1} + B_{g,t} (dt_t x_t)^T
    y_t = S_t^T C_{g,t}                       (the D skip is the caller's)

Three forms live here, as in ``kda.py``:

- :func:`ssm_decode`: ONE token a row against a pool of states ``[E, H,
  N, P]`` (``serve/slots.py``'s state group: one entry a slot), one pass
  over the state in a Pallas kernel (``nezha_ssm_decode``): a grid step
  loads a row's ``H`` matrices once, decays them, adds the rank-one
  write, reads ``y`` and writes the state back over its own input
  (``input_output_aliases``). Bandwidth work (a row's state read and
  written, 4 B an element, against ~5 operations an element): no matmul
  unit is used. :func:`ssm_decode_reference` is its ``jax.numpy`` twin.
- :func:`ssd_chunked`: a run of tokens of ONE sequence in chunks of
  ``C`` (prefill), all float32; the algebra is in its docstring.
- :func:`ssd_recurrent`: the recurrence itself under ``lax.scan`` (the
  tests' yardstick for the chunked form).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from nezha_tpu.ops.pallas.common import resolve_interpret

_HIGHEST = lax.Precision.HIGHEST


def _group_of(heads: int, groups: int):
    """[H] int32: the group of each head."""
    return jnp.arange(heads) // (heads // groups)


def ssm_decode_reference(s_pool, entries, x, dt, a, b, c):
    """What :func:`ssm_decode` computes, composed: gather the rows'
    states, one step of the recurrence, scatter them back."""
    f32 = jnp.float32
    heads, groups = x.shape[1], b.shape[1]
    g = _group_of(heads, groups)
    s = s_pool[entries].astype(f32)                          # [B, H, N, P]
    dt = dt.astype(f32)
    dx = dt[..., None] * x.astype(f32)                       # [B, H, P]
    bh = b.astype(f32)[:, g]                                 # [B, H, N]
    st = (s * jnp.exp(dt * a.astype(f32))[..., None, None]
          + bh[..., :, None] * dx[..., None, :])
    y = jnp.einsum("bhnp,bhn->bhp", st, c.astype(f32)[:, g],
                   precision=_HIGHEST)
    return y, s_pool.at[entries].set(st.astype(s_pool.dtype))


def _ssm_decode_kernel(ent_ref, rows_ref, cols_ref, s_ref, y_ref, s_out_ref,
                       *, per_group: int):
    """One grid step = one row, all heads. ``rows_ref`` ``[1, H, 2, P]``
    holds a head's ``dt * x`` and its decay ``exp(dt * a)`` (the scalar
    repeated over the lanes) as two ROW vectors; ``cols_ref`` ``[1, G, 2,
    N]`` a group's ``B`` and ``C`` as two rows, turned once a group into
    two COLUMN vectors (d_state on sublanes): so the write ``B (dt x)^T``
    is a column times a row and ``y = S^T C`` a sum over sublanes. The
    rows lie dense in HBM whatever ``G``: a ``[.., N, 2]`` operand would
    take a 128-lane tile a pair (64 times its bytes: 0.4 GB a call at 32
    groups of 128 and 192 rows)."""
    del ent_ref     # read by the index maps
    heads = s_ref.shape[1]
    for g in range(heads // per_group):
        bc = cols_ref[0, g].T                                 # [N, 2]
        for h in range(g * per_group, (g + 1) * per_group):
            dx = rows_ref[0, h, 0:1, :]                       # [1, P]
            decay = rows_ref[0, h, 1:2, :]                    # [1, P]
            st = s_ref[0, h] * decay + bc[:, 0:1] * dx
            s_out_ref[0, h] = st
            y_ref[0, h:h + 1, :] = jnp.sum(st * bc[:, 1:2], axis=0,
                                           keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssm_decode_call(s_pool, entries, x, dt, a, b, c, interpret):
    bsz, heads, p = x.shape
    groups, n = b.shape[1], b.shape[2]
    f32 = jnp.float32
    dt = dt.astype(f32)
    decay = jnp.exp(dt * a.astype(f32))                       # [B, H]
    rows = jnp.stack([dt[..., None] * x.astype(f32),
                      jnp.broadcast_to(decay[..., None], x.shape)],
                     axis=2)                                  # [B, H, 2, P]
    cols = jnp.stack([b.astype(f32), c.astype(f32)], axis=2)   # [B, G, 2, N]
    row = lambda b_, ent: (b_, 0, 0, 0)                       # noqa: E731
    state = pl.BlockSpec((1, heads, n, p),
                         lambda b_, ent: (ent[b_], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(bsz,),
        in_specs=[pl.BlockSpec((1, heads, 2, p), row),
                  pl.BlockSpec((1, groups, 2, n), row), state],
        out_specs=[pl.BlockSpec((1, heads, p), lambda b_, ent: (b_, 0, 0)),
                   state])
    return pl.pallas_call(
        functools.partial(_ssm_decode_kernel, per_group=heads // groups),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((bsz, heads, p), f32),
                   jax.ShapeDtypeStruct(s_pool.shape, s_pool.dtype)],
        # operands: entries(0) rows(1) cols(2) s_pool(3): the pool aliases
        # its output, so a step rewrites the rows' entries in place
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # a row's state in and out, double-buffered: 4 x H*N*P*4 B
            # (16 MB at 32 heads of 256 x 128) plus a head's temporaries
            vmem_limit_bytes=64 * 2 ** 20),
        interpret=interpret,
        name="nezha_ssm_decode",
    )(jnp.asarray(entries, jnp.int32), rows, cols, s_pool)


def ssm_decode(s_pool, entries, x, dt, a, b, c,
               interpret: Optional[bool] = None):
    """One token a row: ``s_pool`` ``[E, H, N, P]`` float32, ``entries``
    ``[B]`` int32 (the pool entry each row's state lives in; rows that
    must not advance name a scratch entry), ``x`` ``[B, H, P]``, ``dt``
    ``[B, H]`` (after softplus), ``a`` ``[H]`` (``-exp(A_log)``), ``b, c``
    ``[B, G, N]``. -> (``y`` ``[B, H, P]`` float32, the pool with those
    entries advanced). Entries no row names come back as they were."""
    if s_pool.dtype != jnp.float32:
        raise ValueError(f"the SSM state pool is float32, got {s_pool.dtype}")
    return _ssm_decode_call(s_pool, entries, x, dt, a, b, c,
                            resolve_interpret(interpret))


def ssd_recurrent(x, dt, a, b, c, s0):
    """The token recurrence of one sequence: ``x`` ``[T, H, P]``, ``dt``
    ``[T, H]``, ``a`` ``[H]``, ``b, c`` ``[T, G, N]``, ``s0`` ``[H, N,
    P]``. -> (``y`` ``[T, H, P]``, ``S_T``). float32."""
    f32 = jnp.float32
    g = _group_of(x.shape[1], b.shape[1])
    a = a.astype(f32)

    def step(s, t):
        x_t, dt_t, b_t, c_t = t
        s = (s * jnp.exp(dt_t * a)[:, None, None]
             + b_t[g][:, :, None] * (dt_t[:, None] * x_t)[:, None, :])
        return s, jnp.einsum("hnp,hn->hp", s, c_t[g], precision=_HIGHEST)

    s, y = lax.scan(step, s0.astype(f32),
                    tuple(v.astype(f32) for v in (x, dt, b, c)))
    return y, s


def ssd_chunked(x, dt, a, b, c, s0, chunk: int = 128):
    """The same function as :func:`ssd_recurrent`, a chunk of ``C``
    tokens at a time (``T`` a multiple of ``C``). Inside a chunk, with
    ``S_0`` the state going in and ``L_t = sum_{s<=t} dt_s a`` (a head's
    cumulative log decay, at most 0)::

        y_t = exp(L_t) S_0^T C_t
              + sum_{j<=t} exp(L_t - L_j) (C_t . B_j) dt_j x_j
        S_C = exp(L_C) S_0 + sum_j exp(L_C - L_j) B_j (dt_j x_j)^T

    Every ratio is formed as ``exp(L_t - L_j)`` with ``j <= t`` (at most
    1). A token with ``dt = 0`` (a bucket's pad) neither decays nor
    writes the state. All float32, products at full precision."""
    t, heads, p = x.shape
    groups, n = b.shape[1], b.shape[2]
    per = heads // groups
    if t % chunk:
        raise ValueError(f"{t} tokens are not whole chunks of {chunk}")
    f32 = jnp.float32
    k = t // chunk
    # [T, ...] -> [K, ...] chunks; heads as [G, per]
    xs = (dt.astype(f32)[..., None] * x.astype(f32)).reshape(
        k, chunk, groups, per, p).transpose(0, 2, 3, 1, 4)   # [K,G,per,C,P]
    la = (dt.astype(f32) * a.astype(f32)).reshape(
        k, chunk, groups, per).transpose(0, 2, 3, 1)         # [K,G,per,C]
    bc, cc = (v.astype(f32).reshape(k, chunk, groups, n).transpose(0, 2, 1, 3)
              for v in (b, c))                                # [K,G,C,N]
    i = jnp.arange(chunk)
    upto = i[:, None] >= i[None, :]                           # j <= t
    mm = functools.partial(jnp.einsum, precision=_HIGHEST)

    def step(s, inp):               # s [G, per, N, P]
        dx, l, bk, ck = inp
        cum = jnp.cumsum(l, axis=-1)                          # [G,per,C]
        ratio = jnp.exp(jnp.where(upto, cum[..., :, None] - cum[..., None, :],
                                  -jnp.inf))                  # [G,per,C,C]
        cb = mm("gtn,gjn->gtj", ck, bk)                       # [G,C,C]
        y = (jnp.exp(cum)[..., None] * mm("gtn,ghnp->ghtp", ck, s)
             + mm("ghtj,ghjp->ghtp", ratio * cb[:, None], dx))
        last = cum[..., -1:]                                  # [G,per,1]
        s = (jnp.exp(last)[..., None] * s
             + mm("gjn,ghjp->ghnp", bk, jnp.exp(last - cum)[..., None] * dx))
        return s, y

    s, y = lax.scan(step, s0.astype(f32).reshape(groups, per, n, p),
                    (xs, la, bc, cc))
    y = y.transpose(0, 3, 1, 2, 4).reshape(t, heads, p)       # [K,G,per,C,P]
    return y, s.reshape(heads, n, p)
