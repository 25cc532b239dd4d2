"""Kimi Delta Attention (KDA): a gated delta-rule linear attention whose
cache is one matrix a head a sequence, not a row a token.

Per head, with state ``S`` in ``R^{dk x dv}`` (keys x values, float32,
zero at a sequence's start), per token ``t`` (``q_t`` scaled and ``k_t``
L2-normalised by the caller; ``g_t <= 0`` the per-key-channel log decay,
``beta_t`` in (0, 1) the write rate)::

    S'  = Diag(exp(g_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

Before the recurrence a layer passes its q, k and v projections through
a causal depthwise convolution whose last inputs are cached a sequence as
the state is; :func:`kda_conv_step` is its single-token step over a pool
of tails (``nezha_kda_conv``), :func:`kda_conv_step_reference` its twin.
Three forms of the recurrence live here:

- :func:`kda_decode`: ONE token a row against a pool of states
  ``[N, H, dk, dv]`` (``serve/slots.py``'s state group: one entry a
  slot), as one pass over the state in a Pallas kernel
  (``nezha_kda_decode``): a grid step loads a row's ``H`` matrices once,
  forms ``S'``, ``S'^T k``, ``S_t`` and ``S_t^T q`` in VMEM and writes
  ``S_t`` back over its own input (``input_output_aliases``). It is VPU
  and bandwidth work (64 KB a head read and written against ~100 kFLOP):
  no matmul unit is used, because an ``M = 1`` product pays a whole
  weight load of the state. :func:`kda_decode_reference` is its
  ``jax.numpy`` twin: the path off the TPU and the other side of the
  interpret-mode tests.
- :func:`kda_chunked`: a run of tokens of ONE sequence in chunks of ``C``
  (prefill), all float32; the algebra is in its docstring.
- :func:`kda_recurrent`: the recurrence itself under ``lax.scan`` (the
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


def kda_decode_reference(s_pool, entries, q, k, v, g, beta):
    """What :func:`kda_decode` computes, composed: gather the rows'
    states, one step of the recurrence, scatter them back."""
    s = s_pool[entries].astype(jnp.float32)                 # [B, H, dk, dv]
    sp = s * jnp.exp(g)[..., None]
    pred = jnp.einsum("bhkv,bhk->bhv", sp, k, precision=_HIGHEST)
    u = beta[..., None] * (v - pred)
    st = sp + k[..., None] * u[..., None, :]
    o = jnp.einsum("bhkv,bhk->bhv", st, q, precision=_HIGHEST)
    return o, s_pool.at[entries].set(st.astype(s_pool.dtype))


def _kda_decode_kernel(ent_ref, cols_ref, vb_ref, s_ref, o_ref, s_out_ref, *,
                       heads: int):
    """One grid step = one row, all ``heads``. ``cols_ref`` ``[1, dk,
    4H]`` holds, a key channel a sublane, four COLUMN vectors a head in
    its lanes: ``q | k | exp(g) | beta * k`` (head ``h`` in lanes ``h``,
    ``H + h``, ``2H + h``, ``3H + h``), so a head's column is a static
    lane slice broadcast over the state's lanes and no vector is turned
    from a row into a column in the kernel; ``vb_ref`` ``[1, H, dv]`` is
    ``beta * v`` a row a head. ``beta (v - S'^T k) = beta v - S'^T (beta
    k)``: the write rate is folded into the operands."""
    del ent_ref     # read by the index maps
    h_ = heads
    cols = cols_ref[0]
    for h in range(h_):
        q = cols[:, h:h + 1]
        k = cols[:, h_ + h:h_ + h + 1]
        a = cols[:, 2 * h_ + h:2 * h_ + h + 1]
        kb = cols[:, 3 * h_ + h:3 * h_ + h + 1]
        sp = s_ref[0, h] * a                                  # S'
        u = vb_ref[0, h:h + 1, :] - jnp.sum(sp * kb, axis=0, keepdims=True)
        st = sp + k * u
        s_out_ref[0, h] = st
        o_ref[0, h:h + 1, :] = jnp.sum(st * q, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _kda_decode_call(s_pool, entries, q, k, v, g, beta, interpret):
    b, h, dk = q.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    cols = jnp.concatenate([q, k, jnp.exp(g), beta[..., None] * k],
                           axis=1).transpose(0, 2, 1)         # [B, dk, 4H]
    vb = beta[..., None] * v
    row = lambda b_, ent: (b_, 0, 0)                          # noqa: E731
    state = pl.BlockSpec((1, h, dk, dv), lambda b_, ent: (ent[b_], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(b,),
        in_specs=[pl.BlockSpec((1, dk, 4 * h), row),
                  pl.BlockSpec((1, h, dv), row), state],
        out_specs=[pl.BlockSpec((1, h, dv), row), state])
    return pl.pallas_call(
        functools.partial(_kda_decode_kernel, heads=h),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, h, dv), f32),
                   jax.ShapeDtypeStruct(s_pool.shape, s_pool.dtype)],
        # operands: entries(0) cols(1) vb(2) s_pool(3): the pool aliases
        # its output, so a step rewrites the rows' entries in place
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # a row's state in and out, double-buffered: 4 x H*dk*dv*4 B
            # (8 MB at 32 heads of 128 x 128) plus the unrolled heads'
            # temporaries
            vmem_limit_bytes=64 * 2 ** 20),
        interpret=interpret,
        name="nezha_kda_decode",
    )(jnp.asarray(entries, jnp.int32), cols, vb, s_pool)


def kda_decode(s_pool, entries, q, k, v, g, beta,
               interpret: Optional[bool] = None):
    """One token a row: ``s_pool`` ``[N, H, dk, dv]`` float32, ``entries``
    ``[B]`` int32 (the pool entry each row's state lives in; rows that
    must not advance name a scratch entry), ``q, k, g`` ``[B, H, dk]``,
    ``v`` ``[B, H, dv]``, ``beta`` ``[B, H]``. -> (``o`` ``[B, H, dv]``
    float32, the pool with those entries advanced). Entries no row names
    come back as they were."""
    if s_pool.dtype != jnp.float32:
        raise ValueError(f"the KDA state pool is float32, got {s_pool.dtype}")
    return _kda_decode_call(s_pool, entries, q, k, v, g, beta,
                            resolve_interpret(interpret))


def kda_conv_step_reference(conv_pool, entries, x, w):
    """What :func:`kda_conv_step` computes, composed: gather the rows'
    tails, one output of the causal depthwise convolution, scatter the
    shifted tails back."""
    b, c = x.shape
    taps = w.shape[0] - 1
    rows = jnp.concatenate(
        [conv_pool[entries].reshape(b, taps, c),
         x[:, None].astype(conv_pool.dtype)], axis=1).astype(jnp.float32)
    y = sum(rows[:, i] * w[i].astype(jnp.float32) for i in range(taps + 1))
    new = rows[:, 1:].astype(conv_pool.dtype).reshape(
        (b,) + conv_pool.shape[1:])
    return y, conv_pool.at[entries].set(new)


def _kda_conv_kernel(ent_ref, x_ref, w_ref, tail_ref, y_ref, tail_out_ref, *,
                     taps: int):
    """One grid step = one row: its tail (``taps`` inputs of ``r`` rows
    of 128 lanes each, oldest first) and its new input in, the
    convolution's output and the tail shifted by one input out."""
    del ent_ref     # read by the index maps
    r = x_ref.shape[1]
    x = x_ref[0]
    y = w_ref[taps] * x.astype(jnp.float32)
    for i in range(taps):
        y += w_ref[i] * tail_ref[0, i * r:(i + 1) * r].astype(jnp.float32)
    y_ref[0] = y
    for i in range(taps - 1):
        tail_out_ref[0, i * r:(i + 1) * r] = tail_ref[
            0, (i + 1) * r:(i + 2) * r]
    tail_out_ref[0, (taps - 1) * r:] = x


@functools.partial(jax.jit, static_argnames=("interpret",))
def _kda_conv_call(conv_pool, entries, x, w, interpret):
    b, c = x.shape
    taps = w.shape[0] - 1
    rows, lanes = conv_pool.shape[1] // taps, conv_pool.shape[2]
    row = lambda b_, ent: (b_, 0, 0)                          # noqa: E731
    tail = pl.BlockSpec((1, taps * rows, lanes),
                        lambda b_, ent: (ent[b_], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(b,),
        in_specs=[pl.BlockSpec((1, rows, lanes), row),
                  pl.BlockSpec((taps + 1, rows, lanes),
                               lambda b_, ent: (0, 0, 0)), tail],
        out_specs=[pl.BlockSpec((1, rows, lanes), row), tail])
    y, conv_pool = pl.pallas_call(
        functools.partial(_kda_conv_kernel, taps=taps),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, rows, lanes), jnp.float32),
                   jax.ShapeDtypeStruct(conv_pool.shape, conv_pool.dtype)],
        # operands: entries(0) x(1) w(2) conv_pool(3)
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="nezha_kda_conv",
    )(jnp.asarray(entries, jnp.int32),
      x.astype(conv_pool.dtype).reshape(b, rows, lanes),
      w.astype(jnp.float32).reshape(taps + 1, rows, lanes), conv_pool)
    return y.reshape(b, c), conv_pool


def kda_conv_step(conv_pool, entries, x, w,
                  interpret: Optional[bool] = None):
    """One token a row through the causal depthwise convolution:
    ``conv_pool`` ``[N, taps * r, lanes]`` holds an entry's last ``taps``
    inputs, oldest first, each ``C = r * lanes`` channels (``lanes`` 128
    on the chip: whole tiles, an entry contiguous in HBM); ``entries``
    ``[B]`` as for :func:`kda_decode`; ``x`` ``[B, C]`` the new inputs;
    ``w`` ``[taps + 1, C]`` the filters. -> (``y`` ``[B, C]`` float32,
    ``y = sum_i w_i x_{t-taps+i}``; the pool with those entries' tails
    shifted by one input, in place). XLA's own gather and scatter of 256
    rows of 72 KB run a row at a time: 1.4 ms a layer a step against
    0.05 ms for the bytes (PERF.md section 6, PR 32)."""
    return _kda_conv_call(conv_pool, entries, x, w,
                          resolve_interpret(interpret))


def kda_recurrent(q, k, v, g, beta, s0):
    """The token recurrence of one sequence: ``q, k, g`` ``[T, H, dk]``,
    ``v`` ``[T, H, dv]``, ``beta`` ``[T, H]``, ``s0`` ``[H, dk, dv]``.
    -> (``o`` ``[T, H, dv]``, ``S_T``). float32."""
    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        sp = s * jnp.exp(g_t)[..., None]
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", sp, k_t,
                                             precision=_HIGHEST))
        s = sp + k_t[..., None] * u[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t, precision=_HIGHEST)

    f32 = jnp.float32
    s, o = lax.scan(step, s0.astype(f32),
                    tuple(x.astype(f32) for x in (q, k, v, g, beta)))
    return o, s


def kda_chunked(q, k, v, g, beta, s0, chunk: int = 64):
    """The same function as :func:`kda_recurrent`, a chunk of ``C`` tokens
    at a time (``T`` a multiple of ``C``). Inside a chunk, with ``S_0``
    the state going in and ``G_t = sum_{s<=t} g_s``::

        A_tj = beta_t sum_d k_td k_jd exp(G_td - G_jd)        (j < t)
        U    = (I + A)^-1 Diag(beta) (V - (exp(G) * K) S_0)
        o_t  = S_0^T (exp(G_t) * q_t)
               + sum_{j<=t} u_j sum_d k_jd q_td exp(G_td - G_jd)
        S_C  = Diag(exp(G_C)) S_0 + sum_j (exp(G_C - G_j) * k_j) u_j^T

    Every ratio is formed as ``exp(G_t - G_j)`` with ``j <= t`` (at most
    1), never as ``exp(G_t)`` times ``exp(-G_j)``, which overflows under
    strong decay. ``I + A`` is unit lower triangular and ``A`` is
    nilpotent, so its inverse is the finite product ``(I - A)(I + A^2)(I
    + A^4)...``: ``log2(C)`` squarings instead of ``C`` dependent rows. A
    token with ``beta = 0`` and ``g = 0`` (a bucket's pad) leaves the
    state as it was. All float32, products at full precision."""
    t, h, dk = q.shape
    c = chunk
    if t % c:
        raise ValueError(f"{t} tokens are not whole chunks of {c}")
    f32 = jnp.float32

    def chunks(x):      # [T, H, ...] -> [T/C, H, C, ...]
        x = x.astype(f32).reshape(t // c, c, *x.shape[1:])
        return jnp.swapaxes(x, 1, 2)

    i = jnp.arange(c)
    upto = i[:, None] >= i[None, :]                           # j <= t
    below = i[:, None] > i[None, :]                           # j <  t
    eye = jnp.eye(c, dtype=f32)
    mm = functools.partial(jnp.einsum, precision=_HIGHEST)

    def step(s, x):
        qc, kc, vc, gc, bc = x          # [H, C, dk|dv], bc [H, C]
        gcum = jnp.cumsum(gc, axis=1)
        ratio = jnp.exp(jnp.where(
            upto[None, :, :, None],
            gcum[:, :, None, :] - gcum[:, None, :, :], -jnp.inf))
        ke = kc[:, None, :, :] * ratio                        # [H, t, j, dk]
        a_kk = jnp.sum(kc[:, :, None, :] * ke, axis=-1)       # [H, t, j]
        a_qk = jnp.sum(qc[:, :, None, :] * ke, axis=-1)
        a = jnp.where(below, a_kk, 0.0) * bc[:, :, None]
        decay = jnp.exp(gcum)                                 # exp(G_t) <= 1
        rhs = bc[..., None] * (vc - mm("hcd,hdv->hcv", decay * kc, s))
        # (I + A)^-1 = prod_i (I + (-A)^(2^i))
        inv, power = eye - a, a
        for _ in range(max(c - 1, 1).bit_length() - 1):
            power = mm("hij,hjk->hik", power, power)
            inv = inv + mm("hij,hjk->hik", inv, power)
        u = mm("hij,hjv->hiv", inv, rhs)
        o = (mm("hcd,hdv->hcv", decay * qc, s)
             + mm("htj,hjv->htv", jnp.where(upto, a_qk, 0.0), u))
        last = gcum[:, -1:, :]
        s = (jnp.swapaxes(jnp.exp(last), 1, 2) * s
             + mm("hjd,hjv->hdv", jnp.exp(last - gcum) * kc, u))
        return s, o

    s, o = lax.scan(step, s0.astype(f32),
                    tuple(chunks(x) for x in (q, k, v, g, beta)))
    return jnp.swapaxes(o, 1, 2).reshape(t, h, -1), s
