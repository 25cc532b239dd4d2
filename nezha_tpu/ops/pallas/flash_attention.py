"""Flash attention as Pallas TPU kernels — fused forward AND backward.

Forward: blockwise attention with online softmax — grid = (B, H, Q-blocks,
K-blocks) with the K dimension sequential ("arbitrary" semantics), VMEM
scratch carrying the running max/denominator/accumulator across K blocks.
Q·Kᵀ and P·V hit the MXU in fp32 accumulation; memory per program is
O(block_q · block_k), never the full S×S score matrix. The training path
additionally emits the per-row logsumexp residual (lane-broadcast to 128,
the TPU-native layout).

Causal skipping happens at two grains. Between grid blocks, a block wholly
above the diagonal is skipped. Inside a q block several key blocks tall
(the whole-row block at 512 < S <= 1024), a key block's row strips above
the square the diagonal crosses are skipped: the square is masked and the
strips below it are computed whole (``_sub_tile``, ``_strips``), three of
the four 512-row strips at S = 1024. Where the square is the q block's
first strip there is nothing above it to skip and the block keeps its
whole body. Finer sub-tiles lost on a v5e (PERF.md §6): each fold
of a sub-tile pays a fixed latency that a 128- or 256-wide square walked
one a loop iteration does not amortise. The strips are walked by a
``lax.fori_loop`` whose trip count is a traced value, not unrolled in
Python, so the lowered body is one size whatever their number
(``tests/test_tpu_compile.py`` holds it to that); and the calls are jitted,
so a model's layers share one trace and one lowering of each kernel.

Backward (FlashAttention-2 style): two kernels that recompute P blockwise
from (q, k, lse) instead of materializing S×S —

* dQ kernel: grid (B, H, Q-blocks, K-blocks), K sequential, accumulating
  dq = Σ_k ds·K with ds = P∘(dP − δ), dP = dO·Vᵀ, δ = rowsum(dO∘O)
  computed in-register from the dO/O blocks (never materialized).
* dK/dV kernel: grid (B, H, K-blocks, Q-blocks), Q sequential, accumulating
  dv = Σ_q Pᵀ·dO and dk = Σ_q dsᵀ·Q.

(Reference composes attention from graph ops — SURVEY.md §1; these kernels
are the TPU-fused production path for long-context training, where the
S×S score matrix would dominate HBM.)

Measured on a v5e chip (fwd+bwd, bf16, causal): with the tuned block
sizes in ``_auto_blocks`` (whole-row q blocks at S<=1024, square 512s
beyond) this kernel beats XLA's fused composed attention at every
measured S — 12.8ms vs 14.2ms at S=1024 (B=8 H=12 D=64; +17% e2e on
GPT-2 train), 17.9ms vs 23.9ms at S=2048 — and is the only option at
S=32k, where the composed path fails to compile (the S×S scores alone
need ~24 GB HBM) while these kernels run the step in ~0.95 s. An
early untuned square-block build lost to XLA below S=16k; the
block-size policy is what closed that, so keep ``_auto_blocks`` in
sync with measurements. ``attn_impl="auto"`` selects flash on TPU.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from nezha_tpu import obs

# The online-softmax scratch math and the package scalar helpers live
# in ops/pallas/common.py (shared with the decode and prefill kernels).
from nezha_tpu.ops.pallas.common import (
    LANES as _LANES,
    NEG_BIG as _NEG_BIG,
    pick_block as _pick_block,
    resolve_interpret,
    scratch_init as _scratch_init,
    softmax_block_update as _softmax_block_update,
    softmax_finalize as _softmax_finalize,
)


def _auto_blocks(s_q: int, s_k: int):
    """Measured-on-v5e defaults (bf16 fwd+bwd, B=8 H=12 D=64): at S<=1024 a
    single whole-row q block wins (grid overhead dominates; 12.8ms vs 14.2ms
    XLA at S=1024); at S>=2048 square 512 blocks win (17.9ms vs 23.9ms XLA
    at S=2048) — the causal block-skip starts paying once there are enough
    q rows to skip. A whole-row block skips nothing between grid blocks: it
    skips the row strips above the diagonal inside it (``_sub_tile``)."""
    bq = s_q if s_q <= 1024 else 512
    return bq, 512


def _sub_tile(block_q: int, block_k: int) -> Optional[int]:
    """Side of the square sub-tiles a causal ``block_q x block_k`` block is
    walked in, a row strip of it at a time: the key block's width, so one
    strip is the square the diagonal crosses and the others lie wholly
    above it (skipped) or below it. None where the block keeps its whole
    body: a q block of one strip, or not of whole strips. (Timed alone on a
    v5e at [16, 12, 1024, 64], the three kernels walking square sub-tiles
    of 128 or 256 a loop iteration ran 1.2-2.0x slower than the whole
    blocks they replaced, and strips of 512 faster; PERF.md §6.)"""
    if block_q % block_k or block_q == block_k:
        return None
    return block_k


def _causal_tile_share(s: int, block_q: int, block_k: int) -> float:
    """Share of the S x S score matrix a causal call computes: the grid
    blocks it runs, and inside a block the diagonal crosses only the strips
    from its diagonal square down (what the
    ``kernel.flash.causal_tile_share`` gauge reads)."""
    tiled = _sub_tile(block_q, block_k) is not None
    done = 0
    for q0 in range(0, s, block_q):
        for k0 in range(0, s, block_k):
            if k0 > q0 + block_q - 1:
                continue
            if tiled and k0 + block_k - 1 > q0:
                done += (q0 + block_q - k0) * block_k
            else:
                done += block_q * block_k
    return done / (s * s)


def _causal_blocks(q0, k0, block_q, block_k, whole, tiled):
    """Run one causal grid block whose first row is ``q0`` and first column
    ``k0``: ``tiled`` where the diagonal crosses the block below its first
    strip (so there are strips above it to skip), ``whole`` where it lies
    wholly on or below the diagonal or the diagonal crosses its first strip,
    nothing where it lies wholly above."""
    runs = k0 <= q0 + block_q - 1
    if _sub_tile(block_q, block_k) is None:
        pl.when(runs)(whole)
    else:
        pl.when(k0 <= q0)(whole)
        pl.when(jnp.logical_and(runs, k0 > q0))(tiled)


def _strips(sq, block_q, block_k, strip, state):
    """``strip(i, rows, state)`` for the row strips ``i`` of a q block from
    its diagonal square (strip ``sq``) to its last, by a loop whose trip
    count is a traced value: one body whatever their number."""
    def body(i, st):
        rows = pl.ds(pl.multiple_of(i * block_k, block_k), block_k)
        return strip(i, rows, st)

    return lax.fori_loop(sq, block_q // block_k, body, state)


def _strip_scores(q, k, scale, i, sq, ki, block_k, kv_len):
    """Scaled scores of strip ``i`` of a q block against its key block
    ``ki``, whose diagonal square is strip ``sq``: causally masked (a strip
    below the square keeps all of it), and past ``kv_len``."""
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
    s = _causal_mask(s, i, sq, block_k, block_k)
    if kv_len is not None:
        s = _length_mask(s, ki, block_k, kv_len)
    return s


def _causal_mask(s, qi, ki, block_q, block_k):
    qpos = qi * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = ki * block_k + lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return jnp.where(kpos <= qpos, s, _NEG_BIG)


_GRID_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))


def _length_mask(s, ki, block_k, kv_len):
    """Mask key columns at positions >= kv_len (right-padding support).
    ``kv_len`` is this batch row's scalar from the scalar-prefetched
    lengths operand."""
    kpos = ki * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(kpos < kv_len, s, _NEG_BIG)


# ---------------------------------------------------------------- forward
def _fwd_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                acc_scr, *, scale: float, causal: bool, block_q: int,
                block_k: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    kv_len = None if len_ref is None else len_ref[pl.program_id(0)]

    @pl.when(ki == 0)
    def _init():
        _scratch_init(m_scr, l_scr, acc_scr)

    def _block():
        # Dots take the inputs' native dtype (bf16 on the training path —
        # double MXU rate vs fp32) and accumulate fp32; softmax stats and
        # the running accumulator stay fp32 throughout.
        q = q_ref[0, 0]                                      # [bq, d]
        k = k_ref[0, 0]                                      # [bk, d]
        v = v_ref[0, 0]                                      # [bk, d]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k)
        if kv_len is not None:
            s = _length_mask(s, ki, block_k, kv_len)
        _softmax_block_update(s, v, m_scr, l_scr, acc_scr)

    def _tiled():
        # The online softmax on each strip's rows of the scratch.
        sq = ki - qi * (block_q // block_k)

        def strip(i, rows, carry):
            s = _strip_scores(q_ref[0, 0, rows, :], k_ref[0, 0], scale, i,
                              sq, ki, block_k, kv_len)
            _softmax_block_update(s, v_ref[0, 0], m_scr, l_scr, acc_scr,
                                  rows)
            return carry

        _strips(sq, block_q, block_k, strip, 0)

    if causal:
        _causal_blocks(qi * block_q, ki * block_k, block_q, block_k,
                       _block, _tiled)
    else:
        pl.when(True)(_block)

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finalize():
        _softmax_finalize(o_ref, m_scr, l_scr, acc_scr, lse_ref=lse_ref)


def _plan(q, k, causal, scale, block_q, block_k, interpret):
    """(scale, block_q, block_k, interpret) of a call. A causal call sets
    the ``kernel.flash.causal_tile_share`` gauge as it is traced."""
    s_q, d = q.shape[2:]
    s_k = k.shape[2]
    if causal and s_q != s_k:
        # _causal_mask has no (s_k - s_q) diagonal offset, so rectangular
        # causal inputs would get a silently-wrong mask.
        raise ValueError(
            f"causal flash_attention requires s_q == s_k, got {s_q} != {s_k}")
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    auto_q, auto_k = _auto_blocks(s_q, s_k)
    bq = _pick_block(s_q, block_q or auto_q)
    bk = _pick_block(s_k, block_k or auto_k)
    if causal:
        obs.gauge("kernel.flash.causal_tile_share").set(
            _causal_tile_share(s_q, bq, bk))
    return scale, bq, bk, resolve_interpret(interpret)


# The two calls are jitted on their static arguments, so a model's layers
# share one trace and one lowering of each kernel: a kernel body is
# otherwise traced and lowered again at every call, a layer at a time, on
# every process start and before the compile cache is consulted.
@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "block_q", "block_k", "interpret", "return_lse"))
def _flash_call(q, k, v, causal, scale, block_q, block_k, interpret,
                return_lse: bool = False, kv_lengths=None):
    b, h, s_q, d = q.shape
    s_k = k.shape[2]
    scale, bq, bk, interpret = _plan(q, k, causal, scale, block_q, block_k,
                                     interpret)

    has_len = kv_lengths is not None
    full = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                             block_q=bq, block_k=bk)

    def kernel(*refs):
        # The kernel's (len_ref, lse_ref) slots are optional: splice None
        # into whichever this call doesn't wire.
        refs = list(refs)
        len_ref = refs.pop(0) if has_len else None
        lse_ref = refs.pop(4) if return_lse else None
        full(len_ref, *refs[:4], lse_ref, *refs[4:])

    qo_spec = pl.BlockSpec((1, 1, bq, d),
                           lambda b_, h_, qi, ki, *_: (b_, h_, qi, 0))
    kv_spec = pl.BlockSpec((1, 1, bk, d),
                           lambda b_, h_, qi, ki, *_: (b_, h_, ki, 0))
    # Lengths ride as a scalar-prefetch operand (SMEM); each program
    # reads its batch row's scalar.
    prefetch = [jnp.asarray(kv_lengths, jnp.int32)] if has_len else []
    out_specs = qo_spec
    out_shape = jax.ShapeDtypeStruct(q.shape, q.dtype)
    if return_lse:
        lse_spec = pl.BlockSpec((1, 1, bq, _LANES),
                                lambda b_, h_, qi, ki, *_: (b_, h_, qi, 0))
        out_specs = [qo_spec, lse_spec]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((b, h, s_q, _LANES), jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b, h, s_q // bq, s_k // bk),
        in_specs=[qo_spec, kv_spec, kv_spec],
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((bq, _LANES), jnp.float32),
                        pltpu.VMEM((bq, _LANES), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=_GRID_PARAMS,
        interpret=interpret,
        name="nezha_flash_fwd",
    )(*prefetch, q, k, v)


# --------------------------------------------------------------- backward
def _recompute_p(q_ref, k_ref, lse_ref, qi, ki, scale, causal, bq, bk,
                 kv_len=None):
    q = q_ref[0, 0]                                          # [bq, d]
    k = k_ref[0, 0]                                          # [bk, d]
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
    if causal:
        s = _causal_mask(s, qi, ki, bq, bk)
    if kv_len is not None:
        s = _length_mask(s, ki, bk, kv_len)
    return jnp.exp(s - lse_ref[0, 0][:, :1])                 # [bq, bk]


def _ds_block(p, do, o, v, scale):
    """ds = p * (dp - delta) * scale, delta computed from the dO/O blocks.

    ``do``/``v`` native dtype for the MXU dot; ``p``/``delta`` fp32."""
    dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32)  # [bq, bk]
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)                   # [bq, 1]
    return p * (dp - delta) * scale


def _bwd_dq_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                   dq_ref, dq_scr, delta_scr, *, scale, causal, block_q,
                   block_k):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    kv_len = None if len_ref is None else len_ref[pl.program_id(0)]

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)
        # delta depends only on the q block — compute once per q row, not
        # once per K iteration.
        do = do_ref[0, 0].astype(jnp.float32)
        o = o_ref[0, 0].astype(jnp.float32)
        delta = jnp.sum(do * o, axis=-1, keepdims=True)      # [bq, 1]
        delta_scr[:] = jnp.broadcast_to(delta, delta_scr.shape)

    def _block():
        p = _recompute_p(q_ref, k_ref, lse_ref, qi, ki, scale, causal,
                         block_q, block_k, kv_len)
        do = do_ref[0, 0]
        v = v_ref[0, 0]
        k = k_ref[0, 0]
        dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta_scr[:, :1]) * scale             # [bq, bk]
        dq_scr[:] += lax.dot_general(ds.astype(k.dtype), k,
                                     (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)

    def _tiled():
        # Each strip's dq, added to its rows of dq_scr.
        sq = ki - qi * (block_q // block_k)

        def strip(i, rows, carry):
            k = k_ref[0, 0]
            p = jnp.exp(_strip_scores(q_ref[0, 0, rows, :], k, scale, i, sq,
                                      ki, block_k, kv_len)
                        - lse_ref[0, 0, rows, :][:, :1])
            dp = lax.dot_general(do_ref[0, 0, rows, :], v_ref[0, 0],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
            ds = p * (dp - delta_scr[rows, :][:, :1]) * scale
            dq_scr[rows, :] += lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return carry

        _strips(sq, block_q, block_k, strip, 0)

    if causal:
        _causal_blocks(qi * block_q, ki * block_k, block_q, block_k,
                       _block, _tiled)
    else:
        pl.when(True)(_block)

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finalize():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal,
                    block_q, block_k):
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    kv_len = None if len_ref is None else len_ref[pl.program_id(0)]

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _block():
        p = _recompute_p(q_ref, k_ref, lse_ref, qi, ki, scale, causal,
                         block_q, block_k, kv_len)
        do = do_ref[0, 0]
        o = o_ref[0, 0]
        v = v_ref[0, 0]
        q = q_ref[0, 0]
        dv_scr[:] += lax.dot_general(p.astype(do.dtype), do,
                                     (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        ds = _ds_block(p, do, o, v, scale)                   # [bq, bk]
        dk_scr[:] += lax.dot_general(ds.astype(q.dtype), q,
                                     (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)

    def _tiled():
        # dk and dv summed over the strips in values the loop carries,
        # added to the scratch once.
        sq = ki - qi * (block_q // block_k)

        def strip(i, rows, acc):
            q, do = q_ref[0, 0, rows, :], do_ref[0, 0, rows, :]
            v = v_ref[0, 0]
            p = jnp.exp(_strip_scores(q, k_ref[0, 0], scale, i, sq, ki,
                                      block_k, kv_len)
                        - lse_ref[0, 0, rows, :][:, :1])
            dv = acc[1] + lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = _ds_block(p, do, o_ref[0, 0, rows, :], v, scale)
            dk = acc[0] + lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return dk, dv

        zero = jnp.zeros(dk_scr.shape, jnp.float32)
        dk, dv = _strips(sq, block_q, block_k, strip, (zero, zero))
        dk_scr[:] += dk
        dv_scr[:] += dv

    if causal:
        _causal_blocks(qi * block_q, ki * block_k, block_q, block_k,
                       _block, _tiled)
    else:
        pl.when(True)(_block)

    @pl.when(qi == pl.num_programs(3) - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "block_q", "block_k", "interpret"))
def _flash_bwd_call(q, k, v, o, lse, do, causal, scale, block_q, block_k,
                    interpret, kv_lengths=None):
    b, h, s_q, d = q.shape
    s_k = k.shape[2]
    scale, bq, bk, interpret = _plan(q, k, causal, scale, block_q, block_k,
                                     interpret)

    def spec(rows, outer, minor=d):
        """``rows``-tall sequence block indexed by the grid's OUTER
        sequence dim (2) or its inner, sequential one (3): the dq grid is
        (.., q-blocks, k-blocks), the dk/dv grid (.., k-blocks,
        q-blocks)."""
        if outer:
            return pl.BlockSpec((1, 1, rows, minor),
                                lambda b_, h_, i, j, *_: (b_, h_, i, 0))
        return pl.BlockSpec((1, 1, rows, minor),
                            lambda b_, h_, i, j, *_: (b_, h_, j, 0))

    # The lse residual is saved compactly as [B, H, S]; re-broadcast to the
    # TPU lane layout only transiently for the kernel calls (a per-layer
    # scratch, not a residual pinned across the whole forward pass).
    lse = jnp.broadcast_to(lse[..., None], lse.shape + (_LANES,))

    has_len = kv_lengths is not None
    prefetch = [jnp.asarray(kv_lengths, jnp.int32)] if has_len else []
    operands = [*prefetch, q, k, v, o, do, lse]

    def with_len(kernel_fn):
        bound = functools.partial(kernel_fn, scale=scale, causal=causal,
                                  block_q=bq, block_k=bk)
        if has_len:
            return bound
        return lambda *refs: bound(None, *refs)  # empty len_ref slot

    def in_specs(q_major):
        return [spec(bq, q_major), spec(bk, not q_major),
                spec(bk, not q_major), spec(bq, q_major),
                spec(bq, q_major), spec(bq, q_major, _LANES)]

    dq = pl.pallas_call(
        with_len(_bwd_dq_kernel),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(b, h, s_q // bq, s_k // bk),
            in_specs=in_specs(True),
            out_specs=spec(bq, True),
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32),
                            pltpu.VMEM((bq, _LANES), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=_GRID_PARAMS,
        interpret=interpret,
        name="nezha_flash_bwd_dq",
    )(*operands)

    dk, dv = pl.pallas_call(
        with_len(_bwd_dkv_kernel),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(b, h, s_k // bk, s_q // bq),
            in_specs=in_specs(False),
            out_specs=[spec(bk, True), spec(bk, True)],
            scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                            pltpu.VMEM((bk, d), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        compiler_params=_GRID_PARAMS,
        interpret=interpret,
        name="nezha_flash_bwd_dkv",
    )(*operands)
    return dq, dk, dv


# ---------------------------------------------------- ring building blocks
# Raw (no-VJP) entry points for ring attention (parallel/ring.py), which
# authors its OWN custom VJP over the whole ring: the forward needs each
# hop's (out, lse) pair to merge blocks log-sum-exp-stably, and the
# backward re-runs the per-block kernels with the GLOBAL row lse (which
# makes the recomputed p the true global softmax probability — the
# standard multi-block flash backward).


def flash_block_fwd(q, k, v, causal: bool, scale: Optional[float] = None,
                    interpret: Optional[bool] = None):
    """One block pair, no autodiff: -> (out [B,H,S,D], lse [B,H,S] fp32)."""
    out, lse = _flash_call(q, k, v, causal, scale, None, None, interpret,
                           return_lse=True)
    return out, lse[..., 0]


def flash_block_bwd(q, k, v, o, lse, do, causal: bool,
                    scale: Optional[float] = None,
                    interpret: Optional[bool] = None):
    """Gradients for one block pair given the GLOBAL row lse [B,H,S] and
    the GLOBAL output o (delta = rowsum(dO*O)): -> (dq, dk, dv)."""
    return _flash_bwd_call(q, k, v, o, lse, do, causal, scale, None, None,
                           interpret)


# ------------------------------------------------------------- public API
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention_dense(q, k, v, causal: bool = True,
                           scale: Optional[float] = None,
                           block_q: Optional[int] = None,
                           block_k: Optional[int] = None,
                           interpret: Optional[bool] = None):
    return _flash_call(q, k, v, causal, scale, block_q, block_k, interpret)


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    out, lse = _flash_call(q, k, v, causal, scale, block_q, block_k,
                           interpret, return_lse=True)
    # Residual kept at [B, H, S] (1/128th of the kernel's lane-broadcast
    # output) — at long context the broadcast form would rival the K/V
    # residuals themselves in HBM.
    return out, (q, k, v, out, lse[..., 0])


def _flash_bwd(causal, scale, block_q, block_k, interpret, residuals, g):
    q, k, v, out, lse = residuals
    return _flash_bwd_call(q, k, v, out, lse, g, causal, scale, block_q,
                           block_k, interpret)


_flash_attention_dense.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_attention_varlen(q, k, v, kv_lengths, causal, scale, block_q,
                            block_k, interpret):
    return _flash_call(q, k, v, causal, scale, block_q, block_k, interpret,
                       kv_lengths=kv_lengths)


def _flash_varlen_fwd(q, k, v, kv_lengths, causal, scale, block_q, block_k,
                      interpret):
    out, lse = _flash_call(q, k, v, causal, scale, block_q, block_k,
                           interpret, return_lse=True, kv_lengths=kv_lengths)
    return out, (q, k, v, out, lse[..., 0], kv_lengths)


def _flash_varlen_bwd(causal, scale, block_q, block_k, interpret, residuals,
                      g):
    import numpy as np

    q, k, v, out, lse, kv_lengths = residuals
    dq, dk, dv = _flash_bwd_call(q, k, v, out, lse, g, causal, scale,
                                 block_q, block_k, interpret,
                                 kv_lengths=kv_lengths)
    # Integer lengths carry no gradient: the float0 zero cotangent.
    dlen = np.zeros(kv_lengths.shape, dtype=jax.dtypes.float0)
    return dq, dk, dv, dlen


_flash_attention_varlen.defvjp(_flash_varlen_fwd, _flash_varlen_bwd)


def flash_attention(q, k, v, causal: bool = True,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    kv_lengths=None):
    """q, k, v: [B, H, S, D] -> [B, H, S, D].

    ``block_q``/``block_k`` default to the measured-best sizes for the
    sequence length (see ``_auto_blocks``). ``interpret=None``
    auto-selects: compiled on TPU backends, interpreter elsewhere (so CPU
    tests run the same kernel code).

    ``kv_lengths`` ([B] int32) masks key/value positions at or beyond each
    batch row's length — the right-padding contract (BERT on real,
    unpacked data). Lengths are clamped to >= 1: a fully-padded row
    attends to position 0 only (without the clamp the kernel's online
    softmax would silently attend uniformly to ALL positions, while the
    composed-XLA path NaNs — one defined behavior for both). Query rows
    beyond the length produce arbitrary finite outputs; downstream must
    mask them (MLM's -100 labels do). Gradients for padded keys/values
    are exactly zero — except position 0 of a zero-length row, which the
    clamp makes attendable and which therefore carries gradient.
    """
    if kv_lengths is None:
        return _flash_attention_dense(q, k, v, causal, scale, block_q,
                                      block_k, interpret)
    kv_lengths = jnp.maximum(jnp.asarray(kv_lengths, jnp.int32), 1)
    return _flash_attention_varlen(q, k, v, kv_lengths, causal, scale,
                                   block_q, block_k, interpret)
