"""Flash attention as Pallas TPU kernels — fused forward AND backward.

Forward: blockwise attention with online softmax — grid = (B, H, Q-blocks,
K-blocks) with the K dimension sequential ("arbitrary" semantics), VMEM
scratch carrying the running max/denominator/accumulator across K blocks,
and causal blocks skipped entirely above the diagonal. Q·Kᵀ and P·V hit the
MXU in fp32 accumulation; memory per program is O(block_q · block_k), never
the full S×S score matrix. The training path additionally emits the
per-row logsumexp residual (lane-broadcast to 128, the TPU-native layout).

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
    q rows to skip."""
    bq = s_q if s_q <= 1024 else 512
    return bq, 512


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

    # Causal: skip blocks strictly above the diagonal.
    run = (ki * block_k <= qi * block_q + block_q - 1) if causal else True

    @pl.when(run)
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

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finalize():
        _softmax_finalize(o_ref, m_scr, l_scr, acc_scr, lse_ref=lse_ref)


def _flash_call(q, k, v, causal, scale, block_q, block_k, interpret,
                return_lse: bool = False, kv_lengths=None):
    b, h, s_q, d = q.shape
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
    interpret = resolve_interpret(interpret)

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

    run = (ki * block_k <= qi * block_q + block_q - 1) if causal else True

    @pl.when(run)
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

    # Causal: q blocks entirely above the diagonal contribute nothing.
    run = (qi * block_q + block_q - 1 >= ki * block_k) if causal else True

    @pl.when(run)
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

    @pl.when(qi == pl.num_programs(3) - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd_call(q, k, v, o, lse, do, causal, scale, block_q, block_k,
                    interpret, kv_lengths=None):
    b, h, s_q, d = q.shape
    s_k = k.shape[2]
    if causal and s_q != s_k:
        raise ValueError(
            f"causal flash_attention requires s_q == s_k, got {s_q} != {s_k}")
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    auto_q, auto_k = _auto_blocks(s_q, s_k)
    bq = _pick_block(s_q, block_q or auto_q)
    bk = _pick_block(s_k, block_k or auto_k)
    interpret = resolve_interpret(interpret)

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
