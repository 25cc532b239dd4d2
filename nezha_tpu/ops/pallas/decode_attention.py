"""Flash-decode: batched single-token attention against a pooled KV cache.

The serving decode step asks one question per row: attend ONE query token
over that row's cache prefix ``[0, length)``. The composed path answers it
by materializing a ``[B, 1, 1, L_max]`` additive mask and running dense
attention over the FULL pool — every row pays ``L_max`` bandwidth whatever
its depth, and the softmax round-trips a score matrix through HBM. This
kernel is built for the actual access pattern (the "Harnessing HPC
Kernels" argument from PAPERS.md: shape-specialized hot loops deserve a
kernel, not a generic lowering):

- grid ``(B, H, KV-blocks)`` with the KV dimension sequential
  ("arbitrary" semantics) — the split-K layout: each program folds one
  KV block into VMEM running ``(max, sum, acc)`` scratch via online
  softmax, merged at the final block (no score matrix, no mask tensor);
- per-row ``lengths`` ride as a SCALAR-PREFETCH operand (SMEM — the TPU
  lowering refuses a ``(1, 128)`` VMEM block over ``[B, 128]``): a
  program whose block starts at or past its row's length SKIPS the block
  entirely (``@pl.when``), so short rows and inactive rows
  (``length == 0``) cost block-bookkeeping only — compute is
  proportional to ``sum(lengths)``, not ``B * L_max``;
- Q·Kᵀ and P·V accumulate fp32 over the caches' native dtype (bf16 pool
  dots run at the doubled MXU rate; the softmax statistics and the
  accumulator stay fp32 throughout);
- ``interpret=None`` auto-selects the Pallas interpreter off-TPU, so CPU
  tests exercise the same kernel code that compiles on hardware
  (tests/test_tpu_compile.py compiles every variant for a v5e).

Decode is inference-only, so there is no VJP; ``models/gpt2.py`` routes
its single-token cache branch here behind the ``attn_impl="auto"``
resolution (``GPT2Config.decode_impl`` / ``NEZHA_NO_DECODE_KERNEL=1``
are the escape hatches back to the composed masked path).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The online-softmax core (scratch init / block fold / finalize) is
# shared with flash_attention.py and prefill_attention.py — see
# ops/pallas/common.py. The aliases keep this module's kernel bodies
# reading as before; the math is bit-identical to the pre-factoring
# inline version.
from nezha_tpu.ops.pallas.common import (
    LANES as _LANES,
    block_scale,
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


def _paged_decode_kernel(tab_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                         m_scr, l_scr, acc_scr, *, scale: float,
                         block_k: int):
    # Identical math to the dense kernel: the block table only changed
    # WHERE block ki lives (the BlockSpec index map gathered it), not
    # what it means — per-row lengths still skip blocks at/past the
    # row's depth, so work tracks sum(lengths) over the block
    # indirection exactly as it did over the dense pool.
    _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, scale=scale, block_k=block_k)


def _paged_quant_decode_kernel(tab_ref, len_ref, q_ref, k_ref, v_ref,
                               ks_ref, vs_ref, o_ref, m_scr, l_scr,
                               acc_scr, *, scale: float, block_k: int):
    """Paged kernel over an INT8 block pool: the row's per-block fp32
    scales ride as one ``[1, M]`` lane vector per (row, head) (see
    :func:`gather_row_scales`) and the dequant happens right here in
    the block loop — int8 blocks never round-trip through a dense bf16
    cache. Dequantized tiles are cast to the query's dtype (bf16 pools
    dot at the doubled MXU rate); softmax statistics and the
    accumulator stay fp32."""
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        _scratch_init(m_scr, l_scr, acc_scr)

    length = len_ref[pl.program_id(0)]
    run = ki * block_k < length

    @pl.when(run)
    def _block():
        q = q_ref[0, 0]                                      # [1, d]
        # THE dequant both attention paths share (see
        # ops/quant.dequantize_kv_block): int8 * fp32 scale, cast to
        # the compute dtype — the XLA gather fallback applies the same
        # expression, so kernel and fallback see identical tiles.
        k = (k_ref[0, 0].astype(jnp.float32)
             * block_scale(ks_ref, ki)).astype(q.dtype)      # [bk, d]
        v = (v_ref[0, 0].astype(jnp.float32)
             * block_scale(vs_ref, ki)).astype(q.dtype)      # [bk, d]
        _block_step(q, k, v, length, ki, m_scr, l_scr, acc_scr,
                    scale=scale, block_k=block_k)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _final():
        _finalize(o_ref, l_scr, acc_scr)


def _paged_call(q, k, v, lengths, block_tables, scale, interpret,
                block_scales=None):
    """Paged layout: k/v are BLOCK POOLS ``[N, H, bs, D]`` and
    ``block_tables [B, M]`` maps row b's KV block ki to pool block
    ``block_tables[b, ki]``. Table and lengths ride as SCALAR-PREFETCH
    operands (pltpu.PrefetchScalarGridSpec) so the grid's KV dimension
    gathers blocks through the table in its index map — the kernel body
    is unchanged, per-row length skipping included. With
    ``block_scales`` (int8 pools) the row's per-block fp32 scales are
    pre-gathered through the same table (:func:`gather_row_scales`) and
    the kernel dequantizes each tile in the block loop."""
    b, h, _, d = q.shape
    bs = k.shape[2]
    m = block_tables.shape[1]
    quant = block_scales is not None
    kernel = functools.partial(
        _paged_quant_decode_kernel if quant else _paged_decode_kernel,
        scale=scale, block_k=bs)
    tab = jnp.asarray(block_tables, jnp.int32)
    lens = jnp.clip(jnp.asarray(lengths, jnp.int32), 0, m * bs)
    qo_spec = pl.BlockSpec((1, 1, 1, d),
                           lambda b_, h_, ki, tab, lens: (b_, h_, 0, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, bs, d), lambda b_, h_, ki, tab, lens: (tab[b_, ki], h_, 0, 0))
    in_specs = [qo_spec, kv_spec, kv_spec]
    operands = [q, k, v]
    if quant:
        in_specs += [pl.BlockSpec((1, 1, 1, m),
                                  lambda b_, h_, ki, tab, lens:
                                  (b_, h_, 0, 0))] * 2
        operands += [gather_row_scales(sc, tab) for sc in block_scales]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h, m),
        in_specs=in_specs,
        out_specs=qo_spec,
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
        name=("nezha_decode_attention_paged_int8" if quant
              else "nezha_decode_attention_paged"),
    )(tab, lens, *operands)


def flash_decode_attention(q, k, v, lengths,
                           scale: Optional[float] = None,
                           block_k: Optional[int] = None,
                           interpret: Optional[bool] = None,
                           block_tables=None, block_scales=None):
    """q ``[B, H, 1, D]``, k/v ``[B, H, L, D]``, lengths ``[B]`` int32
    -> ``[B, H, 1, D]``.

    ``lengths[b]`` is the number of attendable cache positions for row
    ``b`` (the decode convention: ``pos + 1``, the query's own position
    included). ``lengths[b] == 0`` marks an inactive row: every KV block
    is skipped and the output row is exactly zero (callers discard it —
    the serve engine freezes inactive rows host-side). Lengths are
    clamped to ``[0, L]``.

    With ``block_tables`` (``[B, M]`` int32 — the paged serving
    layout), k/v are instead BLOCK POOLS shaped
    ``[num_blocks, H, block_size, D]``: row ``b``'s positions
    ``[ki*block_size, (ki+1)*block_size)`` live in pool block
    ``block_tables[b, ki]``, and the kernel gathers KV blocks through
    the table via a scalar-prefetch index map. The per-row length skip
    is preserved verbatim. ``block_k`` is ignored (the pool's block_size
    IS the KV block).

    With ``block_scales`` (paged only — a ``(k_scales, v_scales)`` pair
    of ``[num_blocks, H]`` fp32 arrays) the pools are INT8 and each
    gathered tile is dequantized INSIDE the block loop
    (``tile.astype(f32) * scale -> q.dtype`` — the exact expression of
    ``ops.quant.dequantize_kv_block``, so the composed XLA fallback
    dequantizes identically): dots run in the query's dtype over
    dequantized tiles, softmax statistics and the accumulator stay
    fp32. (A ``(16, 64)`` int8 block — below the int8 native tile —
    compiles and runs on a v5e: ``chip_smoke.py``, PR 21.)

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
    if block_tables is not None:
        if k.shape != v.shape or k.shape[1] != h or k.shape[3] != d:
            raise ValueError(
                f"paged k/v pools {k.shape}/{v.shape} do not match q "
                f"{q.shape}")
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
                           interpret, block_scales=block_scales)
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
    ``q [B, H, 1, D]``, the K/V block pools ``[N, H, bs, D]``, and the
    per-(block, head) scale rows ``[N, H]`` on the H axis runs the
    Mosaic kernel device-locally on an ``H / tp`` slice — the GSPMD
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
    rep = P()

    if block_scales is not None:
        ks, vs = block_scales

        def body_q(q_, k_, v_, l_, t_, ks_, vs_):
            return flash_decode_attention(
                q_, k_, v_, l_, scale=scale, interpret=interpret,
                block_tables=t_, block_scales=(ks_, vs_))

        f = shard_map(body_q, mesh=mesh,
                      in_specs=(hspec, hspec, hspec, rep, rep, hspec,
                                hspec),
                      out_specs=hspec)
        return f(q, k, v, lengths, block_tables, ks, vs)
    if block_tables is not None:
        def body_t(q_, k_, v_, l_, t_):
            return flash_decode_attention(
                q_, k_, v_, l_, scale=scale, interpret=interpret,
                block_tables=t_)

        f = shard_map(body_t, mesh=mesh,
                      in_specs=(hspec, hspec, hspec, rep, rep),
                      out_specs=hspec)
        return f(q, k, v, lengths, block_tables)

    def body(q_, k_, v_, l_):
        return flash_decode_attention(q_, k_, v_, l_, scale=scale,
                                      interpret=interpret)

    f = shard_map(body, mesh=mesh,
                  in_specs=(hspec, hspec, hspec, rep), out_specs=hspec)
    return f(q, k, v, lengths)
