"""Shared symmetric int8 quantization core: one audited implementation.

Two subsystems quantize with the same EQuARX-style recipe (PAPERS.md,
arXiv:2506.17615 — int8 payloads + per-block fp32 absmax scales, fp32
accumulation around the narrow storage/wire format):

- the **wire collectives** (``parallel/quantized.py``): gradients ride
  ICI as int8 + scales, dequantized and summed in fp32 per hop;
- the **paged KV cache** (``serve/slots.py`` + ``models/gpt2.py``):
  ``ServeConfig.kv_dtype="int8"`` stores K/V blocks as int8 with one
  fp32 scale per (block, head), dequantized inside the flash-decode
  kernel's block loop (``ops/pallas/decode_attention.py``).

Both call the functions here so there is exactly one rounding/clipping/
zero-guard policy to audit — a fix to either consumer's numerics lands
in both. Two entry shapes, one policy:

- :func:`quantize_blocks` / :func:`dequantize` — last-axis blocking
  (``[..., k*block] -> int8 [..., k, block] + scales [..., k, 1]``),
  the wire layout. Extracted VERBATIM from ``parallel/quantized.py``;
  tests pin the collectives bit-identical across the extraction.
- :func:`quantize_kv_block` / :func:`dequantize_kv_block` — trailing
  ``[..., bs, D]`` tiles quantized with ONE scale per leading index
  (per block, per head for ``[N, H, bs, D]`` pools), the KV-cache
  layout. Unlike the wire path (whose inputs are finite gradients by
  construction), KV writes can carry a NaN/inf burst (the PR-4 fault
  surface), so this path SANITIZES first — deterministic saturation,
  never a NaN scale poisoning a whole block.

Policy (shared):

- symmetric: ``q = clip(round(x / scale), -127, 127)``, scale =
  ``amax / 127`` — no zero point, so dequant is one fused multiply;
- zero guard: an all-zero block takes ``scale = 1.0`` (quantizes to
  exact zeros, dequantizes to exact zeros, no div-by-zero);
- sanitize (KV path only): ``NaN -> 0``, ``±inf -> ±float32 max`` —
  deterministic, and the serve layer's ``finite_rows`` tripwire still
  catches the burst at the logits (a saturated block is garbage data,
  not garbage CONTROL FLOW);
- scales are fp32; accumulation around the int8 format is the
  caller's job and is fp32 everywhere in this repo.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QMAX = 127.0

# The ±inf saturation value. Deliberately BELOW float32 max: the scale
# ``amax / 127`` rounds up in fp32, so dequantizing the extreme element
# (``127 * scale``) of a block whose amax is exactly f32max would
# overflow to inf — saturating at 3e38 keeps the whole
# quantize->dequantize round trip finite (3e38 * (1 + 2^-23) is still
# representable).
SATURATE_MAX = 3.0e38


def _scale_of(amax: jax.Array) -> jax.Array:
    """absmax -> fp32 scale with the shared zero guard."""
    return jnp.where(amax > 0, amax / QMAX, 1.0).astype(jnp.float32)


# ------------------------------------------------------- wire layout
def quantize_blocks(x: jax.Array, block: int):
    """Symmetric per-block int8 quantization of ``x`` [..., k*block] ->
    (int8 [..., k, block], fp32 scales [..., k, 1]). The wire-collective
    layout — kept bit-identical to the pre-extraction
    ``parallel/quantized.py`` implementation (regression-pinned)."""
    xb = x.reshape(*x.shape[:-1], x.shape[-1] // block, block)
    amax = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
    scale = _scale_of(amax)
    q = jnp.clip(jnp.round(xb / scale), -QMAX, QMAX).astype(jnp.int8)
    return q, scale


def dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    """int8 + broadcastable fp32 scales -> fp32."""
    return q.astype(jnp.float32) * scale


# --------------------------------------------------------- KV layout
def sanitize(x: jax.Array) -> jax.Array:
    """Deterministic non-finite saturation for quantizer inputs:
    ``NaN -> 0``, ``±inf -> ±SATURATE_MAX``. Without it a single
    non-finite element makes the block's absmax (hence scale, hence
    every dequantized element) NaN; with it the round trip stays
    finite end to end."""
    return jnp.nan_to_num(x.astype(jnp.float32), nan=0.0,
                          posinf=SATURATE_MAX, neginf=-SATURATE_MAX)


def quantize_kv_block(x: jax.Array):
    """Quantize trailing ``[..., bs, D]`` tiles with one absmax scale
    per leading index: ``x [..., bs, D]`` (any float dtype) ->
    ``(int8 [..., bs, D], fp32 scales [...])``. For a ``[N, H, bs, D]``
    KV block pool that is one scale per (block, head) — the
    ``[kv_num_blocks, H]`` scale buffers ``PagedSlotPool`` keeps
    alongside each pool. Inputs are sanitized (see :func:`sanitize`)."""
    xf = sanitize(x)
    amax = jnp.max(jnp.abs(xf), axis=(-2, -1))
    scale = _scale_of(amax)
    q = jnp.clip(jnp.round(xf / scale[..., None, None]),
                 -QMAX, QMAX).astype(jnp.int8)
    return q, scale


def dequantize_kv_block(q: jax.Array, scale: jax.Array,
                        dtype=jnp.float32) -> jax.Array:
    """``int8 [..., bs, D]`` + ``fp32 scales [...]`` -> ``dtype``.
    The exact dequant both attention paths (Pallas kernel block loop
    and the gathered XLA fallback) apply, so ``decode_impl="xla"``
    stays a bit-faithful escape hatch for the int8 cache."""
    return (q.astype(jnp.float32)
            * scale[..., None, None]).astype(dtype)


# ------------------------------------------- the paged pool's row layout
# A GPT-2 K/V pool is LANE-DENSE: ``[N, bs, H*D]``, one row a position,
# head ``h`` in lanes ``h*D .. (h+1)*D`` (a minor dimension of whole
# 128-lane tiles is the device's own row-major layout, so no program
# re-lays the pool out: PERF.md section 6, PR 27). The per-head tile
# form ``[..., H, bs, D]`` above stays the WIRE format (serve/migrate.py,
# the host tier); these four are the same policy over rows.
def split_heads(x: jax.Array, heads: int) -> jax.Array:
    """Pool rows ``[..., bs, H*D]`` -> per-head tiles ``[..., H, bs, D]``
    (the wire form; a transpose, so not for a serving program)."""
    x = x.reshape(x.shape[:-1] + (heads, x.shape[-1] // heads))
    return jnp.swapaxes(x, -3, -2)


def merge_heads(x: jax.Array) -> jax.Array:
    """Per-head tiles ``[..., H, bs, D]`` -> pool rows ``[..., bs, H*D]``."""
    x = jnp.swapaxes(x, -3, -2)
    return x.reshape(x.shape[:-2] + (x.shape[-2] * x.shape[-1],))


def quantize_kv_rows(x: jax.Array, heads: int):
    """:func:`quantize_kv_block` over pool rows: ``x [..., bs, H*D]`` ->
    ``(int8 [..., bs, H*D], fp32 scales [..., H])``, one absmax scale per
    (block, head); element for element what the tile form gives."""
    xf = sanitize(x).reshape(x.shape[:-1] + (heads, x.shape[-1] // heads))
    scale = _scale_of(jnp.max(jnp.abs(xf), axis=(-3, -1)))
    q = jnp.clip(jnp.round(xf / scale[..., None, :, None]),
                 -QMAX, QMAX).astype(jnp.int8)
    return q.reshape(x.shape), scale


def dequantize_kv_rows(q: jax.Array, scale: jax.Array,
                       dtype=jnp.float32) -> jax.Array:
    """``int8 [..., bs, H*D]`` + ``fp32 scales [..., H]`` -> ``dtype``:
    :func:`dequantize_kv_block`'s expression with each head's scale
    over its own lanes."""
    heads = scale.shape[-1]
    qf = q.astype(jnp.float32).reshape(
        q.shape[:-1] + (heads, q.shape[-1] // heads))
    return (qf * scale[..., None, :, None]).astype(dtype).reshape(q.shape)


def kv_roundtrip_error(x: jax.Array) -> jax.Array:
    """Max-abs dequant error of one KV-block quantization of ``x``
    (``[..., bs, D]``) -> scalar fp32. The ``serve.kv.quant_error``
    histogram's sample; bounded by ``amax / 254`` per block (half a
    quantization step) for finite inputs."""
    q, s = quantize_kv_block(x)
    return jnp.max(jnp.abs(sanitize(x)
                           - dequantize_kv_block(q, s, jnp.float32)))
