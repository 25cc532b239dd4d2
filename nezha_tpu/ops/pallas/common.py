"""Shared online-softmax core for the Pallas attention kernels.

Every attention kernel in this package — training flash
(``flash_attention.py``), single-token decode (``decode_attention.py``)
and paged prefill (``prefill_attention.py``) — folds KV blocks into the
same three-piece VMEM scratch: a running row max ``m``, a running
denominator ``l`` and an fp32 output accumulator ``acc``. The update
math was duplicated verbatim between the decode ``_block_step`` and the
flash ``_fwd_kernel`` body; this module is the single source both (and
the prefill kernel) now call. Grouping it here is a pure factoring:
the op sequence is bit-identical to what each kernel inlined before,
so every existing kernel test pins the refactor.

Also hosts the package-wide scalar helpers: the finite ``NEG_BIG``
"-inf" (fully-masked rows must stay NaN-free), the ``LANES`` lane
width of the per-row scratch, the int8 block-scale operand layout and
the block-divisor picker.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

NEG_BIG = -1e30
LANES = 128  # per-row softmax statistics ride lane-broadcast: [rows, 128]


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """THE ``interpret=None`` rule of every kernel in this package:
    compiled on a TPU backend, the Pallas interpreter elsewhere (so CPU
    tests run the same kernel code). On a TPU backend the interpreter is
    refused outright — a kernel the compiler rejects must fail there,
    not quietly run interpreted."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret and on_tpu:
        raise ValueError("interpret=True on a TPU backend: kernels run "
                         "compiled there")
    return not on_tpu if interpret is None else interpret


def pick_block(size: int, target: int) -> int:
    """Largest divisor of ``size`` that is <= target (block shapes must
    tile the sequence exactly)."""
    b = min(size, target)
    while size % b:
        b -= 1
    return b


def gather_row_scales(scales, block_tables, group: int):
    """Int8-pool scales ``[N, H]`` gathered through ``block_tables
    [B, M]`` into the kernel operand layout ``[B, H / group, group, M]``:
    one lane vector of per-block scales per (row, head), the heads in
    the groups a grid step holds (all ``H`` for the decode kernel, the
    heads of one lane block of the pool for the prefill kernel). The
    TPU lowering refuses a ``(1, 1)`` block over ``[N, H]`` (the last
    two block dims must be (8, 128)-divisible or span the array's), so
    a step takes the block ``(1, 1, group, M)``, which spans the
    trailing dims, and picks an entry's scales in-kernel
    (:func:`block_scale`)."""
    rows = jnp.asarray(scales, jnp.float32)[block_tables]    # [B, M, H]
    b, m, h = rows.shape
    return rows.transpose(0, 2, 1).reshape(b, h // group, group, m)


def block_scale(rows_ref, t):
    """Entry ``t`` of a :func:`gather_row_scales` block as a
    ``[group, 1]`` tile (a masked lane reduction — Mosaic has no
    dynamic lane index into VMEM). Exact: one selected element plus
    zeros."""
    rows = rows_ref[0, 0]                                    # [group, M]
    lane = lax.broadcasted_iota(jnp.int32, rows.shape, 1)
    return jnp.sum(jnp.where(lane == t, rows, 0.0), axis=-1, keepdims=True)


def scratch_init(m_scr, l_scr, acc_scr):
    """Reset the online-softmax scratch at the first KV block — shared
    by every kernel variant."""
    m_scr[:] = jnp.full_like(m_scr, NEG_BIG)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)


def softmax_block_update(s, v, m_scr, l_scr, acc_scr):
    """Fold one masked score block ``s [rows, bk]`` and its value tile
    ``v [bk, d]`` into the running ``(max, sum, acc)`` scratch — THE
    online-softmax step every kernel shares. ``s`` arrives fully masked
    (causal / length / start-offset masking is the caller's business);
    softmax statistics and the accumulator stay fp32, P·V dots in the
    value tile's native dtype."""
    m_prev = m_scr[:, :1]                                # [rows, 1]
    l_prev = l_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                               # [rows, bk]
    corr = jnp.exp(m_prev - m_new)
    l_new = corr * l_prev + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[:] = acc_scr[:] * corr + lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)


def softmax_finalize(o_ref, m_scr, l_scr, acc_scr, lse_ref=None):
    """Write the normalized accumulator at the last KV block. The denom
    guard keeps a row whose scratch never saw a block (zero-length /
    inactive) at an exact-zero output instead of 0/0. With ``lse_ref``
    (the training forward) the per-row logsumexp residual is emitted
    lane-broadcast alongside."""
    denom = jnp.maximum(l_scr[:, :1], 1e-30)
    o_ref[0, 0] = (acc_scr[:] / denom).astype(o_ref.dtype)
    if lse_ref is not None:
        lse = m_scr[:, :1] + jnp.log(denom)              # [rows, 1]
        lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref[0, 0].shape)


def block_step(q, k, v, length, ki, m_scr, l_scr, acc_scr, *,
               scale: float, block_k: int):
    """One length-masked KV block folded into the scratch — the shared
    core of the decode-kernel variants (dense, paged, paged-int8) and
    the prefill kernel's prior-block path: the variants differ only in
    WHERE ``k``/``v`` came from (BlockSpec gather, in-kernel dequant)
    and in any EXTRA masking applied on top, never in the fold."""
    s = lax.dot_general(q.astype(k.dtype), k,
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
    kpos = ki * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(kpos < length, s, NEG_BIG)             # partial block
    softmax_block_update(s, v, m_scr, l_scr, acc_scr)
