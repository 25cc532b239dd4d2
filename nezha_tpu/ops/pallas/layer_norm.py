"""Fused LayerNorm as Pallas kernels: one VMEM pass computes statistics and
applies scale/shift (the reference fused this in a custom CUDA kernel —
SURVEY.md §2). Rows are tiled over the grid; statistics in fp32.

Differentiable: a custom VJP pairs the forward kernel with a fused backward
kernel that recomputes the row statistics from x (cheaper than storing
mean/rstd residuals at [rows] when the whole row is re-read anyway) and
emits dx plus per-block partial reductions for dscale/dbias, which XLA sums
outside the kernel (a [n_blocks, D] add — negligible).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from nezha_tpu.ops.pallas.common import pick_block, resolve_interpret


def _ln_kernel(x_ref, scale_ref, bias_ref, o_ref, *, eps: float):
    x = x_ref[:].astype(jnp.float32)                      # [bn, D]
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    y = (x - mean) * lax.rsqrt(var + eps)
    y = y * scale_ref[:].astype(jnp.float32) + bias_ref[:].astype(jnp.float32)
    o_ref[:] = y.astype(o_ref.dtype)


def _ln_bwd_kernel(x_ref, scale_ref, dy_ref, dx_ref, dscale_ref, dbias_ref,
                   *, eps: float):
    x = x_ref[:].astype(jnp.float32)                      # [bn, D]
    dy = dy_ref[:].astype(jnp.float32)
    scale = scale_ref[:].astype(jnp.float32)              # [1, D]
    d = x.shape[-1]
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    r = lax.rsqrt(var + eps)
    xhat = (x - mean) * r
    g = dy * scale                                        # dL/dxhat
    m1 = jnp.sum(g, axis=-1, keepdims=True) / d
    m2 = jnp.sum(g * xhat, axis=-1, keepdims=True) / d
    dx = r * (g - m1 - xhat * m2)
    dx_ref[:] = dx.astype(dx_ref.dtype)
    dscale_ref[0] = jnp.sum(dy * xhat, axis=0, keepdims=True)
    dbias_ref[0] = jnp.sum(dy, axis=0, keepdims=True)


def _ln_fwd_raw(x2, scale, bias, eps: float, interpret: bool):
    rows, d = x2.shape
    bn = pick_block(rows, 256)
    return pl.pallas_call(
        functools.partial(_ln_kernel, eps=eps),
        grid=(rows // bn,),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bn, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x2.dtype),
        interpret=interpret,
        name="nezha_layer_norm_fwd",
    )(x2, scale.reshape(1, d), bias.reshape(1, d))


def _ln_bwd_raw(x2, scale, dy2, eps: float, interpret: bool):
    rows, d = x2.shape
    bn = pick_block(rows, 256)
    n_blocks = rows // bn
    dx, dscale_p, dbias_p = pl.pallas_call(
        functools.partial(_ln_bwd_kernel, eps=eps),
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
        ],
        # Partial sums are [n_blocks, 1, D] so each program's (1, 1, D)
        # block spans the trailing dims (a (1, D) block over
        # [n_blocks, D] is refused by the TPU lowering).
        out_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((1, 1, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, d), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, d), x2.dtype),
            jax.ShapeDtypeStruct((n_blocks, 1, d), jnp.float32),
            jax.ShapeDtypeStruct((n_blocks, 1, d), jnp.float32),
        ],
        interpret=interpret,
        name="nezha_layer_norm_bwd",
    )(x2, scale.reshape(1, d), dy2)
    return dx, dscale_p.sum(axis=(0, 1)), dbias_p.sum(axis=(0, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fused_ln(x2, scale, bias, eps: float, interpret: bool):
    return _ln_fwd_raw(x2, scale, bias, eps, interpret)


def _fused_ln_fwd(x2, scale, bias, eps, interpret):
    # `bias` rides along only to pin its cotangent dtype ([D] — negligible).
    return _ln_fwd_raw(x2, scale, bias, eps, interpret), (x2, scale, bias)


def _fused_ln_bwd(eps, interpret, res, dy2):
    x2, scale, bias = res
    dx, dscale, dbias = _ln_bwd_raw(x2, scale, dy2, eps, interpret)
    return dx, dscale.astype(scale.dtype), dbias.astype(bias.dtype)


_fused_ln.defvjp(_fused_ln_fwd, _fused_ln_bwd)


def fused_layer_norm(x, scale, bias, eps: float = 1e-5,
                     interpret: Optional[bool] = None):
    """x: [..., D]; scale, bias: [D]. Returns layernorm(x) in x.dtype.
    Differentiable (fused backward kernel, see module docstring)."""
    orig_shape = x.shape
    d = orig_shape[-1]
    rows = 1
    for dim in orig_shape[:-1]:
        rows *= dim
    x2 = x.reshape(rows, d)
    out = _fused_ln(x2, scale, bias, eps, resolve_interpret(interpret))
    return out.reshape(orig_shape)
