"""Manifold-constrained hyper-connections (mHC, DeepSeek-AI,
arXiv:2512.24880): a residual path of ``n`` streams a token, mixed around
every sublayer by maps that the token's own streams produce.

With ``X`` in ``R^{n x C}`` a token's streams (float32) and ``F`` the
sublayer::

    x~     = RMSNorm_{nC}(vec(X))                  (no learned scale)
    H_pre  = sigmoid(a_pre  * (x~ phi_pre)  + b_pre)            in R^n
    H_post = 2 sigmoid(a_post * (x~ phi_post) + b_post)         in R^n
    H_res  = Sinkhorn(exp(clamp(a_res * mat(x~ phi_res) + b_res)))
                                                                in R^{n x n}
    u  = H_pre X                   (the sublayer's input, R^C)
    X' = H_res X + H_post^T F(u)

``Sinkhorn`` is ``iters`` rounds of ``rows / (row sums + eps)`` then
``columns / (column sums + eps)``: ``H_res`` ends (nearly) doubly
stochastic, so the stream mix neither grows nor shrinks the signal however
deep the stack. One stream with ``H_pre = H_post = H_res = 1`` is ``x +
F(x)``.

The streams travel flat, ``[..., n*C]`` (stream ``i`` in lanes ``i*C ..
(i+1)*C``), and a sublayer's three maps travel as one row of
``MAP_LANES`` float32 a token: ``[H_pre (n) | H_post (n) | H_res (n*n,
row-major) | 0...]``. The parameters are stored as the kernels read them:
``phi`` ``[n*n + 2n, n*C]`` (row ``k`` is column ``k`` of ``[phi_pre |
phi_post | phi_res]``), ``b`` ``[n*n + 2n]`` in the same order, ``alpha``
``[3]`` (pre, post, res); all float32 whatever the policy, as the maps and
the streams are. :class:`HyperConnection` has two forms of :meth:`pre` and
:meth:`post`: the Pallas kernels of ``ops/pallas/mhc.py`` (``impl``
``"kernel"``, or ``"auto"`` on a TPU) and the composed ``jax.numpy`` form
here, which is the path off the TPU and the kernels' test oracle.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from nezha_tpu.nn import initializers as init_lib
from nezha_tpu.nn.module import Module, Variables, make_variables

MAP_LANES = 128     # a token's maps as stored: one whole lane tile
PRE_ACTIVATION_SPREAD = 2.4     # of x~ phi at init, at any width
_HIGHEST = lax.Precision.HIGHEST


def sinkhorn(m, iters: int, eps: float):
    """``iters`` rounds over ``m`` ``[..., n, n]`` (positive): every row
    over its sum plus ``eps``, then every column over its sum plus
    ``eps``."""
    def one(_, m):
        m = m / (m.sum(axis=-1, keepdims=True) + eps)
        return m / (m.sum(axis=-2, keepdims=True) + eps)
    return lax.fori_loop(0, iters, one, m)


def map_scales(alpha, n: int):
    """``alpha`` [3] (pre, post, res) -> the scalar of each of the ``n*n +
    2n`` map entries, in the stored order."""
    return jnp.repeat(alpha.astype(jnp.float32),
                      jnp.asarray([n, n, n * n]), total_repeat_length=n * (n + 2))


def split_maps(maps, n: int):
    """A stored map row ``[..., MAP_LANES]`` -> (``H_pre`` [..., n],
    ``H_post`` [..., n], ``H_res`` [..., n, n])."""
    return (maps[..., :n], maps[..., n:2 * n],
            maps[..., 2 * n:2 * n + n * n].reshape(maps.shape[:-1] + (n, n)))


def sinkhorn_residual(maps, n: int):
    """The largest ``|row sum - 1|`` or ``|column sum - 1|`` of any
    ``H_res`` among ``maps`` ``[..., MAP_LANES]``: one float32."""
    h_res = split_maps(maps, n)[2]
    return jnp.maximum(jnp.abs(h_res.sum(-1) - 1.0).max(),
                       jnp.abs(h_res.sum(-2) - 1.0).max())


class HyperConnection(Module):
    """One sublayer's maps: ``pre`` reads the streams and gives the
    sublayer's input and the maps, ``post`` writes the streams back (the
    block that owns the sublayer calls the two round it)."""

    def __init__(self, width: int, streams: int = 4, sinkhorn_iters: int = 20,
                 eps: float = 1e-6, clamp: Tuple[float, float] = (-30.0, 30.0),
                 norm_eps: float = 1e-6, impl: str = "auto"):
        if streams * streams + 2 * streams > MAP_LANES:
            raise ValueError(f"{streams} streams: the maps of a token do not "
                             f"fit a row of {MAP_LANES}")
        self.width, self.streams = width, streams
        self.sinkhorn_iters, self.eps = sinkhorn_iters, eps
        self.clamp, self.norm_eps = clamp, norm_eps
        self.impl = impl

    @property
    def num_maps(self) -> int:
        return self.streams * (self.streams + 2)

    def init(self, rng: jax.Array) -> Variables:
        r_phi, r_b = jax.random.split(rng)
        nc = self.streams * self.width
        # a_* = 1 and phi ~ PRE_ACTIVATION_SPREAD / sqrt(nC) (0.02 at 4 x
        # 3,584): x~ phi spreads by 2.4 at any width, so every map depends
        # on its token (an a of 0.01, a training-time initialisation, would
        # leave every map at its bias)
        return make_variables({
            "phi": init_lib.normal(PRE_ACTIVATION_SPREAD / nc ** 0.5)(
                r_phi, (self.num_maps, nc), jnp.float32),
            "alpha": jnp.ones((3,), jnp.float32),
            "b": init_lib.normal(0.02)(r_b, (self.num_maps,), jnp.float32)})

    def _use_kernel(self) -> bool:
        return self.impl == "kernel" or (
            self.impl == "auto" and jax.default_backend() == "tpu")

    def static_args(self) -> dict:
        return dict(n=self.streams, iters=self.sinkhorn_iters, eps=self.eps,
                    clamp=tuple(self.clamp), norm_eps=self.norm_eps)

    def pre(self, variables: Variables, x):
        """``x`` [..., n*C] float32 -> (``u`` [..., C] float32, ``maps``
        [..., MAP_LANES] float32)."""
        p = variables["params"]
        lead = x.shape[:-1]
        flat = x.reshape(-1, x.shape[-1])
        if self._use_kernel():
            from nezha_tpu.ops.pallas.mhc import mhc_pre
            u, maps = mhc_pre(flat, p["phi"], p["alpha"], p["b"],
                              **self.static_args())
        else:
            u, maps = mhc_pre_composed(flat, p["phi"], p["alpha"], p["b"],
                                       **self.static_args())
        return u.reshape(lead + (self.width,)), maps.reshape(
            lead + (MAP_LANES,))

    def post(self, x, y, maps):
        """``x`` [..., n*C], the sublayer's output ``y`` [..., C], ``maps``
        from :meth:`pre` -> the new streams [..., n*C] float32."""
        shape = x.shape
        flat = (x.reshape(-1, shape[-1]), y.reshape(-1, y.shape[-1]),
                maps.reshape(-1, MAP_LANES))
        if self._use_kernel():
            from nezha_tpu.ops.pallas.mhc import mhc_post
            return mhc_post(*flat, n=self.streams).reshape(shape)
        return mhc_post_composed(*flat, n=self.streams).reshape(shape)


def mhc_pre_composed(x, phi, alpha, b, *, n: int, iters: int, eps: float,
                     clamp: Tuple[float, float], norm_eps: float):
    """:func:`ops.pallas.mhc.mhc_pre`, composed. ``x`` [T, n*C]."""
    t, nc = x.shape
    c = nc // n
    x = x.astype(jnp.float32)
    xn = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + norm_eps)
    proj = jnp.einsum("tk,mk->tm", xn, phi.astype(jnp.float32),
                      precision=_HIGHEST)
    h = proj * map_scales(alpha, n) + b.astype(jnp.float32)
    h_pre = jax.nn.sigmoid(h[:, :n])
    h_post = 2.0 * jax.nn.sigmoid(h[:, n:2 * n])
    h_res = sinkhorn(
        jnp.exp(jnp.clip(h[:, 2 * n:], clamp[0], clamp[1])).reshape(t, n, n),
        iters, eps)
    maps = jnp.concatenate(
        [h_pre, h_post, h_res.reshape(t, n * n),
         jnp.zeros((t, MAP_LANES - n * (n + 2)), jnp.float32)], axis=-1)
    u = jnp.einsum("tn,tnc->tc", maps[:, :n], x.reshape(t, n, c),
                   precision=_HIGHEST)
    return u, maps


def mhc_post_composed(x, y, maps, *, n: int):
    """:func:`ops.pallas.mhc.mhc_post`, composed. ``x`` [T, n*C], ``y``
    [T, C], ``maps`` [T, MAP_LANES]."""
    t, nc = x.shape
    _, h_post, h_res = split_maps(maps, n)
    mixed = jnp.einsum("tij,tjc->tic", h_res,
                       x.astype(jnp.float32).reshape(t, n, nc // n),
                       precision=_HIGHEST)
    return (mixed + h_post[:, :, None]
            * y.astype(jnp.float32)[:, None, :]).reshape(t, nc)
