"""Manifold-constrained hyper-connections (``nn/hyper_connections.py``,
``ops/pallas/mhc.py``) on the CPU rig: Sinkhorn's rounds, the two kernels in
interpret mode against the composed form, and the tie to the one-stream
residual block.

Seeded float32 inputs throughout. The kernels and the composed form are two
orderings of the same float32 sums, so they agree to round-off (observed
1e-6 on values of order 1; the limit is 2e-5); a map computed in bf16 is off
by 1e-2.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nezha_tpu.models.xing4 import Block, xing4
from nezha_tpu.nn.hyper_connections import (MAP_LANES, HyperConnection,
                                            mhc_post_composed,
                                            mhc_pre_composed, sinkhorn,
                                            sinkhorn_residual, split_maps)
from nezha_tpu.nn.module import child_vars, run_child
from nezha_tpu.ops.pallas.mhc import TOKEN_TILE, mhc_post, mhc_pre

KERNEL_TOL = 2e-5


def _residual(m) -> float:
    return float(jnp.maximum(jnp.abs(m.sum(-1) - 1).max(),
                             jnp.abs(m.sum(-2) - 1).max()))


def _pre_activations(case: str):
    """[4096, 4, 4] float32 of ``H~res``. ``spread``: normal 0.6 (what a
    trained layer's small ``a_res`` gives); ``clamps``: every entry at a
    clamp, +30 on a permutation and -30 off it, plus the same noise."""
    h = 0.6 * jax.random.normal(jax.random.PRNGKey(7), (4096, 4, 4))
    if case == "spread":
        return h
    perm = jax.random.permutation(jax.random.PRNGKey(8), jnp.eye(4), axis=1)
    return jnp.clip(h + jnp.where(perm > 0, 40.0, -40.0), -30.0, 30.0)


@pytest.mark.parametrize("case", ["spread", "clamps"])
def test_sinkhorn_twenty_rounds_are_doubly_stochastic(case):
    h = _pre_activations(case)
    assert case == "spread" or (float(h.max()), float(h.min())) == (30., -30.)
    m = sinkhorn(jnp.exp(h), 20, 1e-6)
    assert m.shape == h.shape and bool(jnp.isfinite(m).all())
    assert _residual(m) < 1e-4
    assert float(m.min()) >= 0.0


def test_sinkhorn_two_rounds_are_not():
    m = sinkhorn(jnp.exp(_pre_activations("spread")), 2, 1e-6)
    assert _residual(m) > 1e-2


def test_sinkhorn_rows_first_then_columns():
    """After the last round the COLUMNS sum to one (to ``eps``) and the rows
    are what is left over: the order is part of the equations."""
    m = sinkhorn(jnp.exp(2.4 * jax.random.normal(jax.random.PRNGKey(3),
                                                 (256, 4, 4))), 3, 1e-6)
    assert float(jnp.abs(m.sum(-2) - 1).max()) < 1e-5
    assert float(jnp.abs(m.sum(-1) - 1).max()) > 1e-3


def _layer(c: int, n: int = 4, **kw):
    hc = HyperConnection(c, n, **kw)
    return hc, hc.init(jax.random.PRNGKey(0))["params"]


# tokens: under a tile of 8, a decode step's rows, a whole tile and one
# past it (the call pads to whole tiles)
@pytest.mark.parametrize("tokens", [5, 32, TOKEN_TILE, TOKEN_TILE + 1])
@pytest.mark.parametrize("width", [128, 64])
def test_kernels_match_the_composed_form(tokens, width):
    hc, p = _layer(width)
    x = jax.random.normal(jax.random.PRNGKey(tokens), (tokens, 4 * width))
    y = jax.random.normal(jax.random.PRNGKey(1), (tokens, width))
    u0, m0 = mhc_pre_composed(x, p["phi"], p["alpha"], p["b"], **hc.static_args())
    u1, m1 = mhc_pre(x, p["phi"], p["alpha"], p["b"], interpret=True,
                     **hc.static_args())
    assert u1.shape == (tokens, width) and m1.shape == (tokens, MAP_LANES)
    assert float(jnp.abs(u0 - u1).max()) < KERNEL_TOL
    assert float(jnp.abs(m0 - m1).max()) < KERNEL_TOL
    assert not bool(m1[:, 24:].any())
    out0 = mhc_post_composed(x, y, m0, n=4)
    out1 = mhc_post(x, y, m0, n=4, interpret=True)
    assert float(jnp.abs(out0 - out1).max()) < KERNEL_TOL
    # a map that depends on its token: the rows differ
    assert float(jnp.std(m0[:, 8], axis=0)) > 1e-2


def test_kernel_clamps_before_the_exponential():
    """Two entries of one row past the clamp weigh the same after it (to
    the other columns' drawn biases, 0.02); a kernel that dropped the clamp
    would weigh them e^5 to 1 going in (12 to 1 after the rounds)."""
    hc, p = _layer(128)
    b = p["b"].at[8].set(40.0).at[9].set(35.0)      # H~res[0, 0], [0, 1]
    x = jax.random.normal(jax.random.PRNGKey(2), (16, 512))
    for pre in (lambda *a, **k: mhc_pre(*a, interpret=True, **k),
                mhc_pre_composed):
        h_res = split_maps(pre(x, 0.0 * p["phi"], p["alpha"], b,
                               **hc.static_args())[1], 4)[2]
        assert float(jnp.abs(h_res[:, 0, 0] - h_res[:, 0, 1]).max()) < 0.02
    loose = {**hc.static_args(), "clamp": (-1e9, 1e9)}
    h_res = split_maps(mhc_pre_composed(x, 0.0 * p["phi"], p["alpha"], b,
                                        **loose)[1], 4)[2]
    assert float((h_res[:, 0, 0] / h_res[:, 0, 1]).min()) > 5.0


def bf16_maps(x, phi, alpha, b, **kw):
    """``mhc_pre_composed`` with the maps' operands and the maps themselves
    rounded to bf16: what a program that kept them there would give."""
    r = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)   # noqa: E731
    u, maps = mhc_pre_composed(r(x), r(phi), alpha, b, **kw)
    return u, r(maps)


def test_maps_in_bf16_are_far_off():
    hc, p = _layer(128)
    x = jax.random.normal(jax.random.PRNGKey(4), (64, 512))
    _, m32 = mhc_pre_composed(x, p["phi"], p["alpha"], p["b"],
                              **hc.static_args())
    _, m16 = bf16_maps(x, p["phi"], p["alpha"], p["b"], **hc.static_args())
    assert float(jnp.abs(m32 - m16).max()) > 100 * KERNEL_TOL


def test_residual_is_the_worst_row_or_column():
    hc, p = _layer(64, sinkhorn_iters=3)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 9, 256))
    _, maps = hc.pre({"params": p}, x)
    assert maps.shape == (2, 9, MAP_LANES)
    h_res = np.asarray(split_maps(maps, 4)[2])
    want = max(np.abs(h_res.sum(-1) - 1).max(), np.abs(h_res.sum(-2) - 1).max())
    assert float(sinkhorn_residual(maps, 4)) == pytest.approx(want, rel=1e-6)
    assert want > 1e-4          # three rounds leave one to see


def test_four_equal_streams_under_identity_maps_are_the_one_stream_block():
    """``H_res = I``, ``H_pre = 1/4`` each, ``H_post = 1`` each, the four
    streams equal: every stream of the block's result is ``h + MLP(norm(h))``
    with ``h = x + Attn(norm(x))``, the block every other model here has."""
    model = xing4("tiny")
    cfg = model.cfg
    for layer in (0, cfg.first_k_dense_replace):     # a dense and a sparse
        block = Block(cfg, layer, model.policy)
        v = block.init(jax.random.PRNGKey(layer))
        forced = {
            "phi": jnp.zeros((24, 4 * cfg.hidden_size)),
            "alpha": jnp.ones((3,)),
            "b": jnp.concatenate([jnp.full((4,), -np.log(3.0)),
                                  jnp.zeros((4,)),
                                  jnp.where(jnp.eye(4) > 0, 30.0,
                                            -30.0).reshape(-1)])}
        v["params"]["hc_attn"] = v["params"]["hc_mlp"] = forced
        x = jax.random.normal(jax.random.PRNGKey(9), (2, 7, cfg.hidden_size))
        got, _ = block.apply(v, jnp.concatenate([x] * 4, axis=-1))
        st: dict = {}
        h = x + run_child(block.attn, "attn", v, st, run_child(
            block.attn_norm, "attn_norm", v, st, x))
        y = run_child(block.mlp_norm, "mlp_norm", v, st, h)
        if block.sparse:
            f = run_child(block.shared, "shared", v, st, y) + run_child(
                block.moe, "moe", v, st, y.reshape(14, -1)).reshape(x.shape)
        else:
            f = run_child(block.mlp, "mlp", v, st, y)
        want = h + f
        for i in range(4):
            stream = got[..., i * cfg.hidden_size:(i + 1) * cfg.hidden_size]
            assert float(jnp.abs(stream - want).max()) < 1e-5
        assert child_vars(v, "hc_attn")["params"] is forced
