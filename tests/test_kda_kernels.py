"""The linear-attention layer's three forms (``ops/pallas/kda.py``) and the
latent form of the paged decode kernel, on the CPU rig: the chunked form
against the token recurrence, and both Pallas kernels in interpret mode
against their ``jax.numpy`` twins. Seeded float32 throughout; tolerances are
float32 round-off with a margin (observed 2e-6 on values of order 1)."""

import numpy as np
import pytest

import jax.numpy as jnp

from nezha_tpu.ops.pallas import (kda_chunked, kda_conv_step,
                                  kda_conv_step_reference, kda_decode,
                                  kda_decode_reference, kda_recurrent,
                                  latent_attention_composed,
                                  latent_decode_attention)

TOL = 5e-5


def _tokens(rng, t, h, dk, dv, decay):
    """q, k (unit), v, g, beta of ``t`` tokens; ``decay`` = (lo, hi) of
    the uniform draw of ``-g``."""
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    k = f(t, h, dk)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -jnp.asarray(rng.uniform(*decay, size=(t, h, dk)), jnp.float32)
    beta = jnp.asarray(rng.uniform(size=(t, h)), jnp.float32)
    return f(t, h, dk) * dk ** -0.5, k, f(t, h, dv), g, beta


# (b) decay near 1 (g ~ 0), mixed, and near 0 (exp(-40): the product
# exp(G_t) * exp(-G_j) of the naive form overflows float32 at 64 tokens)
@pytest.mark.parametrize("decay", [(0.0, 1e-3), (0.0, 3.0), (20.0, 40.0)],
                         ids=["decay~1", "mixed", "decay~0"])
@pytest.mark.parametrize("chunk", [1, 16, 64])
@pytest.mark.parametrize("from_zero", [True, False], ids=["S0=0", "S0!=0"])
def test_chunked_form_is_the_token_recurrence(chunk, decay, from_zero):
    rng = np.random.default_rng(7)
    h, dk, dv, t = 3, 16, 24, 128
    q, k, v, g, beta = _tokens(rng, t, h, dk, dv, decay)
    s0 = jnp.zeros((h, dk, dv)) if from_zero else jnp.asarray(
        rng.normal(size=(h, dk, dv)), jnp.float32)
    o_r, s_r = kda_recurrent(q, k, v, g, beta, s0)
    o_c, s_c = kda_chunked(q, k, v, g, beta, s0, chunk=chunk)
    assert np.isfinite(np.asarray(o_c)).all()
    assert np.isfinite(np.asarray(s_c)).all()
    assert float(jnp.abs(o_r).max()) > 1e-2
    assert float(jnp.abs(o_c - o_r).max()) < TOL
    assert float(jnp.abs(s_c - s_r).max()) < TOL


def test_a_pad_token_leaves_the_state_as_it_was():
    """beta = 0 and g = 0: what the prefill program gives a bucket's pads."""
    rng = np.random.default_rng(8)
    h, dk, dv = 2, 16, 16
    q, k, v, g, beta = _tokens(rng, 32, h, dk, dv, (0.0, 2.0))
    real = jnp.arange(32) < 19
    g = jnp.where(real[:, None, None], g, 0.0)
    beta = jnp.where(real[:, None], beta, 0.0)
    s0 = jnp.asarray(rng.normal(size=(h, dk, dv)), jnp.float32)
    _, s_pad = kda_chunked(q, k, v, g, beta, s0, chunk=16)
    _, s_cut = kda_recurrent(q[:19], k[:19], v[:19], g[:19], beta[:19], s0)
    assert float(jnp.abs(s_pad - s_cut).max()) < TOL
    with pytest.raises(ValueError, match="whole chunks"):
        kda_chunked(q[:19], k[:19], v[:19], g[:19], beta[:19], s0, chunk=16)


# (c) the one-pass state update in interpret mode against its twin
@pytest.mark.parametrize("heads,dk,dv", [(4, 16, 16), (2, 32, 64)])
def test_kda_decode_kernel_against_its_twin(heads, dk, dv):
    rng = np.random.default_rng(9)
    n, b = 7, 5
    pool = jnp.asarray(rng.normal(size=(n, heads, dk, dv)), jnp.float32)
    # rows 1 and 3 must not advance: they name the scratch entry 0
    entries = jnp.asarray([4, 0, 6, 0, 1], jnp.int32)
    q, k, v, g, beta = _tokens(rng, b, heads, dk, dv, (0.0, 2.0))
    o_t, pool_t = kda_decode_reference(pool, entries, q, k, v, g, beta)
    o_k, pool_k = kda_decode(pool, entries, q, k, v, g, beta)
    live = np.asarray(entries) > 0
    assert float(jnp.abs(o_k - o_t)[live].max()) < TOL
    named = np.asarray([4, 6, 1])
    assert float(jnp.abs(pool_k - pool_t)[named].max()) < TOL
    # entries no row names come back bit for bit
    for e in (2, 3, 5):
        assert bool(jnp.array_equal(pool_k[e], pool[e]))
    # and the update is the recurrence's one step
    o_r, s_r = kda_recurrent(q[:1], k[:1], v[:1], g[:1], beta[:1], pool[4])
    assert float(jnp.abs(o_k[0] - o_r[0]).max()) < TOL
    assert float(jnp.abs(pool_k[4] - s_r).max()) < TOL
    with pytest.raises(ValueError, match="float32"):
        kda_decode(pool.astype(jnp.bfloat16), entries, q, k, v, g, beta)


def test_kda_decode_from_a_zero_state_is_a_sequences_first_token():
    rng = np.random.default_rng(10)
    h, dk, dv = 2, 16, 16
    q, k, v, g, beta = _tokens(rng, 1, h, dk, dv, (0.0, 2.0))
    pool = jnp.zeros((2, h, dk, dv), jnp.float32)
    o, new = kda_decode(pool, jnp.asarray([1], jnp.int32), q, k, v, g, beta)
    # S_1 = beta k v^T; o_1 = beta (k . q) v
    want = beta[0][:, None, None] * k[0][..., None] * v[0][:, None, :]
    assert float(jnp.abs(new[1] - want).max()) < TOL
    kq = jnp.sum(k[0] * q[0], -1)
    assert float(jnp.abs(o[0] - (beta[0] * kq)[:, None] * v[0]).max()) < TOL


# (c) the convolution's single-token step in interpret mode against its
# twin, and both against the plain sum of shifted rows
@pytest.mark.parametrize("lanes,chans", [(128, 256), (48, 48)])
def test_kda_conv_step_kernel_against_its_twin(lanes, chans):
    rng = np.random.default_rng(12)
    n, b, taps = 6, 4, 3
    pool = jnp.asarray(rng.normal(size=(n, taps * chans // lanes, lanes)),
                       jnp.float32)
    entries = jnp.asarray([5, 0, 2, 0], jnp.int32)   # rows 1, 3 sit out
    x = jnp.asarray(rng.normal(size=(b, chans)), jnp.float32)
    w = jnp.asarray(rng.uniform(-0.5, 0.5, size=(taps + 1, chans)),
                    jnp.float32)
    y_t, pool_t = kda_conv_step_reference(pool, entries, x, w)
    y_k, pool_k = kda_conv_step(pool, entries, x, w)
    assert y_k.shape == (b, chans)
    live = np.asarray(entries) > 0
    assert float(jnp.abs(y_k - y_t)[live].max()) < TOL
    for e in (5, 2):
        assert float(jnp.abs(pool_k[e] - pool_t[e]).max()) == 0.0
    for e in (1, 3, 4):     # entries no row names come back bit for bit
        assert bool(jnp.array_equal(pool_k[e], pool[e]))
    # y_t = sum_i w_i x_{t-3+i}; the new tail is the old one shifted
    old = pool[5].reshape(taps, chans)
    want = (old * w[:taps]).sum(0) + w[taps] * x[0]
    assert float(jnp.abs(y_k[0] - want).max()) < TOL
    new = pool_k[5].reshape(taps, chans)
    assert bool(jnp.array_equal(new[:2], old[1:]))
    assert bool(jnp.array_equal(new[2], x[0]))


# (c) the latent form of the paged kernel in interpret mode against the
# composed view: an inactive row, rows of one token, a row that ends at an
# entry's edge, the table's last entry, and a table longer than one
# iteration of 16 entries. Kimi-Linear's proportions (a 256-lane row whose
# values are its first 128 lanes: 640 / 512 halved and halved again) and
# Mistral-Small-4's (a 384-lane row whose values are its first 256 lanes,
# as served; its block of 64 scaled down to 8 like the others, its table
# to 32 entries = two iterations of 16): lengths 0, 1, a block's edge and
# one whole iteration each +-1, the table's end; in float32 under the
# float32 tolerance, and over the bf16 pool and query the cell holds,
# where the kernel rounds ``p`` to bf16 before its float32-accumulated
# output product and the result to bf16: 7.6e-3 off the float32 view of
# the same values on results up to 3.2 (the composed view in bf16, which
# normalises first and takes a bf16 product, reads 1.2e-2), against the
# 1.45 that lengths off by one read.
_MISTRAL = dict(table=32, w=384, r=256,
                lens=[0, 1, 7, 8, 9, 127, 128, 129, 256])
LATENT_CASES = {
    "table-6": dict(table=6, w=256, r=128, lens=[0, 1, 8, 9, 45, 48]),
    "table-40": dict(table=40, w=256, r=128, lens=[0, 1, 8, 9, 317, 320]),
    "mistral-f32": _MISTRAL,
    "mistral-bf16": dict(_MISTRAL, dtype=jnp.bfloat16, tol=2e-2),
    # the call's name in a trace is a label: the same bits under any
    "mistral-bf16-named": dict(_MISTRAL, dtype=jnp.bfloat16, tol=2e-2,
                               name="nezha_mla_decode_paged"),
}


@pytest.mark.parametrize("case", sorted(LATENT_CASES))
def test_latent_decode_kernel_against_the_composed_view(case):
    kw = LATENT_CASES[case]
    table, w, r, bs = kw["table"], kw["w"], kw["r"], 8
    dtype, tol = kw.get("dtype", jnp.float32), kw.get("tol", TOL)
    lens = np.asarray(kw["lens"])
    rng = np.random.default_rng(11)
    b, h = len(lens), 4
    n = 1 + b * table
    pool = jnp.asarray(rng.normal(size=(n, bs, w)), dtype)
    # unowned blocks hold what a freed slot leaves: anything at all
    pool = pool.at[0].set(jnp.nan)
    tab = jnp.asarray(1 + rng.permutation(b * table).reshape(b, table),
                      jnp.int32)
    # entries past a row's length are unbound: scratch
    bound = np.arange(table)[None, :] * bs < lens[:, None]
    tab = jnp.where(bound, tab, 0)
    q = jnp.asarray(rng.normal(size=(b, h, w)), dtype)
    # the composed view in float32 over the same (rounded) values
    want = latent_attention_composed(
        q.astype(jnp.float32), jnp.nan_to_num(pool).astype(jnp.float32),
        lens, tab, r, 0.125)
    got = latent_decode_attention(q, pool, lens, tab, r, 0.125)
    assert got.shape == (b, h, 1, r) and got.dtype == dtype
    assert np.isfinite(np.asarray(got, np.float32)).all()
    assert not np.asarray(got[0]).any()             # the inactive row
    assert float(jnp.abs(want[1:]).max()) > 1e-2
    assert float(jnp.abs(got - want)[1:].max()) < tol
    # one token: the output is that row's first ``r`` lanes, every head
    row = pool[tab[1, 0], 0, :r]
    assert float(jnp.abs(got[1, :, 0] - row[None]).max()) < TOL
    if "name" in kw:
        named = latent_decode_attention(q, pool, lens, tab, r, 0.125,
                                        name=kw["name"])
        assert bool(jnp.array_equal(named, got))


def test_latent_decode_refuses_what_it_cannot_walk():
    q = jnp.zeros((2, 4, 192))
    pool = jnp.zeros((5, 8, 192))
    tab = jnp.zeros((2, 2), jnp.int32)
    with pytest.raises(ValueError, match="multiple of 128"):
        latent_decode_attention(q, pool, [1, 1], tab, 128, 1.0)
    with pytest.raises(ValueError, match="do not match"):
        latent_decode_attention(jnp.zeros((2, 4, 256)), pool, [1, 1], tab,
                                128, 1.0)
