"""The paged decode kernel's grouped-query and window forms, in interpret
mode on the CPU, against the composed form of the same function
(``ops.pallas.paged_attention_composed``: a gathered view of each row's
table, masked, a plain softmax). GPT-2's cases (one query head a K/V head,
the full table) stay in ``test_decode_attention.py`` as they are.

float32 operands: the two sides differ by the order of float32 sums
(observed under 5e-7 on outputs of order 1).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from nezha_tpu.ops.pallas import (flash_decode_attention,
                                  paged_attention_composed, ring_entries)

TOL = 5e-6


def _case(group, m, lens, *, window=None, bs=8, kvh=2, d=16, seed=0):
    """Pools of ``1 + b * m`` blocks with every row's table a random
    permutation of its own blocks, every block filled (so a key the mask
    should hide shows if it does not)."""
    rng = np.random.default_rng(seed)
    b, h = len(lens), kvh * group
    n = 1 + b * m
    k = jnp.asarray(rng.normal(size=(n, bs, kvh * d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(n, bs, kvh * d)), jnp.float32)
    tab = jnp.asarray(1 + rng.permutation(b * m).reshape(b, m), jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, h, 1, d)), jnp.float32)
    lens = jnp.asarray(lens, jnp.int32)
    got = flash_decode_attention(q, k, v, lens, block_tables=tab,
                                 window=window)
    want = paged_attention_composed(q, k, v, lens, tab, window=window)
    return np.asarray(got), np.asarray(want), (q, k, v, tab, lens)


# Two K/V heads of 16 are a 32-lane pool (the GRID form walks the table),
# of 64 a whole 128-lane tile (the per-row LOOP walks a full table; a ring
# keeps the grid form at every width): `_paged_call`.
WIDTHS = pytest.mark.parametrize("d", [16, 64], ids=["hd32", "hd128"])


@WIDTHS
@pytest.mark.parametrize("group", [1, 2, 4, 8])
@pytest.mark.parametrize("m", [3, 6, 32])
def test_grouped_heads_over_a_full_table(group, m, d):
    """Tables of 3, 6 and 32 entries (1, 1 and 2 iterations a row at 16
    entries each); rows empty, inside a block, at a block's edge and at
    the table's end, an empty row between live ones and one last."""
    cap = m * 8
    got, want, _ = _case(group, m, (0, 5, 0, 16, cap, 0), d=d)
    assert got.shape == (6, 2 * group, 1, d)
    assert np.abs(got - want).max() < TOL
    assert not got[0].any()                       # the empty row


@WIDTHS
@pytest.mark.parametrize("group", [1, 2, 4, 8])
@pytest.mark.parametrize("window, bs", [(12, 8), (16, 8), (8, 4), (5, 8)])
def test_window_over_a_ring(group, window, bs, d):
    """A ring of ``ceil(window / bs) + 1`` entries: rows that have not
    filled their window, that have just filled it, that have wrapped the
    ring once and many times, and an empty row."""
    m = ring_entries(window, bs)
    lens = (0, 3, window, window + 1, m * bs + 3, 4001)
    got, want, _ = _case(group, m, lens, window=window, bs=bs, d=d)
    assert np.abs(got - want).max() < TOL
    assert not got[0].any()


@WIDTHS
def test_a_ring_wider_than_it_must_be_and_walked_in_two_steps(d):
    """32 entries for a window that needs 3: two iterations a row, one
    of which may hold no visible key; an empty row between two live
    ones."""
    got, want, _ = _case(4, 32, (1, 0, 9, 130, 0, 1000), window=12, d=d)
    assert np.abs(got - want).max() < TOL


def test_the_window_hides_what_the_composed_form_says_it_hides():
    """The composed form against a dense softmax written here: position
    p of a row lives in ring entry (p // bs) % m at offset p % bs."""
    window, bs, kvh, d = 12, 8, 2, 16
    m = ring_entries(window, bs)
    _, want, (q, k, v, tab, lens) = _case(2, m, (30,), window=window)
    length = int(lens[0])
    pos = np.arange(length - window, length)
    blk = np.asarray(tab)[0, (pos // bs) % m]
    keys = np.asarray(k)[blk, pos % bs].reshape(window, kvh, d)
    vals = np.asarray(v)[blk, pos % bs].reshape(window, kvh, d)
    qh = np.asarray(q)[0, :, 0]                          # [H, d]
    for h in range(4):
        s = keys[:, h // 2] @ qh[h] / np.sqrt(d)
        p = np.exp(s - s.max())
        out = (p / p.sum()) @ vals[:, h // 2]
        assert np.abs(out - want[0, h, 0]).max() < TOL


@pytest.mark.parametrize("kw, match", [
    (dict(window=40), "needs a ring of 6"),
    (dict(window=0), "needs a ring"),
    (dict(scales=True), "no grouped-query or window form"),
    (dict(heads=3), "H a multiple of KVH"),
])
def test_typed_refusals(kw, match):
    kvh, d, bs, m = 2, 16, 8, 3
    h = kw.get("heads", 4)
    k = jnp.zeros((1 + m, bs, kvh * d))
    q = jnp.zeros((1, h, 1, d))
    tab = jnp.zeros((1, m), jnp.int32)
    scales = ((jnp.ones((1 + m, h)),) * 2) if kw.get("scales") else None
    with pytest.raises(ValueError, match=match):
        flash_decode_attention(q, k, k, jnp.ones((1,), jnp.int32),
                               block_tables=tab, block_scales=scales,
                               window=kw.get("window"))


def test_window_without_a_table_is_refused():
    x = jnp.zeros((1, 2, 1, 16))
    with pytest.raises(ValueError, match="window requires block_tables"):
        flash_decode_attention(x, jnp.zeros((1, 2, 8, 16)),
                               jnp.zeros((1, 2, 8, 16)),
                               jnp.ones((1,), jnp.int32), window=4)
