"""serve/sampling.py's nucleus without a vocabulary sort.

``filter_logits`` reads each row's nucleus threshold off the ``k_max``
head ``lax.top_k`` returns and sorts the vocabulary only in a step where
some row's nucleus is wider than the head. The sort-based filter it
replaced is kept HERE as the reference: for every row with ``top_p < 1``
the kept set (the ``-inf`` mask) must be the reference's, ties included,
and the kept values bit for bit the same. ``top_p >= 1`` keeps every
top-k survivor, as documented (the reference's float32 cumulative sum
could reach 1.0 before a long row's end and drop a ~1e-7 tail there).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from nezha_tpu.serve import Engine, Request, Scheduler, ServeConfig
from nezha_tpu.serve.sampling import (filter_logits, filter_logits_and_flag,
                                      split_and_sample)

V, K_MAX = 512, 16


# ------------------------------------------------ the sort-based reference
def reference_filter(logits, temperature, top_k, top_p, k_max):
    """The filter as it was before the head-based threshold: top-k by rank
    against the k-th value, then a nucleus over the whole sorted row."""
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    kth_vals = lax.top_k(scaled, k_max)[0]
    k_eff = jnp.clip(top_k, 1, k_max)
    kth = jnp.take_along_axis(kth_vals, (k_eff - 1)[:, None], axis=1)
    apply_k = (top_k > 0)[:, None]
    scaled = jnp.where(apply_k & (scaled < kth), -jnp.inf, scaled)
    sorted_logits = jnp.flip(jnp.sort(scaled, axis=-1), axis=-1)
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    exclusive_cum = jnp.cumsum(probs, axis=-1) - probs
    rank = lax.broadcasted_iota(jnp.int32, sorted_logits.shape, 1)
    keep = (exclusive_cum < top_p[:, None]) | (rank == 0)
    threshold = jnp.min(
        jnp.where(keep, sorted_logits, jnp.inf), axis=-1, keepdims=True)
    return jnp.where(scaled < threshold, -jnp.inf, scaled)


def reference_split_and_sample(keys, logits, temperature, top_k, top_p,
                               k_max):
    splits = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
    scaled = reference_filter(logits, temperature, top_k, top_p, k_max)
    sampled = jax.vmap(jax.random.categorical)(splits[:, 1], scaled)
    tok = jnp.where(temperature <= 0.0, jnp.argmax(logits, axis=-1), sampled)
    return splits[:, 0], tok.astype(jnp.int32)


_new = jax.jit(filter_logits_and_flag, static_argnums=4)
_ref = jax.jit(reference_filter, static_argnums=4)


def _bf16(x):
    """Logits as a bf16 head would leave them: neighbours tie often."""
    return np.asarray(jnp.asarray(x, jnp.bfloat16).astype(jnp.float32))


def _rows(seed, n, scale, v=V):
    return (np.random.default_rng(seed).standard_normal((n, v)) * scale
            ).astype(np.float32)


def _params(n, temperature, top_k, top_p):
    full = lambda x, dt: np.broadcast_to(np.asarray(x, dt), (n,)).copy()
    return (full(temperature, np.float32), full(top_k, np.int32),
            full(top_p, np.float32))


def _tied_row(head, tied_value, n_tied, v=V):
    """``head`` distinct values on top, ``n_tied`` entries at
    ``tied_value`` under them, the rest far below, shuffled."""
    row = np.full(v, tied_value - 4.0, np.float32)
    row[:len(head)] = head
    row[len(head):len(head) + n_tied] = tied_value
    return np.random.default_rng(len(head) * 131 + n_tied).permutation(row)


def _check(logits, temperature, top_k, top_p, k_max=K_MAX, flag=None):
    """The kept set and the kept values are the reference's; ``flag``
    (when given) is whether the sort branch ran. A row with ``top_p >=
    1`` is held to the documented rule (every top-k survivor kept): the
    reference is given an infinite ``top_p`` there, which its cumulative
    sum cannot reach."""
    args = (jnp.asarray(logits), jnp.asarray(temperature),
            jnp.asarray(top_k))
    top_p = jnp.asarray(top_p)
    got, sorted_ = _new(*args, top_p, k_max)
    want = np.asarray(_ref(*args, jnp.where(top_p >= 1, jnp.inf, top_p),
                           k_max))
    got = np.asarray(got)
    kept = ~np.isneginf(got)
    np.testing.assert_array_equal(kept, ~np.isneginf(want))
    np.testing.assert_array_equal(got[kept], want[kept])
    assert kept.any(axis=-1).all()                 # never an empty nucleus
    if flag is not None:
        assert bool(sorted_) is flag
    return kept


# ---------------------------------------------------- masks, case by case
@pytest.mark.parametrize("n_tied", [3, 13, 14, 40],
                         ids=lambda n: f"{n}-tied")
@pytest.mark.parametrize("top_p", [0.5, 0.9, 0.95])
def test_ties_at_the_kth_value_inside_and_beyond_the_head(n_tied, top_p):
    """top_k = 3 over two distinct values and ``n_tied`` entries at the
    third: the ties fill part of the 16-entry head (3), exactly the rest
    of it (14 with the 2 above), or run past it (40). ``scaled < kth``
    keeps every tie, so the normaliser must count those outside the head
    too; bf16-rounded, as a model's logits are."""
    rows = np.stack([_bf16(_tied_row([5.0, 4.5], 4.0, n_tied)),
                     _bf16(_tied_row([2.0, 1.75], 1.5, n_tied)),
                     _bf16(_tied_row([0.25, 0.125], 0.0625, n_tied))])
    kept = _check(rows, *_params(3, 0.8, 3, top_p), flag=False)
    assert set(kept.sum(axis=-1)) <= {1, 2, 2 + n_tied}


@pytest.mark.parametrize("top_p", [1e-6, 0.5, 0.9, 0.95])
@pytest.mark.parametrize("top_k", [0, 1, 5, K_MAX, K_MAX + 9],
                         ids=["k-off", "k-1", "k-under", "k-at-cap",
                              "k-over-cap"])
def test_top_k_and_top_p_grid(top_k, top_p):
    """Every per-row top-k setting against every top_p, on peaked and
    flat rows, raw and bf16-rounded; with top-k off the flat rows'
    nuclei are wider than the head and go through the sort branch."""
    rows = np.concatenate([_rows(1, 4, 6.0), _bf16(_rows(2, 4, 6.0)),
                           _rows(3, 4, 0.3), _bf16(_rows(4, 4, 0.3))])
    _check(rows, *_params(len(rows), 0.8, top_k, top_p),
           flag=None if top_k == 0 else False)


def test_mixed_per_row_params_in_one_batch():
    """Each row its own (temperature, top_k, top_p), as the step program
    sees them: the filter is per row whatever the neighbours ask for."""
    rng = np.random.default_rng(5)
    for scale in (0.2, 1.0, 4.0):
        rows = _bf16(_rows(int(scale * 10), 24, scale))
        _check(rows,
               rng.choice([0.0, 0.5, 0.8, 1.0, 1.5], 24).astype(np.float32),
               rng.choice([0, 1, 5, K_MAX, 100], 24).astype(np.int32),
               rng.choice([1e-6, 0.1, 0.5, 0.9, 0.95], 24
                          ).astype(np.float32))


def test_peaked_row_beside_a_flat_row_takes_the_sort_for_the_flat_one():
    """top-k off, top_p 0.9: the peaked row's nucleus fits the head, the
    flat row's is hundreds of entries wide. One batch, both right, and
    the sort branch ran (for the flat row only: the peaked row keeps the
    head's threshold)."""
    rows = np.stack([_rows(6, 1, 8.0)[0], _rows(7, 1, 0.05)[0]])
    kept = _check(rows, *_params(2, 0.8, 0, 0.9), flag=True)
    assert kept[0].sum() <= K_MAX < kept[1].sum() < V


def test_greedy_rows_beside_sampled_ones():
    """temperature 0 scales by 1e6 (the greedy row's filter result is
    unused by ``sample_tokens`` but is part of the contract)."""
    rows = _bf16(_rows(8, 6, 1.0))
    temperature = np.asarray([0.0, 0.8, 0.0, 1.5, 0.0, 0.8], np.float32)
    top_k = np.asarray([0, 0, 5, 5, 40, 40], np.int32)
    _check(rows, temperature, top_k, np.full(6, 0.9, np.float32))
    _check(rows, temperature, top_k, np.full(6, 0.5, np.float32))


@pytest.mark.parametrize("top_p", [1.0, 1.5])
@pytest.mark.parametrize("top_k", [0, 5], ids=["k-off", "k-5"])
def test_top_p_of_one_or_more_keeps_every_survivor(top_k, top_p):
    """The documented rule, and the one intended difference from the
    sort-based filter, whose float32 cumulative sum could reach 1.0
    before a long row's end and drop a ~1e-7 tail (by how the sum
    happened to round): no nucleus, no sort, every survivor kept."""
    rows = _rows(9, 4, 0.01, v=4096)
    kept = _check(rows, *_params(4, 0.8, top_k, top_p), flag=False)
    assert (kept.sum(axis=-1) == (top_k or 4096)).all()


def test_head_holds_the_nucleus_when_nothing_lies_below_its_last_value():
    """top-k off and every head entry inside the nucleus, but the row has
    no entry under the head's last value (three distinct values, the
    lowest repeated to the end): the head's threshold is exact and no
    sort runs."""
    row = np.full(V, 1.0, np.float32)
    row[:2] = [3.0, 2.0]
    _check(row[None], *_params(1, 1.0, 0, 0.99), flag=False)


# ------------------------------------------------ when the sort branch runs
@pytest.mark.parametrize("case", ["all-top-k", "all-peaked-top-p"])
def test_sort_branch_is_not_taken(case):
    """The benchmark's traffic (T 0.8, top-k 40, top_p 1.0 and under) and
    peaked top_p 0.9 traffic with top-k off: no step sorts."""
    if case == "all-top-k":
        rows = np.concatenate([_rows(10, 4, 0.05), _bf16(_rows(11, 4, 3.0))])
        top_p = np.asarray([1.0, 0.9] * 4, np.float32)
        _check(rows, *_params(8, 0.8, 40, 1.0)[:2], top_p, k_max=64,
               flag=False)
    else:
        rows = np.concatenate([_rows(12, 4, 8.0), _bf16(_rows(13, 4, 8.0))])
        _check(rows, *_params(8, 0.8, 0, 0.9), k_max=64, flag=False)


def test_filter_logits_is_the_flagged_filters_first_result():
    rows = _rows(14, 4, 1.0)
    args = (jnp.asarray(rows), *map(jnp.asarray, _params(4, 0.8, 0, 0.9)))
    np.testing.assert_array_equal(
        np.asarray(filter_logits(*args, K_MAX)),
        np.asarray(filter_logits_and_flag(*args, K_MAX)[0]))


@pytest.mark.parametrize("top_k", [0, 40], ids=["k-off", "k-40"])
def test_split_and_sample_gives_the_references_tokens(top_k):
    """Same keys, same kept set, same values: ``categorical`` draws the
    same tokens, so a request's stream for its seed does not move."""
    rows = jnp.asarray(np.concatenate([_rows(15, 8, 2.0),
                                       _bf16(_rows(16, 8, 0.2))]))
    temperature, k, top_p = map(jnp.asarray, _params(16, 0.8, top_k, 0.9))
    temperature = temperature.at[3].set(0.0)             # one greedy row
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(16, dtype=jnp.uint32))
    for _ in range(5):
        new_keys, tok, _ = split_and_sample(keys, rows, temperature, k,
                                            top_p, 64)
        ref_keys, ref_tok = reference_split_and_sample(
            keys, rows, temperature, k, top_p, 64)
        np.testing.assert_array_equal(np.asarray(tok), np.asarray(ref_tok))
        np.testing.assert_array_equal(np.asarray(new_keys),
                                      np.asarray(ref_keys))
        keys = new_keys


# ------------------------------------------- the counter, through the engine
@pytest.fixture
def flat_logits_scheduler(tmp_path):
    """(decode_horizon, speculative) -> (engine, scheduler) under a run dir, over a tiny
    GPT-2 whose weights are scaled to almost nothing: 97 near-equal
    logits against a 16-entry head, so a ``top_k off, top_p 0.9`` row's
    nucleus is always wider than the head."""
    from nezha_tpu import obs
    from nezha_tpu.models.gpt2 import GPT2, GPT2Config

    model = GPT2(GPT2Config(vocab_size=97, max_positions=64, num_layers=1,
                            num_heads=2, hidden_size=32))
    variables = jax.tree_util.tree_map(
        lambda a: a * 1e-3, model.init(jax.random.PRNGKey(0)))
    obs.start_run(str(tmp_path / "run"), meta={"kind": "serve_test"})

    def build(decode_horizon, speculative=None):
        engine = Engine(model, variables, ServeConfig(
            max_batch_size=2, max_len=32, max_prefill_len=8,
            prefill_buckets=(8,), k_max=16, queue_capacity=4,
            cache_dtype=jnp.float32, decode_horizon=decode_horizon,
            speculative=speculative))
        return engine, Scheduler(engine)

    yield build
    obs.end_run()


def _full_sort_steps():
    from nezha_tpu import obs
    return obs.counter("serve.sampling.full_sort_steps_total").value


@pytest.mark.parametrize("traffic", ["top-k", "wide-nucleus"])
def test_engine_counts_the_steps_that_sorted(flat_logits_scheduler, traffic):
    """``serve.sampling.full_sort_steps_total`` is 0 for top-k traffic and
    the step count for a ``top_k off, top_p 0.9`` row. A retired slot's
    stale ``top_p`` must not count: a top-k request then decodes beside
    the first one's retired slot."""
    engine, sched = flat_logits_scheduler(1)
    top_k = 5 if traffic == "top-k" else 0
    sched.submit(Request(prompt=[3, 1, 4], max_new_tokens=6,
                         temperature=0.8, top_k=top_k, top_p=0.9, seed=1))
    sched.run_until_idle(max_iters=50)
    sched.submit(Request(prompt=[2, 7], max_new_tokens=4, temperature=0.8,
                         top_k=5, seed=2))
    sched.run_until_idle(max_iters=50)
    assert not sched.has_work()
    assert engine.step_calls == 10
    assert _full_sort_steps() == (0 if traffic == "top-k" else 6)


def test_horizon_block_sums_its_steps_that_sorted(flat_logits_scheduler):
    """At decode_horizon 4 the step program returns the count over its
    scan: 8 wide-nucleus tokens are 8 sorted steps in 2 dispatches."""
    engine, sched = flat_logits_scheduler(4)
    sched.submit(Request(prompt=[3, 1, 4], max_new_tokens=8,
                         temperature=0.8, top_k=0, top_p=0.9, seed=1))
    sched.run_until_idle(max_iters=50)
    assert engine.step_calls == 2
    assert _full_sort_steps() == 8


@pytest.mark.parametrize("traffic", ["top-k", "wide-nucleus"])
def test_speculative_windows_sort_only_for_a_wide_nucleus(
        flat_logits_scheduler, traffic):
    """The draft->verify window filters the carried row, each draft row
    and the k verify positions (as ``B * k`` rows: a ``vmap`` would batch
    the predicate and turn the ``cond`` into a select that always
    sorts): none of them sorts for top-k traffic, every window does for
    the wide nucleus."""
    from nezha_tpu.serve.engine import SpeculativeConfig

    engine, sched = flat_logits_scheduler(
        1, SpeculativeConfig(draft_k=2, draft_layers=1))
    sched.submit(Request(prompt=[3, 1, 4], max_new_tokens=6,
                         temperature=0.8, seed=1, top_p=0.9,
                         top_k=5 if traffic == "top-k" else 0))
    sched.run_until_idle(max_iters=50)
    assert not sched.has_work()
    assert _full_sort_steps() == (0 if traffic == "top-k"
                                  else engine.step_calls)
