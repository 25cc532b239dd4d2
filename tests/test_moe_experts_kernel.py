"""The routed experts' kernel (``nezha_moe_experts``) in interpret mode.

The kernel against three ``jax.lax.ragged_dot`` calls on the same operands
(its contract: the same three rounding points), its visit plan against a
brute-force count, ``DroplessMoE.apply`` end to end against a per-token
Python loop over the held experts, and the engine's counters of a decode
step's visits. Widths are the three serving cells' scaled down to whole
128-lane tiles; ``tiles=`` puts a row tile's and a ``d_ff`` tile's edges
where a test can afford them (the rule itself is pinned at the published
widths below, and compiled for a v5e in the ``test_tpu_compile*`` files).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nezha_tpu.ops.pallas.moe_experts import (moe_experts,
                                              moe_experts_reference,
                                              tile_sizes, visit_plan)
from nezha_tpu.parallel.expert import (DroplessMoE, DroplessMoEConfig,
                                       route_top_k)


def _routed_sizes(rng, tokens, top_k, experts, held):
    """Group sizes as a router gives them: every token draws ``top_k``
    distinct experts of all ``experts``; the first ``held`` are here."""
    ids = np.stack([rng.choice(experts, top_k, replace=False)
                    for _ in range(tokens)])
    return np.bincount(ids[ids < held], minlength=held)


def _operands(rows, held, d, d_ff, dtype, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    xs = jax.random.normal(k[0], (rows, d), jnp.float32).astype(dtype)
    shapes = [(held, d, d_ff), (held, d, d_ff), (held, d_ff, d)]
    return xs, [(jax.random.normal(kk, s, jnp.float32) * 0.1).astype(dtype)
                for kk, s in zip(k[1:], shapes)]


def _one_hot_sizes(held, at, n):
    sizes = np.zeros((held,), np.int64)
    sizes[at] = n
    return sizes


# name: (held, d, d_ff, rows, sizes, tiles)
_RNG = np.random.default_rng(33)
CASES = {
    # the three cells' decode steps: (held, top_k, tokens) as served,
    # d / d_ff scaled to 256 / 128, 384 / 128 and 256 / 128
    "mistral-decode": (32, 256, 128, 512,
                       _routed_sizes(_RNG, 128, 4, 128, 32), None),
    "k-exaone-decode": (16, 384, 128, 1024,
                        _routed_sizes(_RNG, 128, 8, 128, 16), None),
    "kimi-decode": (64, 256, 128, 2048,
                    _routed_sizes(_RNG, 256, 8, 256, 64), None),
    "empty-experts": (8, 128, 128, 256, [9, 0, 0, 31, 0, 1, 17, 0], None),
    "one-expert-holds-every-row": (8, 128, 128, 256,
                                   _one_hot_sizes(8, 5, 256), (64, 128, 32)),
    "no-row": (8, 128, 128, 256, [0] * 8, None),
    "one-row": (8, 128, 128, 256, _one_hot_sizes(8, 7, 1), None),
    "every-row-held": (8, 128, 128, 256, [32] * 8, (64, 128, 32)),
    # groups cut by a row tile's edge (64 rows) and by a window's (16),
    # over two d_ff tiles
    "edges-inside-tiles": (8, 128, 256, 256,
                           [3, 70, 0, 5, 59, 1, 40, 2], (64, 128, 16)),
    "one-row-tile-two-ff-tiles": (8, 128, 256, 256,
                                  [3, 70, 0, 5, 59, 1, 40, 2],
                                  (256, 128, 32)),
    # a 1,024-token chunk: 4,096 pair rows, a quarter of them held
    "chunk": (8, 128, 128, 4096,
              _routed_sizes(_RNG, 1024, 4, 32, 8), (512, 128, 128)),
    # rows that are not whole 16-row tiles are padded by the wrapper
    "odd-rows": (4, 64, 32, 24, [3, 0, 10, 5], None),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_matches_three_ragged_dots(name):
    """float32 operands: the kernel's only freedom is the order in which
    the ``d_ff`` tiles' partial products are summed, round-off of a few
    1e-6 on results of order 1."""
    held, d, d_ff, rows, sizes, tiles = CASES[name]
    sizes = jnp.asarray(np.asarray(sizes), jnp.int32)
    n = int(sizes.sum())
    xs, weights = _operands(rows, held, d, d_ff, jnp.float32)
    out, stats = moe_experts(xs, sizes, *weights, tiles=tiles)
    assert out.shape == (rows, d) and out.dtype == jnp.float32
    want = moe_experts_reference(xs, sizes, *weights)
    assert float(jnp.abs(out[:n] - want[:n]).max(initial=0.0)) < 2e-5
    assert float(jnp.abs(want[:n]).max(initial=1.0)) > 0.1
    # what lies past sum(sizes) must not reach the visited rows
    poisoned = xs.at[n:].set(jnp.nan)
    again, _ = moe_experts(poisoned, sizes, *weights, tiles=tiles)
    assert jnp.array_equal(again[:n], out[:n])
    assert int(stats[1]) == int((np.asarray(sizes) > 0).sum())
    assert int(stats[0]) >= int(stats[1])
    if tiles is None:       # one row tile: every touched expert once
        assert int(stats[0]) == int(stats[1])


@pytest.mark.parametrize("name", ["mistral-decode", "edges-inside-tiles"])
def test_kernel_keeps_the_three_rounding_points_in_bf16(name):
    """bf16 operands, float32 accumulation, ``h`` rounded to bf16 before
    the down projection: as the three grouped matmuls. What may differ is
    an ``h`` entry rounded the other way after a last-bit difference of
    ``gate * up`` (one bf16 ulp of ``h``, 4e-3 of it, times one weight)."""
    held, d, d_ff, rows, sizes, tiles = CASES[name]
    sizes = jnp.asarray(np.asarray(sizes), jnp.int32)
    n = int(sizes.sum())
    xs, weights = _operands(rows, held, d, d_ff, jnp.bfloat16)
    out, _ = moe_experts(xs, sizes, *weights, tiles=tiles)
    want = moe_experts_reference(xs, sizes, *weights)
    scale = float(jnp.abs(want[:n]).max())
    assert float(jnp.abs(out[:n] - want[:n]).max()) < 4e-3 * scale
    # and a float32 computation of the same bf16 operands is 10x further
    exact = moe_experts_reference(
        xs.astype(jnp.float32), sizes,
        *[w.astype(jnp.float32) for w in weights])
    assert float(jnp.abs(exact[:n] - want[:n]).max()) > float(
        jnp.abs(out[:n] - want[:n]).max())


def test_mixed_dtypes_are_refused():
    xs, weights = _operands(32, 2, 64, 32, jnp.float32)
    with pytest.raises(ValueError, match="share one dtype"):
        moe_experts(xs.astype(jnp.bfloat16), jnp.asarray([3, 4]), *weights)


@pytest.mark.parametrize("tm", [16, 64, 256])
def test_visit_plan_lists_every_tile_a_group_touches(tm):
    rng = np.random.default_rng(tm)
    rows, held = 256, 12
    for _ in range(20):
        sizes = rng.multinomial(rng.integers(0, rows + 1),
                                rng.dirichlet(np.full(held, 0.3)))
        off, eid, tid, stats = (np.asarray(a) for a in visit_plan(
            jnp.asarray(sizes, jnp.int32), rows, tm))
        ends = np.cumsum(sizes)
        want = [(e, t) for e in range(held) if sizes[e]
                for t in range((ends[e] - sizes[e]) // tm,
                               (ends[e] - 1) // tm + 1)]
        assert off.tolist() == [0] + ends.tolist()
        assert stats.tolist() == [len(want), int((sizes > 0).sum())]
        assert len(eid) == len(tid) == held + rows // tm - 1 >= len(want)
        assert list(zip(eid, tid))[:len(want)] == want
        # past the last visit the plan repeats it: nothing new to copy
        last = want[-1] if want else (eid[0], tid[0])
        assert all(pair == last for pair in list(zip(eid, tid))[len(want):])


def test_tile_rule_at_the_three_published_widths():
    """(tm, tf, window) of the cells' decode steps and 1,024-token chunks
    (bf16): a decode step's held rows (~128 / ~128 / ~512) fit ONE row
    tile, so every touched expert is visited once."""
    assert tile_sizes(512, 4096, 2048, 2) == (512, 512, 64)         # Mistral
    assert tile_sizes(4096, 4096, 2048, 2) == (512, 512, 64)
    assert tile_sizes(1024, 6144, 2048, 2) == (512, 512, 64)        # K-EXAONE
    assert tile_sizes(8192, 6144, 2048, 2) == (512, 512, 64)
    assert tile_sizes(2048, 2304, 1024, 2) == (1024, 1024, 64)      # Kimi
    assert tile_sizes(8192, 2304, 1024, 2) == (1024, 1024, 64)
    # the tiny presets (float32, 64 / 32 wide): whole widths, one tile
    assert tile_sizes(16, 64, 32, 4) == (16, 32, 16)


def _loop_over_held_experts(layer, params, x):
    """``DroplessMoE.apply`` a token and an expert at a time."""
    cfg = layer.cfg
    first, held = cfg.experts_held
    logits = x @ params["router"]["w"]
    ids, weights = route_top_k(logits, cfg.top_k, cfg.norm_topk_prob,
                               cfg.routed_scaling_factor, cfg.score_func,
                               params["router"].get("bias"))
    ids, weights = np.asarray(ids), np.asarray(weights)
    y = np.zeros(x.shape, np.float64)
    load = np.zeros((held,), np.int64)
    for t in range(x.shape[0]):
        for e, w in zip(ids[t], weights[t]):
            if first <= e < first + held:
                xt = np.asarray(x[t], np.float64)
                gate = xt @ np.asarray(params["w_gate"][e - first], np.float64)
                up = xt @ np.asarray(params["w_up"][e - first], np.float64)
                h = gate / (1.0 + np.exp(-gate)) * up
                y[t] += w * (h @ np.asarray(params["w_down"][e - first],
                                            np.float64))
                load[e - first] += 1
    return y, load


@pytest.mark.parametrize("score_func,held", [("softmax", (0, 4)),
                                             ("sigmoid", (4, 8)),
                                             ("softmax", (0, 16))])
def test_dropless_apply_matches_a_loop_over_tokens_and_held_experts(
        score_func, held):
    cfg = DroplessMoEConfig(64, 32, 16, 4, held, score_func=score_func,
                            routed_scaling_factor=2.5)
    layer = DroplessMoE(cfg)
    params = layer.init(jax.random.PRNGKey(1))["params"]
    params = jax.tree_util.tree_map(lambda a: a * 10.0, params)
    x = jax.random.normal(jax.random.PRNGKey(2), (40, 64))
    y, st = jax.jit(lambda p, x_: layer.apply(
        {"params": p, "state": {}}, x_))(params, x)
    want, load = _loop_over_held_experts(layer, params, x)
    assert np.abs(np.asarray(y) - want).max() < 1e-4 * np.abs(want).max()
    assert np.abs(want).max() > 0.1
    assert st["load"].tolist() == load.tolist()
    touched = int((load > 0).sum())
    assert st["visits"].tolist() == [touched, touched]


def test_decode_size_call_visits_each_touched_expert_once():
    """A decode step of 128 rows, top-4 of 128 experts, 32 held (the
    Mistral cell's, 64 wide): the pair rows are one row tile, so the
    kernel's visits are the touched experts, whatever the routing."""
    cfg = DroplessMoEConfig(64, 32, 128, 4, (0, 32))
    layer = DroplessMoE(cfg)
    apply = jax.jit(lambda p, x_: layer.apply(
        {"params": p, "state": {}}, x_)[1])
    for seed in range(3):
        params = layer.init(jax.random.PRNGKey(seed))["params"]
        params["router"]["w"] = params["router"]["w"] * 50.0
        st = apply(params, jax.random.normal(
            jax.random.PRNGKey(10 + seed), (128, 64)))
        touched = int((np.asarray(st["load"]) > 0).sum())
        assert 0 < touched <= 32
        assert st["visits"].tolist() == [touched, touched]


def test_engine_counts_a_decode_steps_visits(tmp_path):
    """``serve.moe.expert_visits_total`` beside
    ``serve.moe.experts_touched_total``: equal for decode steps (one
    visit a touched expert), both pinned in the telemetry schema."""
    from nezha_tpu import obs
    from nezha_tpu.analysis import telemetry_schema
    from nezha_tpu.models.mistral4 import mistral_small4
    from nezha_tpu.serve import Engine, ServeConfig

    names = ("serve.moe.expert_visits_total",
             "serve.moe.experts_touched_total")
    assert set(names) <= telemetry_schema.PINNED_COUNTERS
    model = mistral_small4("tiny")
    eng = Engine(model, model.init(jax.random.PRNGKey(0)), ServeConfig(
        max_batch_size=3, max_len=64, max_prefill_len=16,
        prefill_buckets=(8, 16), kv_block_size=8, cache_dtype=jnp.float32))
    obs.start_run(str(tmp_path / "run"), meta={"kind": "serve_test"})
    try:
        slots = [eng.pool.alloc(), eng.pool.alloc()]
        for slot, prompt in zip(slots, ([5, 9, 2, 7], [11, 3, 8])):
            eng.prefill(slot, prompt, max_new_tokens=8)
        active = np.zeros((3,), bool)
        active[slots] = True
        touched = 0
        for _ in range(4):
            eng.step(active)
            touched += int((eng.last_expert_load > 0).sum())
        visits, counted = (obs.counter(n).value for n in names)
    finally:
        obs.end_run()
    # the inactive third row is routed too, so the kernel may touch an
    # expert no active row chose
    assert visits == counted >= touched > 0
