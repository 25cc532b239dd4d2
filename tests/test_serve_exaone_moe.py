"""K-EXAONE on the serving path, at tiny size on the CPU rig: the model
against the plain reference (``chipbench/reference/exaone_moe.py``), the
pool's two cache groups, the share test under the sigmoid router, and the
typed refusals.

Seeded random weights and logits throughout, never sampled tokens. The
tiny preset computes in float32, so every tolerance below is float32
round-off over a few dozen 64-wide contractions with a wide margin (1e-4
absolute on logits of order 1; observed 3e-7): a program that computed in
bf16, whose rounding alone is 4e-3 of a value, fails each of them by an
order of magnitude or more.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench.reference import exaone_moe as ref
from nezha_tpu.models.exaone_moe import (TINY_KW, ExaoneMoe, ExaoneMoeConfig,
                                         k_exaone)
from nezha_tpu.ops import rotary
from nezha_tpu.parallel.expert import (DroplessMoE, DroplessMoEConfig,
                                       route_top_k)
from nezha_tpu.serve import Engine, ServeConfig
from nezha_tpu.serve.slots import PagedSlotPool

F32_TOL = 1e-4


def ref_cfg(c: ExaoneMoeConfig) -> dict:
    """The reference's view of a config: the published keys, as the
    configuration file spells them."""
    return {
        "num_attention_heads": c.num_attention_heads,
        "num_key_value_heads": c.num_key_value_heads, "head_dim": c.head_dim,
        "rms_norm_eps": c.rms_norm_eps, "layer_types": list(c.layer_types),
        "sliding_window": c.sliding_window,
        "rope_parameters": {"rope_theta": c.rope_theta},
        "num_experts_per_tok": c.num_experts_per_tok,
        "norm_topk_prob": c.norm_topk_prob,
        "routed_scaling_factor": c.routed_scaling_factor,
        "experts_held": list(c.experts_held)}


@pytest.fixture(scope="module")
def tiny():
    model = k_exaone("tiny")
    return model, model.init(jax.random.PRNGKey(0))


def _engine(model, variables, **kw):
    # window 8 over blocks of 4: a ring of 3 entries (12 positions);
    # chunks of 16 with buckets 8/16
    kw = {"max_batch_size": 3, "max_len": 96, "max_prefill_len": 16,
          "prefill_buckets": (8, 16), "kv_block_size": 4,
          "cache_dtype": jnp.float32, "prefix_cache": False, **kw}
    return Engine(model, variables, ServeConfig(**kw))


def _ref_row(variables, c, seq):
    """The reference's logits for the last token of ``seq``."""
    return ref.logits_at(variables["params"], jnp.asarray([seq], jnp.int32),
                         jnp.asarray([[len(seq) - 1]]), ref_cfg(c))[0, 0]


def _ref_rows(variables, c, seq, first: int):
    """The reference's logits for the tokens of ``seq`` from index
    ``first`` on. One padded length for every call (the reference is
    causal, so what follows a token does not reach it): one compiled
    program for every row."""
    padded = np.zeros((1, 96), np.int32)
    padded[0, :len(seq)] = seq
    return np.asarray(ref.logits_at(
        variables["params"], jnp.asarray(padded),
        jnp.arange(first, len(seq))[None], ref_cfg(c)))[0]


# (a) the cache-less forward
def test_cacheless_forward_matches_reference(tiny):
    model, variables = tiny
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0, 512)
    got, _ = model.apply(variables, toks)
    want = ref.logits_at(variables["params"], toks,
                         jnp.tile(jnp.arange(40)[None], (2, 1)),
                         ref_cfg(model.cfg))
    assert got.dtype == jnp.float32 and got.shape == (2, 40, 512)
    assert float(jnp.abs(want).max()) > 0.3
    assert float(jnp.abs(got - want).max()) < F32_TOL


# (a) prefill in chunks, then decode through both cache groups, rows of
# different lengths in one batch. Prompt 37 = chunks 16 + 16 + 5 (in the
# 8 bucket): the chunk boundaries at 16 and 32 fall inside a window of 8,
# the tail's three pads must not be written, and 37 + 30 positions take
# the 12-position ring round more than five times.
@pytest.mark.parametrize("impl", ["xla", "kernel"])
def test_engine_prefill_and_decode_match_reference(tiny, impl):
    model, variables = tiny
    eng = _engine(model, variables, decode_impl=impl)
    assert eng.pool.window == 8 and eng.pool.window_entries == 3
    rng = np.random.default_rng(3)
    seqs = [rng.integers(0, 512, n).tolist() for n in (37, 5, 21)]
    for seq in seqs:
        eng.prefill(eng.pool.alloc(), seq, max_new_tokens=40)
    active = np.ones(3, bool)
    got = [[] for _ in seqs]            # each row's logits, step by step
    for _ in range(30):
        for r, rows in enumerate(got):
            rows.append(np.asarray(eng.last_logits[r]))
        tok, emitted = eng.step(active)
        assert emitted.tolist() == [1, 1, 1]
        for r, seq in enumerate(seqs):
            seq.append(int(tok[r, 0]))
    # one reference call a row, over the tokens its compared rows saw
    worst = max(float(np.abs(np.stack(rows) - _ref_rows(
        variables, model.cfg, seq[:-1], len(seq) - 1 - len(rows))).max())
        for rows, seq in zip(got, seqs))
    assert worst < F32_TOL
    assert eng.last_expert_load.shape == (4, 4)     # sparse layers x held
    # the ring never grew; the growing group holds what was written
    assert eng.pool.window_blocks_used == 3 * 3
    assert eng.pool.blocks_used == sum(-(-len(s) // 4) for s in seqs)
    for slot in range(3):
        eng.pool.free(slot)
    eng.pool.leak_check()
    assert eng.pool.window_blocks_used == 0 and eng.pool.blocks_used == 0


def test_a_row_that_stops_leaves_the_others_exact(tiny):
    """An inactive row writes the scratch block of either group and
    attends nothing; its neighbours' logits do not move."""
    model, variables = tiny
    eng = _engine(model, variables)
    rng = np.random.default_rng(4)
    seqs = [rng.integers(0, 512, n).tolist() for n in (9, 14)]
    for seq in seqs:
        eng.prefill(eng.pool.alloc(), seq, max_new_tokens=20)
    active = np.array([True, True, False])
    for i in range(12):
        if i == 4:
            active[0] = False
        tok, emitted = eng.step(active)
        for r in np.flatnonzero(active[:2]):
            seqs[r].append(int(tok[r, 0]))
    assert len(seqs[0]) == 9 + 4 and len(seqs[1]) == 14 + 12
    got = eng.last_logits[1]
    assert float(jnp.abs(got - _ref_row(variables, model.cfg, seqs[1])).max()
                 ) < F32_TOL


def test_half_split_rotary_is_the_interleaved_one_permuted():
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 5, 2, 16))
    pos = jnp.arange(5)[None, :, None]
    inv = 1e6 ** (-jnp.arange(0, 16, 2, dtype=jnp.float32) / 16)
    got = rotary.apply_half_split(x, pos, inv)
    # pair i of the half-split form is (x[i], x[i + 8])
    inter = jnp.stack([x[..., :8], x[..., 8:]], -1).reshape(x.shape)
    want = rotary.apply_interleaved(inter, pos, inv)
    want = jnp.concatenate([want[..., 0::2], want[..., 1::2]], -1)
    assert float(jnp.abs(got - want).max()) < 1e-6
    # and the reference's own rotation agrees
    theirs = ref._rope(x, jnp.arange(5, dtype=jnp.float32), 1e6)
    assert float(jnp.abs(got - theirs).max()) < 1e-6


# (d) the share test: the routed parts of all the shares plus the shared
# expert counted once add up to the uncut layer, under the sigmoid router
# with its selection bias
def test_shares_add_up_to_the_uncut_layer_under_the_sigmoid_router(tiny):
    model, _ = tiny
    c = model.cfg
    whole = dataclasses.replace(c, experts_held=(0, c.num_experts))
    blk = ExaoneMoe(whole).init(jax.random.PRNGKey(5))["params"]["h1"]
    assert blk["moe"]["router"]["bias"].shape == (c.num_experts,)
    assert float(jnp.abs(blk["moe"]["router"]["bias"]).max()) > 0
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 50, c.hidden_size))
    want, _ = ref._moe(blk, x, ref_cfg(whole))          # the uncut layer
    shared = ref._gated(blk["shared"]["gate"]["w"], blk["shared"]["up"]["w"],
                        blk["shared"]["down"]["w"], x)
    total = shared                                      # counted once
    for first in range(0, c.num_experts, 4):
        layer = DroplessMoE(DroplessMoEConfig(
            c.hidden_size, c.moe_intermediate_size, c.num_experts,
            c.num_experts_per_tok, (first, 4),
            routed_scaling_factor=c.routed_scaling_factor,
            score_func="sigmoid"))
        share = {"router": blk["moe"]["router"],
                 **{k: blk["moe"][k][first:first + 4]
                    for k in ("w_gate", "w_up", "w_down")}}
        y, _ = layer.apply({"params": share, "state": {}}, x[0])
        total = total + y[None]
        got_ref, _ = ref._moe({**blk, "moe": share}, x,
                              {**ref_cfg(whole), "experts_held": [first, 4]})
        assert jnp.abs(shared + y[None] - got_ref).max() < 1e-5
    assert jnp.abs(total - want).max() < 1e-5


def test_the_bias_changes_who_is_chosen_and_not_the_weights():
    logits = jnp.asarray([[2.0, 1.0, 0.5, -1.0]])
    s = jax.nn.sigmoid(logits)[0]
    ids, w = route_top_k(logits, 2, True, 2.5, "sigmoid", jnp.zeros((4,)))
    assert sorted(ids[0].tolist()) == [0, 1]
    # a bias that lifts expert 3 over expert 1: chosen {0, 3}, weighed by
    # their own sigmoid scores, the bias nowhere in the weights
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.6])
    ids, w = route_top_k(logits, 2, True, 2.5, "sigmoid", bias)
    assert sorted(ids[0].tolist()) == [0, 3]
    got = dict(zip(ids[0].tolist(), w[0].tolist()))
    assert got[0] == pytest.approx(2.5 * float(s[0] / (s[0] + s[3])), rel=1e-6)
    assert got[3] == pytest.approx(2.5 * float(s[3] / (s[0] + s[3])), rel=1e-6)
    # softmax stays what it was, and an unknown score function is refused
    ids, w = route_top_k(logits, 2, False, 1.0)
    assert w[0].tolist() == pytest.approx(
        jax.nn.softmax(logits)[0, :2].tolist())
    with pytest.raises(ValueError, match="score_func"):
        DroplessMoE(DroplessMoEConfig(8, 8, 4, 2, (0, 4), score_func="tanh"))


def test_reference_router_margin_is_on_the_biased_score():
    """Experts 0-1 held of 8, two a token. With the bias, the selection
    scores are s + b: expert 0 (held) is chosen 0.01 above the first left
    out, which is what the margin must report, in ulps of 2**-8."""
    cfg = {"num_experts_per_tok": 2, "experts_held": [0, 2],
           "norm_topk_prob": True, "routed_scaling_factor": 2.5}
    zeros = {"w": jnp.zeros((4, 4))}
    bias = jnp.asarray([0.21, -0.4, 0.0, 0.0, 0.0, 0.2, 0.3, 0.0])
    blk = {"shared": {"gate": zeros, "up": zeros, "down": zeros},
           "moe": {"router": {"w": jnp.zeros((4, 8)), "bias": bias},
                   "w_gate": jnp.zeros((2, 4, 4)), "w_up": jnp.zeros((2, 4, 4)),
                   "w_down": jnp.zeros((2, 4, 4))}}
    x = jnp.asarray([[[1.0, 0.0, 0.0, 0.0]]])
    # every logit 0: s = 0.5 everywhere, so s + b orders by the bias
    # alone: 6 (0.8), 0 (0.71), 5 (0.70), others 0.5, 1 (0.1)
    _, margin = ref._moe(blk, x, cfg)
    assert float(margin[0, 0]) == pytest.approx(0.01 / 2.0 ** -8, rel=1e-3)


# (e) the pool: two groups, one lifecycle
def test_pool_groups_rings_and_bytes(tiny):
    model, _ = tiny
    pool = PagedSlotPool(model, 3, 64, jnp.bfloat16, block_size=4,
                         prefix_cache=False)
    kvw = 2 * 16                                # KVH * D lanes
    assert pool.layer_groups == ("window",) * 3 + ("global", "window")
    assert (pool.window, pool.window_entries) == (8, 3)
    assert pool.num_blocks == 1 + 3 * 16 and pool.blocks_per_slot == 16
    for layer, group in zip(pool.caches, pool.layer_groups):
        n = pool.num_blocks if group == "global" else 1 + 3 * 3
        assert layer["k"].shape == layer["v"].shape == (n, 4, kvw)
    assert pool.bytes_per_block == 2 * 4 * kvw * 2          # one full layer
    assert pool.window_bytes_per_block == 4 * 2 * 4 * kvw * 2
    assert not pool.kv_wire
    assert sorted(pool.device_tables()) == ["global", "window"]
    # a ring is bound when the slot is taken, whole, and stays as it is
    a, b = pool.alloc(), pool.alloc()
    ring_a = pool.window_tables_host[a].copy()
    assert ring_a.all() and pool.window_blocks_used == 6
    assert not set(ring_a) & set(pool.window_tables_host[b])
    assert pool.blocks_used == 0 and pool.bytes_resident == 0
    pool.prepare_write(a, 0, 30)
    pool.prepare_write(a, 30, 31)
    assert (pool.window_tables_host[a] == ring_a).all()
    assert pool.blocks_used == 8 and pool.window_blocks_used == 6
    assert pool.window_bytes_resident == 6 * pool.window_bytes_per_block
    pool.release_blocks(a)                      # the growing group only
    assert pool.blocks_used == 0 and (pool.window_tables_host[a] == ring_a).all()
    pool.leak_check()
    pool.free(a)
    assert pool.window_blocks_used == 3 and not pool.window_tables_host[a].any()
    pool.leak_check()
    # the books notice a ring block that went missing
    pool.window_tables_host[b, 1] = 0
    with pytest.raises(AssertionError, match="ring"):
        pool.leak_check()


def test_a_model_without_window_layers_has_no_ring():
    from nezha_tpu.models.gpt2 import GPT2, GPT2Config
    model = GPT2(GPT2Config(vocab_size=64, max_positions=64, num_layers=2,
                            num_heads=4, hidden_size=32))
    pool = PagedSlotPool(model, 2, 64, jnp.bfloat16, block_size=16)
    assert pool.window is None and pool.window_entries == 0
    assert pool.layer_groups == ("global", "global")
    assert pool.window_tables_host.shape == (2, 0)
    assert sorted(pool.device_tables()) == ["global"]
    s = pool.alloc()
    assert pool.window_blocks_used == 0 and pool.window_bytes_resident == 0
    pool.free(s)
    pool.leak_check()


# refusals: typed, at start-up
@pytest.mark.parametrize("kw, match", [
    ({"prefix_cache": True}, "prefix_cache with window layers"),
    ({"kv_dtype": "int8"}, "grouped-query or window form"),
    ({"speculative": "on"}, "speculative"),
    ({"prefill_impl": "kernel"}, "no such knob"),
])
def test_typed_refusals(tiny, kw, match):
    model, variables = tiny
    if "speculative" in kw:
        from nezha_tpu.serve.engine import SpeculativeConfig
        kw = {"speculative": SpeculativeConfig(draft_k=2, draft_layers=1)}
    with pytest.raises(ValueError, match=match):
        _engine(model, variables, **kw)


def test_the_model_refuses_a_verify_window_and_a_cache_without_tables(tiny):
    model, variables = tiny
    rows = [{name: jnp.zeros((4,) + tuple(shape), dt)
             for name, (shape, dt) in leaves.items()}
            for _, _, leaves in model.cache_leaves(4, jnp.float32)]
    with pytest.raises(ValueError, match="block-paged only"):
        model.apply(variables, jnp.zeros((1, 4), jnp.int32), cache=rows,
                    pos=jnp.zeros((), jnp.int32))
    rows = [{**r, "tables": jnp.zeros((2, 3), jnp.int32)} for r in rows]
    with pytest.raises(ValueError, match="speculative"):
        model.apply(variables, jnp.zeros((2, 3), jnp.int32), cache=rows,
                    pos=jnp.zeros((2,), jnp.int32))


def test_cli_builds_the_stack_and_refuses_what_it_cannot_serve():
    from nezha_tpu.cli import serve as cli
    base = ["--model", "k_exaone", "--random-init", "--model-preset", "tiny",
            "--max-len", "64", "--max-batch-size", "2", "--max-prefill-len",
            "8", "--kv-block-size", "4", "--cache-dtype", "f32"]
    off = ["--prefix-cache", "off"]
    sched, _, _ = cli._build_stack(cli.build_parser().parse_args(base + off))
    assert type(sched.engine.model).__name__ == "ExaoneMoe"
    assert sched.engine.vocab == TINY_KW["vocab_held"]
    assert sched.engine.pool.window_entries == 3
    for extra, match in ((["--prefix-cache", "on"],
                          "serve engine: prefix_cache with window layers"),
                         (off + ["--mesh", "2"], "--mesh"),
                         (off + ["--kv-dtype", "int8"], "int8"),
                         (off + ["--speculative"], "--speculative"),
                         (off + ["--kv-host-blocks", "4"], "--kv-host-blocks"),
                         (off + ["--role", "prefill"], "KV migration")):
        with pytest.raises(SystemExit, match=match):
            cli._build_stack(cli.build_parser().parse_args(base + extra))
    with pytest.raises(SystemExit, match="--random-init only"):
        cli._build_stack(cli.build_parser().parse_args(
            ["--model", "k_exaone", "--ckpt-dir", "/nonexistent"] + off))


def test_scheduler_serves_requests_and_reports_both_groups(tiny, tmp_path):
    """Through the scheduler: requests finish, the gauges of both groups
    are set, the dispatch span carries both groups' blocks, nothing
    leaks."""
    from nezha_tpu import obs
    from nezha_tpu.serve import Request, Scheduler
    model, variables = tiny
    obs.start_run(str(tmp_path), meta={"kind": "serve_test"})
    try:
        eng = _engine(model, variables)
        seen = []
        real = eng._dispatch_attrs
        eng._dispatch_attrs = lambda a: seen.append(real(a)) or seen[-1]
        sched = Scheduler(eng)
        rng = np.random.default_rng(5)
        for i in range(5):
            sched.submit(Request(prompt=rng.integers(0, 512, 7 + 6 * i).tolist(),
                                 max_new_tokens=9, temperature=0.0,
                                 request_id=f"r{i}"))
        sched.step()
        assert obs.gauge("serve.kv.window_blocks_used").value == 9.0
        assert obs.gauge("serve.kv.bytes_resident").value == (
            eng.pool.bytes_resident + eng.pool.window_bytes_resident)
        sched.run_until_idle()
        assert seen and all(
            a["window_blocks"] <= 3 * a["rows"] <= a["blocks"] * 3
            for a in seen)
        assert any(a["window_blocks"] < a["blocks"] for a in seen)
        eng.pool.leak_check()
        assert eng.pool.window_blocks_used == 0
    finally:
        obs.end_run()
