"""Sequence-sharded prefill (ISSUE 20): ``prefill_mode="sequence"``
spreads each prefill chunk's attention over the serve mesh's ``tp``
axis (ulysses all-to-all by default, ``lax.ppermute`` ring hops as the
variant — serve/sharded/seq_prefill.py), landing finished blocks in
the same head-sharded paged pool so decode proceeds unchanged.

Pins, per the acceptance list:

- greedy tokens BIT-IDENTICAL to the single-device engine at mesh 2
  across the parity suites: float and int8 pools, ulysses AND ring,
  chunked long prompts through the new ``long_prefill_buckets``,
  shared-prefix partial prefills, speculative decode riding along;
- the frozen program contract re-pinned as ``1 step +
  len(all_prefill_buckets)`` with misses FROZEN after warmup — long
  buckets widen the compiled set deliberately, sequence mode adds
  nothing on top;
- the greedy largest-fit chunk planner: pad-up long tails, big-stride
  long chunks, and EXACT reduction to the classic plan when
  ``long_prefill_buckets=()``;
- config/CLI validation is typed and early (mode and variant names,
  long-bucket monotonicity and range, bucket divisibility by the mesh,
  the single-device refusal);
- the ``serve.prefill.seq`` chaos point: an injected error retires
  ONLY the victim request with zero slot/block/scale leaks per shard;
- the telemetry (``serve.prefill.seq_shards`` gauge,
  ``serve.prefill.ring_hops_total`` counter, ``serve.prefill.seq_s``
  span, the report's ``seq xM`` mode label) is captured schema-clean
  and schema-PINNED (dropping an instrument fails the check).
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import pytest

from nezha_tpu import faults, obs
from nezha_tpu.faults import FaultPlan
from nezha_tpu.models.gpt2 import GPT2, GPT2Config
from nezha_tpu.serve import Engine, Request, Scheduler, ServeConfig
from nezha_tpu.serve.engine import SpeculativeConfig
from nezha_tpu.serve.sharded import ShardedEngine

CFG = dict(vocab_size=64, max_positions=64, num_layers=2, num_heads=4,
           hidden_size=32)
SCFG = ServeConfig(max_batch_size=3, max_len=32, max_prefill_len=8,
                   prefill_buckets=(4, 8), k_max=16, queue_capacity=8,
                   cache_dtype=jnp.float32)
# Long-context shape (scaled down): two long buckets above
# max_prefill_len, the 8k/32k document story at test sizes.
LCFG = ServeConfig(max_batch_size=2, max_len=64, max_prefill_len=8,
                   prefill_buckets=(4, 8), long_prefill_buckets=(16, 32),
                   k_max=16, queue_capacity=8, cache_dtype=jnp.float32)
PROMPTS = [[3, 5, 7, 9], [11, 2, 4], [1, 2, 3, 4, 5, 6, 7, 8, 9]]
# Warm every bucket of LCFG.all_prefill_buckets (4, 8, 16, 32): 27
# pads up to 32, 17 to 32, 12 to 16, 3 to 4, 7 to 8.
LONG_PROMPTS = [list(range(1, 28)), list(range(3, 20)),
                list(range(2, 14)), [5, 6, 7], [1] * 7]


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    faults.clear()
    yield
    faults.clear()


@pytest.fixture(scope="module")
def model_and_vars():
    model = GPT2(GPT2Config(**CFG))
    return model, model.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def ref_tokens(model_and_vars):
    """Single-device greedy reference for the shared SCFG/PROMPTS."""
    model, variables = model_and_vars
    return _greedy(Engine(model, variables, SCFG), PROMPTS)


@pytest.fixture(scope="module")
def ref8_tokens(model_and_vars):
    """Single-device int8-pool reference, shared by both seq variants."""
    model, variables = model_and_vars
    i8 = dataclasses.replace(SCFG, kv_dtype="int8")
    return _greedy(Engine(model, variables, i8), PROMPTS)


def _greedy(engine, prompts, max_new=6):
    sched = Scheduler(engine)
    for i, p in enumerate(prompts):
        sched.submit(Request(prompt=list(p), max_new_tokens=max_new,
                             request_id=f"r{i}"))
    sched.run_until_idle(max_iters=400)
    assert not sched.has_work()
    return {k: v.tokens for k, v in sched.results.items()}


def _seq(cfg, **kw):
    return dataclasses.replace(cfg, prefill_mode="sequence", **kw)


# ----------------------------------------------------- parity + contract
def test_seq_ulysses_greedy_parity_bit_identical(model_and_vars,
                                                 ref_tokens):
    """The headline gate: sequence-sharded prefill at mesh 2 (auto →
    ulysses, the bitwise layout — each shard runs the EXACT replicated
    computation on its H/M heads after the all-to-all reshard) emits
    exactly the single-device engine's tokens."""
    model, variables = model_and_vars
    eng = ShardedEngine(model, variables, _seq(SCFG), mesh_devices=2)
    assert eng._seq_active and eng._seq_variant == "ulysses"
    got = _greedy(eng, PROMPTS)
    assert got == ref_tokens
    assert all(v for v in ref_tokens.values())
    # Frozen program contract, sequence mode included: 1 step +
    # len(all_prefill_buckets) entries, misses frozen after warmup.
    stats = eng.compile_stats()
    assert stats["entries"] == 1 + len(SCFG.all_prefill_buckets)
    misses0 = stats["misses"]
    _greedy(eng, [[7, 7, 7], [9] * 7])
    after = eng.compile_stats()
    assert after["entries"] == 1 + len(SCFG.all_prefill_buckets)
    assert after["misses"] == misses0, "seq-mode dispatch recompiled"


def test_seq_ring_greedy_parity(model_and_vars, ref_tokens):
    """The ppermute ring variant (queries + zero out-buffers circulate,
    one flash-kernel call per hop via ``q_offsets``) holds greedy
    parity with the single-device engine on float pools."""
    model, variables = model_and_vars
    eng = ShardedEngine(model, variables,
                        _seq(SCFG, seq_prefill_variant="ring"),
                        mesh_devices=2)
    assert eng._seq_variant == "ring"
    assert _greedy(eng, PROMPTS) == ref_tokens


@pytest.mark.parametrize("variant", ["auto", "ring"])
def test_seq_int8_parity_and_no_leaks(model_and_vars, ref8_tokens,
                                      variant):
    """int8 pools under sequence sharding: the fused epilogue write
    still lands per head shard, greedy tokens match the single-device
    int8 engine, and the per-shard books balance after drain."""
    model, variables = model_and_vars
    i8 = dataclasses.replace(SCFG, kv_dtype="int8")
    eng = ShardedEngine(model, variables,
                        _seq(i8, seq_prefill_variant=variant),
                        mesh_devices=2)
    assert _greedy(eng, PROMPTS) == ref8_tokens
    eng.pool.leak_check()
    assert eng.pool.bytes_resident_per_shard == 0


def test_long_bucket_parity_and_contract(model_and_vars):
    """``long_prefill_buckets``: document-length prompts prefill in a
    handful of wide sequence-sharded dispatches, bit-identical to the
    single-device engine running the SAME widened plan, and the
    program count grows to exactly ``1 + len(all_prefill_buckets)``
    once every bucket is warm."""
    model, variables = model_and_vars
    ref = _greedy(Engine(model, variables, LCFG), LONG_PROMPTS)
    eng = ShardedEngine(model, variables, _seq(LCFG), mesh_devices=2)
    assert _greedy(eng, LONG_PROMPTS) == ref
    stats = eng.compile_stats()
    assert stats["entries"] == 1 + len(LCFG.all_prefill_buckets)
    assert LCFG.all_prefill_buckets == (4, 8, 16, 32)


def test_seq_shared_prefix_parity(model_and_vars):
    """Shared-prefix partial prefill composes: the repeated prompt
    takes a prefix hit (nonzero chunk start into the seq-sharded
    program) and tokens stay bit-identical to the single-device
    engine under the same serial traffic."""
    model, variables = model_and_vars
    long = [5, 17, 3, 9, 11, 2, 7, 23, 41, 8, 1, 13,
            6, 30, 44, 29, 10, 50, 33, 2]
    prompts = [long, [1, 2, 3], long]    # 3rd = prefix hit

    def serial(engine):
        sched = Scheduler(engine)
        outs = []
        for i, p in enumerate(prompts):
            rid = sched.submit(Request(prompt=list(p),
                                       max_new_tokens=6,
                                       request_id=f"r{i}"))
            sched.run_until_idle(max_iters=400)
            outs.append(list(sched.results[rid].tokens))
        return outs

    cfg = dataclasses.replace(LCFG, kv_block_size=4)
    ref = serial(Engine(model, variables, cfg))
    eng = ShardedEngine(model, variables, _seq(cfg), mesh_devices=2)
    got = serial(eng)
    assert got == ref
    assert eng.pool.prefix_hits >= 1


def test_seq_speculative_parity(model_and_vars):
    """Speculative decode rides along: the draft engine's bucket
    programs route through the same seq-prefill hook, accepted/bonus
    tokens bit-identical to the single-device speculative engine."""
    model, variables = model_and_vars
    spec = dataclasses.replace(
        SCFG, speculative=SpeculativeConfig(draft_k=2, draft_layers=1))
    ref = _greedy(Engine(model, variables, spec), PROMPTS)
    got = _greedy(ShardedEngine(model, variables, _seq(spec),
                                mesh_devices=2), PROMPTS)
    assert got == ref


# ------------------------------------------------------- chunk planner
def test_plan_chunks_long_buckets_and_classic_reduction(model_and_vars):
    """The greedy largest-fit planner: pad-up long tails (27 → one
    32-wide dispatch, never 3×8+4), big strides (33 → 32 + 4-tail),
    and EXACT reduction to the classic stride-then-tail plan when
    ``long_prefill_buckets=()``."""
    model, variables = model_and_vars
    eng = Engine(model, variables, LCFG)
    assert eng._plan_chunks(27) == [(0, 27, 32)]
    assert eng._plan_chunks(12) == [(0, 12, 16)]
    assert eng._plan_chunks(33) == [(0, 32, 32), (32, 1, 4)]
    assert eng._plan_chunks(64) == [(0, 32, 32), (32, 32, 32)]
    assert eng.bucket_for(3) == 4 and eng.bucket_for(7) == 8
    classic = Engine(model, variables, dataclasses.replace(
        LCFG, long_prefill_buckets=()))
    assert classic._plan_chunks(27) == [(0, 8, 8), (8, 8, 8),
                                        (16, 8, 8), (24, 3, 4)]
    assert classic._plan_chunks(12) == [(0, 8, 8), (8, 4, 4)]
    assert classic._plan_chunks(3) == [(0, 3, 4)]


# ------------------------------------------------------- validation
def test_single_device_engine_rejects_sequence_mode(model_and_vars):
    model, variables = model_and_vars
    with pytest.raises(ValueError, match="mesh"):
        Engine(model, variables, _seq(SCFG))


def test_sharded_engine_rejects_indivisible_bucket(model_and_vars):
    model, variables = model_and_vars
    bad = _seq(SCFG, prefill_buckets=(3, 8))
    with pytest.raises(ValueError, match="divisible"):
        ShardedEngine(model, variables, bad, mesh_devices=2)


def test_serve_config_validates_seq_knobs():
    with pytest.raises(ValueError, match="prefill_mode"):
        ServeConfig(prefill_mode="ring")
    with pytest.raises(ValueError, match="seq_prefill_variant"):
        ServeConfig(seq_prefill_variant="deepspeed")
    with pytest.raises(ValueError, match="strictly increasing"):
        dataclasses.replace(LCFG, long_prefill_buckets=(32, 16))
    with pytest.raises(ValueError, match="max_prefill_len"):
        dataclasses.replace(LCFG, long_prefill_buckets=(8, 16))
    with pytest.raises(ValueError, match="max_prefill_len"):
        dataclasses.replace(LCFG, long_prefill_buckets=(16, 128))


def test_cli_rejects_sequence_without_mesh(capsys):
    """``nezha-serve --prefill-mode sequence`` without ``--mesh M>1``
    is a typed SystemExit at argv time, before any engine builds."""
    from nezha_tpu.cli.serve import _build_stack, build_parser
    args = build_parser().parse_args(
        ["--random-init", "--model-preset", "tiny",
         "--prefill-mode", "sequence", "--platform", "cpu"])
    with pytest.raises(SystemExit, match="--mesh"):
        _build_stack(args)


# ----------------------------------------------------- chaos + telemetry
def test_chaos_seq_prefill_victim_only_zero_leaks(model_and_vars):
    """The pinned ``serve.prefill.seq`` chaos point: a seeded error at
    the sequence-prefill entry retires ONLY the victim request
    (typed ``error`` finish), everyone else completes, and the
    per-shard books (slots, blocks, int8 scale shapes) balance."""
    model, variables = model_and_vars
    cfg = _seq(dataclasses.replace(SCFG, queue_capacity=16,
                                   kv_dtype="int8"))
    eng = ShardedEngine(model, variables, cfg, mesh_devices=2)
    sched = Scheduler(eng)
    faults.install(FaultPlan.parse("serve.prefill.seq:error@2", seed=7))
    for i in range(8):
        sched.submit(Request(prompt=[(3 + 5 * i) % 64, 2, 9],
                             max_new_tokens=4, request_id=f"c{i}",
                             seed=i))
    sched.run_until_idle(max_iters=600)
    faults.clear()
    assert not sched.has_work()
    assert len(sched.results) == 8
    reasons = [r.finish_reason for r in sched.results.values()]
    assert set(reasons) <= {"length", "error", "eos"}
    assert reasons.count("error") == 1      # the victim, nobody else
    assert eng.pool.num_free == cfg.max_batch_size
    eng.pool.leak_check()
    assert eng.pool.bytes_resident_per_shard == 0


def test_seq_telemetry_capture_and_report(model_and_vars, tmp_path):
    """A sequence-mode ring run captures schema-clean with the PR's
    instruments live — ``serve.prefill.seq_shards`` = mesh size,
    nonzero ``serve.prefill.ring_hops_total``, ``serve.prefill.seq_s``
    spans — and the report's prefill line carries the ``seq x2`` mode
    label plus the ring-hop count. Dropping an instrument FAILS the
    pinned schema."""
    from nezha_tpu.analysis.telemetry_schema import check_run_dir
    model, variables = model_and_vars
    run_dir = str(tmp_path / "run_seq")
    obs.start_run(run_dir, meta={"kind": "seq_prefill_test"})
    try:
        eng = ShardedEngine(model, variables,
                            _seq(SCFG, seq_prefill_variant="ring"),
                            mesh_devices=2)
        _greedy(eng, PROMPTS[:2])
    finally:
        obs.end_run()
    assert check_run_dir(run_dir) == []
    with open(os.path.join(run_dir, "summary.json")) as f:
        summary = json.load(f)
    assert summary["gauges"]["serve.prefill.seq_shards"] == 2
    assert summary["counters"]["serve.prefill.ring_hops_total"] > 0
    with open(os.path.join(run_dir, "spans.jsonl")) as f:
        span_names = {json.loads(ln)["name"] for ln in f if ln.strip()}
    assert "serve.prefill.seq_s" in span_names
    from nezha_tpu.analysis.telemetry_schema import PINNED_SPANS
    assert "serve.prefill.seq_s" in PINNED_SPANS
    from nezha_tpu.obs.report import render_report
    report = render_report(run_dir)
    assert "prefill[xla, seq x2]:" in report
    assert "ring hops" in report
    del summary["gauges"]["serve.prefill.seq_shards"]
    with open(os.path.join(run_dir, "summary.json"), "w") as f:
        json.dump(summary, f)
    assert any("serve.prefill.seq_shards" in e
               for e in check_run_dir(run_dir))
