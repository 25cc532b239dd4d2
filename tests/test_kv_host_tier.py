"""Tiered KV cache (ISSUE 15): host-memory spill of evicted int8
blocks with async promote-on-hit.

Covers the tier lifecycle end to end: demote→promote BIT-IDENTITY
(the int8 payload + per-(block, head) scales round-trip exactly — a
promoted block is byte-for-byte the block that was evicted, proven
against a never-evicted gather), token-level parity of a promoted
revisit against a cold engine, the host-LRU budget cap, a promotion
whose own allocations trigger concurrent eviction/demotion
(promote-racing-eviction), the ``serve.kv.promote`` fault point
(failed promote degrades to a cold prefill — typed, counted, nothing
surfaced to the request), a seeded chaos run with ZERO device and
host block leaks + schema-valid artifacts carrying the new pinned
instruments, the ``kv_eviction="none"``/bf16 refusal surface (those
pools are unchanged — the tier is int8 + lru + prefix-cache only),
the CLI/bench plumbing (``--kv-host-blocks`` parse, worker argv
passthrough, the churn record), and the nezha-bench ``kv_churn`` gate
rows.
"""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nezha_tpu import faults, obs
from nezha_tpu.models.gpt2 import GPT2, GPT2Config
from nezha_tpu.serve import (
    Engine,
    PagedSlotPool,
    Request,
    Scheduler,
    ServeConfig,
)
from nezha_tpu.serve.slots import _gather_blocks_quantized_jit

CFG = dict(vocab_size=97, max_positions=64, num_layers=2, num_heads=4,
           hidden_size=64)
# Host-tier serving shapes: block_size 4 + a small block budget so
# eviction (hence demotion) fires at test sizes, int8 blocks (the
# tier's storage precondition), a generous host budget.
HCFG = ServeConfig(max_batch_size=2, max_len=32, max_prefill_len=8,
                   prefill_buckets=(4, 8), k_max=16, queue_capacity=8,
                   cache_dtype=jnp.float32, kv_block_size=4,
                   kv_num_blocks=9, kv_dtype="int8", kv_host_blocks=16)

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for sub in ("tools", "benchmarks"):
    p = os.path.join(_ROOT, sub)
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture(scope="module")
def model_and_vars():
    model = GPT2(GPT2Config(**CFG))
    return model, model.init(jax.random.PRNGKey(0))


def _drain(sched, max_iters=400):
    sched.run_until_idle(max_iters=max_iters)
    assert not sched.has_work(), "scheduler did not drain"


def _gather_host(pool, blocks):
    """Block payloads as host arrays (the demote capture, done by
    hand): per-layer {k, v, k_scale, v_scale} for ``blocks``."""
    idx = jnp.asarray(np.asarray(blocks, np.int32))
    return [{k: np.asarray(v) for k, v in layer.items()}
            for layer in _gather_blocks_quantized_jit(pool.caches, idx)]


def _assert_payload_equal(a, b):
    assert len(a) == len(b)
    for la, lb in zip(a, b):
        assert set(la) == set(lb) == {"k", "v", "k_scale", "v_scale"}
        for key in la:
            np.testing.assert_array_equal(la[key], lb[key])


# -------------------------------------------------- config validation
def test_host_tier_config_validation():
    with pytest.raises(ValueError, match="kv_host_blocks"):
        ServeConfig(kv_host_blocks=-1)
    # int8-only: the demoted payload is the wire-format bytes verbatim.
    with pytest.raises(ValueError, match="int8"):
        ServeConfig(kv_host_blocks=8)
    with pytest.raises(TypeError, match="kv_layout"):
        ServeConfig(kv_layout="paged", kv_dtype="int8", kv_host_blocks=8)
    with pytest.raises(ValueError, match="prefix_cache"):
        ServeConfig(kv_dtype="int8", prefix_cache=False,
                    kv_host_blocks=8)
    with pytest.raises(ValueError, match="lru"):
        ServeConfig(kv_dtype="int8", kv_eviction="none",
                    kv_host_blocks=8)


def test_host_tier_pool_validation(model_and_vars):
    model, _ = model_and_vars
    with pytest.raises(ValueError, match="quantized"):
        PagedSlotPool(model, capacity=1, max_len=16,
                      block_size=4, host_blocks=4)
    with pytest.raises(ValueError, match="prefix_cache"):
        PagedSlotPool(model, capacity=1, max_len=16, block_size=4,
                      quantized=True, prefix_cache=False, host_blocks=4)


# ------------------------------------------------- demote -> promote
def test_demote_promote_bit_identity_and_token_parity(model_and_vars):
    """THE tier contract: a demoted block's int8 payload + scales come
    back bit-identical on promotion (compared against a gather taken
    BEFORE eviction), and the promoted revisit decodes token-for-token
    what a cold engine produces. The promote is observable: the
    revisit's device trie match is empty (its blocks were evicted),
    promotions fire, and the prefill shrinks to one tail chunk."""
    model, variables = model_and_vars
    eng = Engine(model, variables, HCFG)
    sched = Scheduler(eng)
    prompt_a = [(3 * i + 5) % 97 for i in range(10)]    # 2 full blocks
    a = sched.submit(Request(prompt=prompt_a, max_new_tokens=2,
                             request_id="a"))
    _drain(sched)
    cached = eng.pool.trie.match(prompt_a)
    assert len(cached) == 2
    before = _gather_host(eng.pool, cached)

    # Pressure: a 30-token prompt binds every usable block (span 32 =
    # 8 blocks), evicting — hence DEMOTING — both of A's cached blocks.
    b = sched.submit(Request(prompt=[(7 * i + 1) % 97 for i in range(30)],
                             max_new_tokens=2, request_id="b"))
    _drain(sched)
    assert eng.pool.trie.match(prompt_a) == []
    assert eng.pool.demotions >= 2
    assert eng.pool.host_blocks_used >= 2
    # The demoted entries ARE the pre-eviction bytes (keyed by the
    # full prefix path).
    entry1 = eng.pool._host_tier[tuple(prompt_a[:4])]
    entry2 = eng.pool._host_tier[tuple(prompt_a[:8])]
    _assert_payload_equal(
        [{k: v[:1] for k, v in layer.items()} for layer in before],
        entry1)
    _assert_payload_equal(
        [{k: v[1:2] for k, v in layer.items()} for layer in before],
        entry2)

    # Revisit: promote-on-hit. The tail differs (turn N+1), so only
    # the 8-position full-block prefix is served from the tier.
    obs_run = obs.counter("serve.kv.promotions_total").value
    prompt_a2 = prompt_a[:8] + [33, 44]
    a2 = sched.submit(Request(prompt=prompt_a2, max_new_tokens=2,
                              request_id="a2"))
    _drain(sched)
    assert eng.pool.promotions >= 2
    promoted = eng.pool.trie.match(prompt_a2)
    assert len(promoted) == 2
    _assert_payload_equal(before, _gather_host(eng.pool, promoted))
    # Exclusive move: the promoted entries left the host tier.
    assert tuple(prompt_a[:4]) not in eng.pool._host_tier
    assert tuple(prompt_a[:8]) not in eng.pool._host_tier
    eng.pool.leak_check()

    # Token parity vs a never-tiered cold engine.
    cold = Engine(model, variables, dataclasses.replace(
        HCFG, kv_host_blocks=0, prefix_cache=False))
    sc = Scheduler(cold)
    ref = sc.submit(Request(prompt=prompt_a2, max_new_tokens=2))
    _drain(sc)
    assert sched.results["a2"].tokens == sc.results[ref].tokens
    assert sched.results["a2"].finish_reason == "length"
    del a, b, a2, obs_run


def test_host_lru_budget_cap(model_and_vars):
    """The host budget is a hard cap: demotions past it drop the
    OLDEST entries (for good — there is no colder tier), occupancy and
    byte accounting stay consistent, and leak_check's host column
    passes throughout."""
    model, variables = model_and_vars
    eng = Engine(model, variables,
                 dataclasses.replace(HCFG, kv_host_blocks=2))
    sched = Scheduler(eng)
    prompts = [[(11 * u + 3 * i + 5) % 97 for i in range(10)]
               for u in range(3)]
    for u, p in enumerate(prompts):
        sched.submit(Request(prompt=p, max_new_tokens=2,
                             request_id=f"u{u}"))
        _drain(sched)
    # Keep evicting: a wide prompt flushes whatever is still cached.
    sched.submit(Request(prompt=[(7 * i + 2) % 97 for i in range(30)],
                         max_new_tokens=2))
    _drain(sched)
    pool = eng.pool
    assert pool.demotions > 2                  # more demoted than fits
    assert pool.host_blocks_used <= 2          # the cap held
    assert pool.host_bytes_resident == sum(
        pool._entry_bytes(e) for e in pool._host_tier.values())
    pool.leak_check()
    # Entries are dropped oldest-first: whatever remains was demoted
    # LAST (the wide prompt's own cached blocks, once evicted later,
    # or the youngest user's) — the first user's first block is gone.
    assert tuple(prompts[0][:4]) not in pool._host_tier


def test_promote_racing_concurrent_eviction(model_and_vars):
    """A promotion whose own allocations trigger eviction — hence
    demotion of OTHER entries mid-promote — must succeed with balanced
    books: the popped entries can't be raced away by the host LRU, the
    evicted third party lands in the tier, and the promoted content is
    still bit-identical."""
    model, _ = model_and_vars
    pool = PagedSlotPool(model, capacity=3, max_len=16,
                         dtype=jnp.float32, block_size=4, num_blocks=6,
                         quantized=True, host_blocks=8)
    t1 = [(3 * i + 1) % 97 for i in range(9)]      # 2 full blocks + 1
    t2 = [(5 * i + 2) % 97 for i in range(9)]      # 2 full blocks + 1
    t3 = [(7 * i + 3) % 97 for i in range(12)]     # 3 blocks
    s = pool.alloc()
    pool.bind_for_prompt(s, t1)
    pool.prepare_write(s, 0, 9)
    pool.register_prefix(s, t1)
    t1_bytes = _gather_host(pool, [int(b) for b in
                                   pool.tables_host[s, :2]])
    pool.free(s)                                   # t1 cached: 2 blocks
    s = pool.alloc()
    pool.bind_for_prompt(s, t2)
    pool.prepare_write(s, 0, 9)
    pool.register_prefix(s, t2)
    pool.free(s)                                   # t2 cached: 2 blocks
    # t3 binds 3: free list holds 1, so 2 LRU evictions DEMOTE t1's
    # chain; t3 stays LIVE so its blocks pin the pool.
    s3 = pool.alloc()
    pool.bind_for_prompt(s3, t3)
    pool.prepare_write(s3, 0, 12)
    assert pool.demotions == 2
    assert [b for b in pool.trie.match(t1)] == []
    # Revisit t1: promotion needs 2 blocks; free list is EMPTY and the
    # only reclaimable blocks are t2's cached pair — the promote's own
    # _alloc_block calls evict+demote them, racing the host tier the
    # promote is concurrently reading.
    s4 = pool.alloc()
    shared = pool.bind_for_prompt(s4, t1)
    assert shared == 8                       # 2 promoted full blocks
    assert pool.promotions == 2
    assert pool.demotions == 4               # t2's pair demoted DURING
    assert pool.trie.match(t2) == []
    assert tuple(t2[:4]) in pool._host_tier
    _assert_payload_equal(
        t1_bytes,
        _gather_host(pool, [int(b) for b in pool.tables_host[s4, :2]]))
    pool.leak_check()
    pool.free(s4)
    pool.free(s3)
    pool.leak_check()
    pool.clear_prefix_cache()
    assert pool.blocks_used == 0
    assert pool.clear_host_tier() > 0
    pool.leak_check()


def test_promote_never_exceeds_admission_budget_on_aligned_prompt(
        model_and_vars):
    """The admission-budget invariant: a promote-path prefill of a
    BLOCK-ALIGNED prompt (whose final block would COW immediately —
    the last token always re-runs) must allocate no more device blocks
    than the cold footprint the scheduler budgeted. The promote scan
    caps at (n-1)//bs, so the guaranteed-COW block re-prefills instead
    of being promoted-then-copied — and the request still succeeds on
    a pool at exactly the admission edge."""
    model, variables = model_and_vars
    eng = Engine(model, variables, HCFG)
    sched = Scheduler(eng)
    prompt = [(3 * i + 5) % 97 for i in range(8)]   # exactly 2 blocks
    sched.submit(Request(prompt=prompt, max_new_tokens=2))
    _drain(sched)
    sched.submit(Request(prompt=[(7 * i + 1) % 97 for i in range(30)],
                         max_new_tokens=2))
    _drain(sched)                        # prompt's blocks now host-only
    assert eng.pool.host_blocks_used >= 2
    need = eng.prefill_blocks_needed(len(prompt))
    used_before = eng.pool.blocks_used
    slot = eng.pool.alloc()
    eng.prefill(slot, prompt, max_new_tokens=2)
    # Only the promotable span (block 0) came back; block 1 — which
    # would have COWed — re-prefilled cold, keeping the allocation
    # within the admission budget.
    assert eng.pool.promotions == 1
    assert eng.pool.blocks_used - used_before <= need
    eng.pool.free(slot)
    eng.pool.leak_check()


def test_failed_promote_restore_reapplies_host_budget_cap(
        model_and_vars):
    """A promote that fails MID-allocation (after some allocs already
    evicted-and-demoted third-party blocks into a tier at budget) must
    restore its popped entries WITHOUT busting the hard cap: the LRU
    trim re-applies on the degrade path, leak_check's host column
    holds, and nothing on either tier leaks."""
    model, _ = model_and_vars
    pool = PagedSlotPool(model, capacity=3, max_len=16,
                         dtype=jnp.float32, block_size=4, num_blocks=6,
                         quantized=True, host_blocks=2)
    t1 = [(3 * i + 1) % 97 for i in range(9)]
    t2 = [(5 * i + 2) % 97 for i in range(9)]
    for toks in (t1, t2):
        s = pool.alloc()
        pool.bind_for_prompt(s, toks)
        pool.prepare_write(s, 0, 9)
        pool.register_prefix(s, toks)
        pool.free(s)
    # t3 live: binds 3, demoting t1's chain — tier now AT its cap of 2.
    s3 = pool.alloc()
    pool.bind_for_prompt(s3, [(7 * i + 3) % 97 for i in range(12)])
    pool.prepare_write(s3, 0, 12)
    assert pool.host_blocks_used == 2
    # Revisit t1: the promote pops both entries, its first alloc
    # demotes a t2 block into the tier, then the second alloc dies on
    # an injected bind fault — the restore path must trim back to cap.
    s4 = pool.alloc()
    try:
        faults.install(faults.FaultPlan.parse("serve.kv.bind:error@2"))
        assert pool.bind_for_prompt(s4, t1) == 0   # degraded: cold
    finally:
        faults.clear()
    assert pool.promote_failures == 1 and pool.promotions == 0
    assert pool.host_blocks_used <= 2
    pool.leak_check()
    pool.free(s4)
    pool.free(s3)
    pool.clear_prefix_cache()
    pool.clear_host_tier()
    pool.leak_check()
    assert pool.blocks_used == 0


# ------------------------------------------------------- fault point
def test_promote_fault_degrades_to_cold_prefill(model_and_vars):
    """The serve.kv.promote fault point: an injected promote failure
    DEGRADES the request to a cold prefill — served correctly, typed +
    counted (promote_failures, faults.injected_total), the demoted
    entries left resident for the next hit — and the next promote
    (fault exhausted) succeeds."""
    model, variables = model_and_vars
    eng = Engine(model, variables, HCFG)
    sched = Scheduler(eng)
    prompt = [(3 * i + 5) % 97 for i in range(10)]
    sched.submit(Request(prompt=prompt, max_new_tokens=2))
    _drain(sched)
    sched.submit(Request(prompt=[(7 * i + 1) % 97 for i in range(30)],
                         max_new_tokens=2))
    _drain(sched)                       # prompt's blocks now host-only
    assert eng.pool.host_blocks_used >= 2
    cold = Engine(model, variables, dataclasses.replace(
        HCFG, kv_host_blocks=0, prefix_cache=False))
    sc = Scheduler(cold)
    ref = sc.submit(Request(prompt=prompt, max_new_tokens=2))
    _drain(sc)
    try:
        faults.install(faults.FaultPlan.parse("serve.kv.promote:error@1"))
        r1 = sched.submit(Request(prompt=prompt, max_new_tokens=2,
                                  request_id="r1"))
        _drain(sched)
    finally:
        faults.clear()
    res = sched.results[r1]
    assert res.finish_reason == "length"        # served, not errored
    assert res.tokens == sc.results[ref].tokens
    assert eng.pool.promotions == 0
    assert eng.pool.promote_failures == 1
    # Degrade left the entries host-resident; the cold prefill then
    # re-registered the prefix on device, so the next identical
    # request is a DEVICE hit (no promote needed) — and the books
    # balance either way.
    assert tuple(prompt[:4]) in eng.pool._host_tier
    eng.pool.leak_check()


# ------------------------------------------------------------- chaos
def test_chaos_host_tier_zero_leaks(model_and_vars, tmp_path):
    """Seeded chaos over churning templated traffic with the host tier
    in play: prefill errors + NaN bursts + kv.bind failures + promote
    failures. Every request gets exactly one result, the device books
    balance AND the host column holds (zero leaks on both tiers), the
    program set stays frozen (promotion adds none), and the artifacts
    pass the pinned schema including the new serve.kv.* instruments;
    the report renders the host-tier segment."""
    model, variables = model_and_vars
    run_dir = str(tmp_path / "chaos_host_tier")
    obs.start_run(run_dir, meta={"kind": "chaos_host_tier"})
    try:
        cfg = dataclasses.replace(HCFG, queue_capacity=32)
        eng = Engine(model, variables, cfg)
        sched = Scheduler(eng)
        faults.install(faults.FaultPlan.parse(
            "serve.prefill:error%0.08;serve.step.logits:nan%0.05;"
            "serve.kv.bind:error%0.02;serve.kv.promote:error%0.3",
            seed=11))
        try:
            users = [[(13 * u + 3 * i + 5) % 97 for i in range(10)]
                     for u in range(4)]
            rids = []
            for i in range(20):
                prompt = (users[i % 4][:8] + [i % 97, (2 * i) % 97]
                          if i >= 4 else users[i % 4])
                rids.append(sched.submit(Request(
                    prompt=prompt, max_new_tokens=4,
                    temperature=0.8 if i % 3 == 0 else 0.0,
                    top_k=10 if i % 3 == 0 else None, seed=i,
                    request_id=f"c{i}")))
            _drain(sched)
        finally:
            faults.clear()
        assert set(rids) <= set(sched.results)
        reasons = {sched.results[r].finish_reason for r in rids}
        assert reasons <= {"length", "error"}
        assert eng.pool.demotions > 0          # the tier actually churned
        # Zero slot leaks, zero DEVICE block leaks, zero HOST leaks
        # (budget + byte books + geometry), frozen programs.
        assert eng.pool.num_free == cfg.max_batch_size
        eng.pool.leak_check()
        stats = eng.compile_stats()
        assert stats["entries"] == stats["misses"] == \
            1 + len(cfg.prefill_buckets)
        eng.pool.clear_prefix_cache()
        eng.pool.clear_host_tier()
        eng.pool.leak_check()
        assert eng.pool.blocks_used == 0
        assert eng.pool.host_blocks_used == 0
    finally:
        obs.end_run()
    from check_telemetry_schema import check_run_dir
    assert check_run_dir(run_dir) == []
    with open(os.path.join(run_dir, "summary.json")) as f:
        summary = json.load(f)
    assert summary["counters"]["serve.kv.demotions_total"] > 0
    assert "serve.kv.promotions_total" in summary["counters"]
    assert "serve.kv.host_blocks_used" in summary["gauges"]
    assert "serve.kv.host_bytes_resident" in summary["gauges"]
    # Dropping a host-tier instrument must FAIL the pinned schema.
    del summary["counters"]["serve.kv.demotions_total"]
    with open(os.path.join(run_dir, "summary.json"), "w") as f:
        json.dump(summary, f)
    assert any("serve.kv.demotions_total" in e
               for e in check_run_dir(run_dir))
    summary["counters"]["serve.kv.demotions_total"] = 3
    with open(os.path.join(run_dir, "summary.json"), "w") as f:
        json.dump(summary, f)
    from nezha_tpu.obs.report import render_report
    report = render_report(run_dir)
    assert "kv host tier:" in report and "demoted" in report


# --------------------------------------- unchanged-behavior surfaces
def test_no_host_tier_and_bf16_pools_unchanged(model_and_vars):
    """kv_host_blocks=0 (the default) and bf16 pools behave exactly as
    before: no demotions ever, eviction discards, the host gauges and
    ledgers read 0 — and kv_eviction='none' still surfaces typed
    backpressure with an inert tier surface."""
    model, variables = model_and_vars
    for cfg in (dataclasses.replace(HCFG, kv_host_blocks=0),
                dataclasses.replace(HCFG, kv_host_blocks=0,
                                    kv_dtype="bf16"),
                dataclasses.replace(HCFG, kv_host_blocks=0,
                                    kv_dtype="bf16",
                                    kv_eviction="none")):
        eng = Engine(model, variables, cfg)
        sched = Scheduler(eng)
        sched.submit(Request(prompt=[(3 * i + 5) % 97
                                     for i in range(10)],
                             max_new_tokens=2))
        _drain(sched)
        sched.submit(Request(prompt=[(7 * i + 1) % 97
                                     for i in range(30)],
                             max_new_tokens=2))
        _drain(sched)
        assert eng.pool.demotions == 0
        assert eng.pool.promotions == 0
        assert eng.pool.host_blocks_used == 0
        assert eng.pool.host_bytes_resident == 0
        eng.pool.leak_check()


# ------------------------------------------------- CLI + bench surface
def test_serve_cli_host_blocks_plumbing():
    """--kv-host-blocks parses, flows into the worker argv (the
    --replicas passthrough), and build_parser defaults it off."""
    from nezha_tpu.cli.serve import _worker_argv, build_parser

    args = build_parser().parse_args(
        ["--random-init", "--kv-dtype", "int8",
         "--kv-host-blocks", "48"])
    assert args.kv_host_blocks == 48
    argv = _worker_argv(args, rid=0, port=9999)
    assert argv[argv.index("--kv-host-blocks") + 1] == "48"
    assert build_parser().parse_args(
        ["--random-init"]).kv_host_blocks == 0


def test_serving_benchmark_kv_churn_record(model_and_vars):
    """benchmarks/serving.py --churn-users + --kv-host-blocks: the
    churn record carries the first-visit/revisit TTFT split and the
    demote/promote ledgers, promotions actually fire (the pool is
    sized so users' blocks cycle between visits), and the kv block
    reports the host-tier fields."""
    import serving as bench

    rec = bench.run(bench.build_parser().parse_args(
        ["--requests", "12", "--concurrency", "1",
         "--churn-users", "4", "--churn-prefix-len", "16",
         "--kv-block-size", "4", "--kv-dtype", "int8",
         "--kv-host-blocks", "32", "--max-batch-size", "2",
         "--max-len", "24", "--max-prefill-len", "8",
         "--kv-num-blocks", "13", "--max-new-tokens", "4",
         "--sample-fraction", "0"]))
    assert rec["finished"] == 12
    ch = rec["kv_churn"]
    assert ch["users"] == 4 and ch["prefix_len"] == 16
    assert ch["demotions"] > 0 and ch["promotions"] > 0
    assert ch["ttft_first_visit_s"]["p50"] > 0
    assert ch["ttft_revisit_s"]["p50"] > 0
    assert ch["revisit_vs_first_ttft_p50"] > 0
    kv = rec["kv"]
    assert kv["host_blocks"] == 32
    assert kv["demotions"] == ch["demotions"]
    assert kv["promotions"] == ch["promotions"]
    assert kv["peak_host_blocks_used"] > 0
    # Churn prefixes must be block-aligned — a misaligned length is a
    # typed refusal, not silent partial caching.
    with pytest.raises(SystemExit, match="multiple"):
        bench.run(bench.build_parser().parse_args(
            ["--churn-users", "2", "--churn-prefix-len", "10",
             "--kv-block-size", "4", "--kv-dtype", "int8"]))


def test_nezha_bench_kv_churn_gate_rows():
    """The kv_churn gate logic (no model run — cooked results): the
    promote-vs-cold ratio is a HARD gate at 0.5, promotions must be
    nonzero, and a committed baseline adds a drift gate."""
    from nezha_tpu.cli import bench as nb

    good = {"kv_churn": {"promote_vs_cold_ttft_p50": 0.38,
                         "promotions": 72}}
    rows = nb._gate(good, {}, "cpu", 0.30)["serving"]
    assert rows["kv_churn.promote_vs_cold_ttft_p50"]["ok"]
    assert rows["kv_churn.promotions"]["ok"]

    bad = {"kv_churn": {"promote_vs_cold_ttft_p50": 0.8,
                        "promotions": 0}}
    rows = nb._gate(bad, {}, "cpu", 0.30)["serving"]
    assert not rows["kv_churn.promote_vs_cold_ttft_p50"]["ok"]
    assert not rows["kv_churn.promotions"]["ok"]

    base = {"by_platform": {"cpu": {
        "kv_churn": {"promote_vs_cold_ttft_p50": 0.30}}}}
    rows = nb._gate(good, {"serving": base}, "cpu", 0.30)["serving"]
    drift = rows["kv_churn.promote_vs_cold_ttft_p50_vs_baseline"]
    assert drift["ok"]                      # 0.38/0.30 = 1.27 <= 1.30
    rows = nb._gate(good, {"serving": base}, "cpu", 0.10)["serving"]
    assert not rows[
        "kv_churn.promote_vs_cold_ttft_p50_vs_baseline"]["ok"]
