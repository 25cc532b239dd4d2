"""Speculative decoding (ISSUE 13): draft→verify→accept inside the
device-resident horizon scan.

Covers the parity gates (greedy outputs bit-identical speculative vs
classic with the prefix trie on and off, h=1 and h=8, chunked prefill
included; the lossless rejection-sampling law on the sampling kernels; sampled spec
outputs horizon-invariant), the on-device completion semantics (EOS
inside an accepted prefix freezes the row mid-window — overshoot never
reaches the client), the frozen TWO-ENGINE program-count contract
(target: 1 step + len(prefill_buckets); draft: len(prefill_buckets) —
the draft's decode lives inside the one fused step program), the
mirrored draft-pool slot lifecycle (lockstep alloc/free, leak_check
drift oracle), the pinned ``serve.spec.verify`` fault point (NaN
retires only the victim; an error rule rides the bounded-retry
envelope), the seeded chaos acceptance with zero slot/block leaks in
BOTH pools, the schema-pinned ``serve.spec.*`` instruments + report
line, and the benchmark's ``spec{...}`` record block.
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
from nezha_tpu.models.generate import generate
from nezha_tpu.models.gpt2 import GPT2, GPT2Config
from nezha_tpu.serve import Engine, Request, Scheduler, ServeConfig
from nezha_tpu.serve.engine import SpeculativeConfig, self_draft
from nezha_tpu.serve.sampling import accept_mask, residual_logits
from nezha_tpu.serve.slots import PagedSlotPool

CFG = dict(vocab_size=97, max_positions=64, num_layers=2, num_heads=4,
           hidden_size=64)
SCFG = ServeConfig(max_batch_size=3, max_len=48, max_prefill_len=8,
                   prefill_buckets=(4, 8), k_max=16, queue_capacity=16,
                   cache_dtype=jnp.float32, kv_block_size=4)
SPEC = SpeculativeConfig(draft_k=2, draft_layers=1)

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


def _requests():
    """A mixed load: short/bucketed/chunked prompts, greedy and
    sampled rows (prompt 13 > max_prefill_len=8 -> chunked)."""
    return [
        Request(prompt=[5, 17, 3, 42], max_new_tokens=8,
                request_id="g0"),
        Request(prompt=[7, 7], max_new_tokens=7, temperature=0.9,
                top_k=10, seed=7, request_id="s0"),
        Request(prompt=[(3 * i + 2) % 97 for i in range(13)],
                max_new_tokens=6, request_id="g1"),
        Request(prompt=[11, 4, 9, 2, 8, 1], max_new_tokens=8,
                temperature=0.7, top_k=12, seed=3, request_id="s1"),
    ]


def _run(model, variables, cfg):
    eng = Engine(model, variables, cfg)
    sched = Scheduler(eng)
    for r in _requests():
        sched.submit(r)
    _drain(sched)
    return eng, {k: (v.tokens, v.finish_reason)
                 for k, v in sched.results.items()}


# ------------------------------------------------------------ parity
@pytest.mark.parametrize("prefix_cache", [True, False],
                         ids=["trie", "no-trie"])
def test_greedy_parity_spec_vs_classic_bit_identical(model_and_vars,
                                                     prefix_cache):
    """The ISSUE 13 parity gate: with speculative ON every request's
    output (greedy AND sampled-within-spec across horizons) matches —
    greedy rows bit-identical to the CLASSIC engine and to one-shot
    generate(), at h=1 and h=8, chunked prompts included. Every
    accepted draft token is verified against the target, so the draft
    (a 1-layer early-exit) can only change speed, never tokens. The
    draft pool never has a prefix trie; the target's may."""
    model, variables = model_and_vars
    outs = {}
    for h in (1, 8):
        base = dataclasses.replace(SCFG, prefix_cache=prefix_cache,
                                   decode_horizon=h)
        _, classic = _run(model, variables, base)
        eng, spec = _run(model, variables,
                         dataclasses.replace(base, speculative=SPEC))
        # Greedy rows: bit-identical to classic, reason and all.
        for rid in ("g0", "g1"):
            assert spec[rid] == classic[rid], (prefix_cache, h, rid)
        # The speculation actually ran and accepted draft tokens.
        assert eng.spec_verifies > 0
        assert eng.spec_accepted > 0
        outs[h] = spec
    # Spec outputs (sampled rows included) are horizon-invariant.
    assert outs[1] == outs[8]
    ref = np.asarray(generate(
        model, variables, np.asarray([[5, 17, 3, 42]], np.int32),
        max_new_tokens=8, temperature=0.0,
        cache_dtype=jnp.float32))[0, 4:]
    assert outs[8]["g0"][0] == ref.tolist()


def test_rejection_sampling_law_monte_carlo():
    """The lossless-speculative-sampling pin on the kernels themselves:
    draw d ~ q, accept when u·q(d) <= p(d), else resample from
    ``residual_logits(p, q)`` — the emitted marginal must equal p
    EXACTLY (checked empirically to Monte Carlo noise). This is the
    distribution-invariance half of the parity gate: greedy rows are
    pinned bit-identical above; sampled rows are pinned lawful here."""
    v, n = 8, 200_000
    key = jax.random.PRNGKey(0)
    kp, kq, kd, ku, kr = jax.random.split(key, 5)
    p = jax.nn.softmax(jax.random.normal(kp, (v,)) * 1.5)
    q = jax.nn.softmax(jax.random.normal(kq, (v,)) * 1.5)
    d = jax.random.categorical(kd, jnp.log(q), shape=(n,))
    u = jax.random.uniform(ku, (n,))
    acc = accept_mask(
        d[:, None], jnp.broadcast_to(p, (n, 1, v)),
        jnp.broadcast_to(q, (n, 1, v)), u[:, None],
        jnp.zeros((n,), bool), jnp.zeros((n, 1), jnp.int32))[:, 0]
    res = jax.random.categorical(
        kr, jnp.broadcast_to(residual_logits(p[None, :], q[None, :]),
                             (n, v)), axis=-1)
    emitted = jnp.where(acc, d, res)
    emp = jnp.bincount(emitted, length=v) / n
    tv = 0.5 * float(jnp.abs(emp - p).sum())
    assert tv < 0.01, f"total variation {tv} vs target p"
    # Sanity: the test is discriminating — q itself is far from p.
    assert 0.5 * float(jnp.abs(q - p).sum()) > 0.05
    # Boundary regression: jax.random.uniform can return EXACTLY 0; a
    # draft token the target assigns zero probability must still be
    # rejected (u·q < p is strict — `<=` would emit a token classic
    # sampling never could).
    p0 = jnp.array([[[0.0, 1.0]]])          # target: token 0 impossible
    q0 = jnp.array([[[1.0, 0.0]]])          # draft proposes token 0
    acc0 = accept_mask(jnp.array([[0]]), p0, q0, jnp.array([[0.0]]),
                       jnp.zeros((1,), bool), jnp.zeros((1, 1),
                                                        jnp.int32))
    assert not bool(acc0[0, 0])


def test_sampled_rejections_survive_bf16_and_health_tripwire(
        model_and_vars):
    """Regression (found driving the real server): after a REJECTION
    the carried residual log-probs hold floor values for zero-mass
    entries — the floor must stay a NORMAL fp32 number, because XLA's
    CPU backend flushes denormals to zero and ``log(0) = -inf`` would
    trip the carried-logits health check, retiring a healthy sampled
    row as 'non-finite logits'. A shallow draft at bf16 cache dtype
    (the CLI default) forces rejections; the request must finish
    LENGTH, never ERROR, and keep its residual logits finite."""
    model, variables = model_and_vars
    cfg = dataclasses.replace(SCFG, cache_dtype=jnp.bfloat16,
                              speculative=SPEC)
    eng = Engine(model, variables, cfg)
    sched = Scheduler(eng)
    rid = sched.submit(Request(prompt=[7, 7, 9], max_new_tokens=10,
                               temperature=0.8, top_k=40, seed=7))
    _drain(sched)
    res = sched.results[rid]
    assert res.finish_reason == "length", res.error
    assert len(res.tokens) == 10
    # The machinery genuinely rejected along the way (the residual
    # path fired), and the carried logits stayed finite through it.
    assert eng.spec_accepted < eng.spec_verifies * SPEC.draft_k
    assert bool(np.isfinite(np.asarray(eng.last_logits)).all())


# ------------------------------------------- on-device completion
def test_eos_inside_accepted_prefix_freezes_row(model_and_vars):
    """An EOS landing INSIDE the accepted prefix of a verify window
    cuts emission at the EOS on device: emitted stops there, the cache
    position freezes (no K/V appended past it), the window's overshoot
    columns are pad — and the client sees tokens ending exactly at the
    EOS. The draft is the full-depth identity (accept rate ~1), so the
    cut is the EOS mask, not a rejection."""
    model, variables = model_and_vars
    spec = SpeculativeConfig(draft_k=5, draft_layers=None)
    cfg = dataclasses.replace(SCFG, speculative=spec)
    kw = dict(prompt=[5, 17, 3, 42], max_new_tokens=6, temperature=0.9,
              top_k=10, seed=7)
    eng = Engine(model, variables, cfg)
    sched = Scheduler(eng)
    probe = sched.submit(Request(**kw))
    _drain(sched)
    seq = sched.results[probe].tokens
    stop = next(i for i in range(1, len(seq)) if seq[i] not in seq[:i])
    eos, ref = seq[stop], seq[:stop + 1]
    assert 1 <= stop < 5          # genuinely inside the first window

    eng2 = Engine(model, variables, cfg)
    eng2.prefill(0, kw["prompt"], seed=7, temperature=0.9, top_k=10,
                 eos_id=eos, max_new_tokens=6)
    active = np.zeros((SCFG.max_batch_size,), bool)
    active[0] = True
    tok, emitted = eng2.step(active)
    assert tok.shape == (SCFG.max_batch_size, 6)  # H * (k+1), cap 6
    assert emitted[0] == stop + 1
    assert tok[0, :stop + 1].tolist() == ref      # ends WITH the eos
    assert (tok[0, stop + 1:] == SCFG.pad_id).all()
    assert (emitted[1:] == 0).all()
    assert int(np.asarray(eng2.positions)[0]) == \
        len(kw["prompt"]) + stop + 1

    sched2 = Scheduler(Engine(model, variables, cfg))
    rid = sched2.submit(Request(**kw, eos_id=eos))
    _drain(sched2)
    res = sched2.results[rid]
    assert res.finish_reason == "eos"
    assert res.tokens == ref


def test_spec_ttft_and_tpot_credited_per_accepted_token(
        model_and_vars, tmp_path):
    """A verify dispatch emitting e tokens observes serve.tpot_s once
    PER ACCEPTED token (block dt split over e) and credits TTFT at the
    first accepted token's position within the block — not at the
    block end (the PR 5 move, denominator = accepted count)."""
    model, variables = model_and_vars
    obs.start_run(str(tmp_path / "spec_tpot"), meta={"kind": "serve"})
    try:
        spec = SpeculativeConfig(draft_k=7, draft_layers=None)
        eng = Engine(model, variables,
                     dataclasses.replace(SCFG, max_batch_size=1,
                                         speculative=spec))
        sched = Scheduler(eng)
        rid = sched.submit(Request(prompt=[5, 17, 3], max_new_tokens=8))
        _drain(sched)
        assert eng.step_calls == 1          # all 8 tokens, one verify
        h = obs.histogram("serve.tpot_s")
        assert h.count == 8                 # one observation per token
        res = sched.results[rid]
        assert res.ttft_s < res.latency_s
        # serve.decode.horizon records the tokens-per-dispatch CEILING
        # h * (draft_k + 1).
        dh = obs.histogram("serve.decode.horizon")
        assert dh.summary()["max"] == 8
    finally:
        obs.end_run()


# ------------------------------------------------ program contract
def test_two_engine_frozen_program_counts(model_and_vars):
    """The frozen program contract counted PER ENGINE: target keeps
    exactly 1 step + len(prefill_buckets) programs (the whole
    draft→verify→accept loop is baked into the one step program) and
    the draft engine exactly len(prefill_buckets) bucket prefills (its
    decode never dispatches on its own) — all misses frozen after
    warmup, and >1 token accepted per verify dispatch on the ledger."""
    model, variables = model_and_vars
    eng = Engine(model, variables,
                 dataclasses.replace(SCFG, speculative=SPEC))
    sched = Scheduler(eng)
    n_buckets = len(SCFG.prefill_buckets)

    def wave(tag):
        for i in range(4):
            sched.submit(Request(
                prompt=[3 + i, 1, 4] * (1 + i % 2),   # both buckets
                max_new_tokens=8, request_id=f"{tag}{i}"))
        _drain(sched)

    wave("a")
    t, d = eng.compile_stats(), eng.draft_compile_stats()
    assert t["entries"] == t["misses"] == 1 + n_buckets
    assert d["entries"] == d["misses"] == n_buckets
    wave("b")                                  # steady state: no growth
    t2, d2 = eng.compile_stats(), eng.draft_compile_stats()
    assert (t2["entries"], t2["misses"]) == \
        (1 + n_buckets, 1 + n_buckets)
    assert (d2["entries"], d2["misses"]) == (n_buckets, n_buckets)
    assert t2["hits"] > t["hits"]
    # The headline ledger: more than one token accepted per verify.
    assert eng.spec_verifies > 0
    assert (eng.spec_accepted + eng.spec_verifies) \
        / eng.spec_verifies > 1.0


def test_draft_pool_mirrors_slot_lifecycle(model_and_vars):
    """The draft pool shadows the target pool's slot lifecycle by
    INDEX: alloc claims the same slot in both, free releases both in
    the same call, and the leak oracle catches lifecycle drift."""
    model, variables = model_and_vars
    pool = PagedSlotPool(model, 3, 48, jnp.float32, block_size=4)
    draft, dvars = self_draft(model, variables, 1)
    del dvars
    mirror = PagedSlotPool(draft, 3, 48, jnp.float32, block_size=4)
    pool.mirror = mirror
    s = pool.alloc()
    assert s is not None and s not in mirror._free_slots
    pool.free(s)
    assert sorted(mirror._free_slots) == sorted(pool._free_slots)
    pool.leak_check()
    # Claiming a slot the mirror already holds must surface.
    s = pool.alloc()
    with pytest.raises(ValueError):
        mirror.claim(s)
    # Drift: the mirror losing lockstep must surface, not corrupt.
    mirror.free(s)
    with pytest.raises(AssertionError, match="draft pool slot drift"):
        pool.leak_check()
    mirror.claim(s)                           # restore lockstep
    pool.free(s)
    pool.leak_check()


def test_speculative_config_validation(model_and_vars):
    model, variables = model_and_vars
    with pytest.raises(ValueError, match="draft_k"):
        ServeConfig(speculative=SpeculativeConfig(draft_k=0))
    with pytest.raises(ValueError, match="draft_layers"):
        ServeConfig(speculative=SpeculativeConfig(draft_layers=0))
    # argv/JSON convenience: a dict coerces to SpeculativeConfig.
    cfg = ServeConfig(speculative={"draft_k": 2})
    assert isinstance(cfg.speculative, SpeculativeConfig)
    with pytest.raises(ValueError, match="draft_layers"):
        self_draft(model, variables, CFG["num_layers"] + 1)
    with pytest.raises(ValueError, match="draft_variables"):
        Engine(model, variables,
               dataclasses.replace(SCFG, speculative=SPEC),
               draft_model=model)
    other = GPT2(GPT2Config(**{**CFG, "vocab_size": 96}))
    with pytest.raises(ValueError, match="vocab"):
        Engine(model, variables,
               dataclasses.replace(SCFG, speculative=SPEC),
               draft_model=other,
               draft_variables=other.init(jax.random.PRNGKey(1)))
    # Early-exit self-draft: first N blocks, shared trunk leaves.
    draft, dvars = self_draft(model, variables, 1)
    assert draft.cfg.num_layers == 1
    assert dvars["params"]["wte"] is variables["params"]["wte"]


# ------------------------------------------------- faults + chaos
def test_spec_verify_nan_retires_only_victim(model_and_vars):
    """The pinned serve.spec.verify fault point, nan rule: one row's
    carried logits are poisoned after a verify dispatch; the NEXT
    dispatch's in-program tripwire freezes that row and the scheduler
    retires it typed — batch neighbors finish clean, zero leaks in
    either pool."""
    model, variables = model_and_vars
    eng = Engine(model, variables,
                 dataclasses.replace(SCFG, speculative=SPEC))
    sched = Scheduler(eng)
    faults.install(faults.FaultPlan.parse(
        "serve.spec.verify:nan@1x1", seed=3))
    try:
        rids = [sched.submit(Request(prompt=[9 + i, 2, 5],
                                     max_new_tokens=8,
                                     request_id=f"v{i}"))
                for i in range(3)]
        _drain(sched)
    finally:
        faults.clear()
    reasons = {r: sched.results[r].finish_reason for r in rids}
    assert sorted(reasons.values()) == ["error", "length", "length"]
    victim = next(r for r, why in reasons.items() if why == "error")
    assert sched.results[victim].error
    assert eng.pool.num_free == SCFG.max_batch_size
    eng.pool.leak_check()                     # recurses into the mirror


def test_spec_verify_error_rides_bounded_retry(model_and_vars):
    """An error rule at serve.spec.verify raises typed InjectedFault
    out of engine.step; the scheduler's single bounded retry redials
    and every request still finishes clean."""
    model, variables = model_and_vars
    eng = Engine(model, variables,
                 dataclasses.replace(SCFG, speculative=SPEC))
    sched = Scheduler(eng)
    faults.install(faults.FaultPlan.parse(
        "serve.spec.verify:error@2x1", seed=0))
    try:
        rids = [sched.submit(Request(prompt=[4 + i, 8], max_new_tokens=6,
                                     request_id=f"e{i}"))
                for i in range(2)]
        _drain(sched)
    finally:
        faults.clear()
    assert all(sched.results[r].finish_reason == "length" for r in rids)
    eng.pool.leak_check()


def test_spec_chaos_zero_leaks_both_pools(model_and_vars, tmp_path):
    """The chaos acceptance with speculation ON at horizon 4: seeded
    prefill errors + verify NaN bursts + kv.bind failures over 16
    requests. Every request gets exactly one typed result, zero slot
    leaks and zero block leaks in BOTH the target and draft pools (the
    leak oracle recurses through the mirror), the two-engine program
    set stays frozen, and the artifacts pass the pinned schema
    including the serve.spec.* instruments and the report's
    speculation line."""
    model, variables = model_and_vars
    run_dir = str(tmp_path / "chaos_spec")
    obs.start_run(run_dir, meta={"kind": "chaos_spec"})
    try:
        cfg = dataclasses.replace(SCFG, decode_horizon=4,
                                  speculative=SPEC)
        eng = Engine(model, variables, cfg)
        sched = Scheduler(eng)
        faults.install(faults.FaultPlan.parse(
            "serve.prefill:error%0.08;serve.spec.verify:nan%0.05;"
            "serve.kv.bind:error%0.03", seed=7))
        try:
            prefix = [(3 * i + 5) % 97 for i in range(8)]
            rids = []
            for i in range(16):
                prompt = (prefix + [i % 97, (2 * i) % 97]
                          if i % 2 else
                          [(11 * i + j) % 97 for j in range(6)])
                rids.append(sched.submit(Request(
                    prompt=prompt, max_new_tokens=6,
                    temperature=0.8 if i % 3 == 0 else 0.0,
                    top_k=10 if i % 3 == 0 else None, seed=i,
                    request_id=f"c{i}")))
            _drain(sched)
        finally:
            faults.clear()
        assert set(rids) <= set(sched.results)
        reasons = {sched.results[r].finish_reason for r in rids}
        assert reasons <= {"length", "error"}
        assert eng.pool.num_free == cfg.max_batch_size
        eng.pool.leak_check()                 # target + mirror oracles
        stats = eng.compile_stats()
        assert stats["entries"] == stats["misses"] == \
            1 + len(cfg.prefill_buckets)
        d = eng.draft_compile_stats()
        assert d["entries"] == d["misses"] == len(cfg.prefill_buckets)
        eng.pool.clear_prefix_cache()
        eng.pool.leak_check()
        assert eng.pool.blocks_used == 0
        assert eng.draft_pool.blocks_used == 0
    finally:
        obs.end_run()
    from check_telemetry_schema import check_run_dir
    assert check_run_dir(run_dir) == []
    with open(os.path.join(run_dir, "summary.json")) as f:
        summary = json.load(f)
    assert summary["counters"]["serve.spec.draft_tokens_total"] > 0
    assert summary["counters"]["serve.spec.accepted_total"] > 0
    assert summary["histograms"]["serve.spec.accepted_len"]["count"] > 0
    from nezha_tpu.obs.report import render_report
    report = render_report(run_dir)
    assert "speculation:" in report and "tokens/verify" in report
    # Dropping a spec instrument must FAIL the pinned schema.
    del summary["histograms"]["serve.spec.accepted_len"]
    with open(os.path.join(run_dir, "summary.json"), "w") as f:
        json.dump(summary, f)
    assert any("serve.spec.accepted_len" in e
               for e in check_run_dir(run_dir))


# --------------------------------------------------------- benchmark
def test_serving_benchmark_spec_record(tmp_path):
    """benchmarks/serving.py --speculative: the record gains the
    spec{draft_k, accept_rate, tokens_per_verify, ...} block and the
    tiny closed loop already accepts >1 token per verify dispatch."""
    import serving as serving_bench

    args = serving_bench.build_parser().parse_args([
        "--requests", "6", "--concurrency", "2",
        "--max-batch-size", "2", "--max-len", "48",
        "--max-prefill-len", "8", "--prompt-len", "4",
        "--max-new-tokens", "8", "--sample-fraction", "0",
        "--decode-horizon", "1", "--speculative", "--draft-k", "3",
        "--draft-layers", "1", "--platform", "cpu",
        "--run-dir", str(tmp_path / "specbench")])
    record = serving_bench.run(args)
    rec = record["by_horizon"]["1"] if "by_horizon" in record else record
    sp = rec["spec"]
    assert sp["draft_k"] == 3 and sp["draft_layers"] == 1
    assert sp["verifies"] > 0
    assert sp["draft_tokens"] == sp["verifies"] * 3
    assert 0.0 < sp["accept_rate"] <= 1.0
    assert sp["tokens_per_verify"] > 1.0
    assert rec["tokens_per_sec"] > 0
