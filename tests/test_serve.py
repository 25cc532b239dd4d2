"""Serving stack: slot pool, per-row sampling, the frozen-program engine
(1 + len(prefill_buckets) compiled programs), bucketed + chunked
prefill, scheduler edge cases (queue-full backpressure, EOS retirement +
same-iteration admission, per-row isolation, deadlines, validation
before slot allocation), and the serving telemetry artifacts.
Everything runs the tiny CPU GPT-2 from tests/test_generate.py's
config — tier-1 budget is tight, and the engine's whole point is that
the program set compiles once per bucket and never again."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nezha_tpu.models.generate import generate
from nezha_tpu.models.gpt2 import GPT2, GPT2Config
from nezha_tpu.serve import (
    Engine,
    QueueFull,
    Request,
    Scheduler,
    ServeConfig,
    sample_tokens,
)

CFG = dict(vocab_size=97, max_positions=64, num_layers=2, num_heads=4,
           hidden_size=64)
SCFG = ServeConfig(max_batch_size=3, max_len=48, max_prefill_len=8,
                   prefill_buckets=(4, 8), k_max=16, queue_capacity=4,
                   cache_dtype=jnp.float32)


@pytest.fixture(scope="module")
def model_and_vars():
    model = GPT2(GPT2Config(**CFG))
    return model, model.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def engine(model_and_vars):
    """ONE engine for the whole module: its program set (step + one
    prefill per bucket) compiles once and every test reuses it (the
    serving property under test)."""
    model, variables = model_and_vars
    return Engine(model, variables, SCFG)


def _drain(sched, max_iters=200):
    iters = sched.run_until_idle(max_iters=max_iters)
    assert not sched.has_work(), "scheduler did not drain"
    return iters


# ------------------------------------------------------ per-row sampling
def test_sample_tokens_per_row_params():
    logits = jnp.asarray([[5.0, 4.0, 3.0, 2.0, 1.0]] * 4, jnp.float32)
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(4, dtype=jnp.uint32))
    # row 0 greedy, row 1 top-k=1 (forced argmax), row 2 nucleus p->0
    # (degrades to argmax), row 3 unconstrained sampling.
    for seed in range(10):
        keys = jax.vmap(jax.random.PRNGKey)(
            jnp.arange(4, dtype=jnp.uint32) + seed * 7)
        tok = np.asarray(sample_tokens(
            logits, keys,
            temperature=jnp.asarray([0.0, 1.0, 1.0, 1.0]),
            top_k=jnp.asarray([0, 1, 0, 0], jnp.int32),
            top_p=jnp.asarray([1.0, 1.0, 1e-6, 1.0]),
            k_max=4))
        assert tok[0] == 0 and tok[1] == 0 and tok[2] == 0
        assert 0 <= tok[3] < 5

    # per-row k under the static cap: k=2 rows never leave the top-2 set
    # even when a batch neighbor samples the full vocab.
    seen = set()
    for seed in range(50):
        keys = jax.vmap(jax.random.PRNGKey)(
            jnp.arange(2, dtype=jnp.uint32) + seed * 13)
        tok = np.asarray(sample_tokens(
            jnp.asarray([[1.0, 2.0, 3.0, 2.5, 0.0]] * 2, jnp.float32),
            keys, temperature=jnp.asarray([2.0, 2.0]),
            top_k=jnp.asarray([2, 0], jnp.int32),
            top_p=jnp.asarray([1.0, 1.0]), k_max=4))
        seen.add(int(tok[0]))
    assert seen <= {2, 3}, seen  # the two largest logits

    with pytest.raises(ValueError, match="k_max"):
        sample_tokens(logits, keys[:4], jnp.zeros(4),
                      jnp.zeros(4, jnp.int32), jnp.ones(4), k_max=99)


# ------------------------------------------------------- scheduler edges
def test_queue_full_rejection(engine):
    sched = Scheduler(engine)
    for _ in range(SCFG.queue_capacity):
        sched.submit(Request(prompt=[1, 2], max_new_tokens=2))
    with pytest.raises(QueueFull):
        sched.submit(Request(prompt=[1, 2], max_new_tokens=2))
    _drain(sched)

    # The admission limit is the slot's KV capacity, NOT the prefill
    # width — a 20-token prompt (> max_prefill_len=8) is admissible
    # (chunked prefill); only max_len bounds what can be served.
    with pytest.raises(ValueError, match="exceeds max_len"):
        sched.submit(Request(prompt=list(range(1, 48)), max_new_tokens=2))
    with pytest.raises(ValueError, match="exceeds max_len"):
        sched.submit(Request(prompt=[1, 2], max_new_tokens=100))
    with pytest.raises(ValueError, match="non-empty"):
        sched.submit(Request(prompt=[], max_new_tokens=2))


def test_rejected_request_never_consumes_slot(engine):
    """Validation is hoisted into admission: a bad request bounces at
    submit() with no slot held, no queue entry, and no program run."""
    sched = Scheduler(engine)
    free_before = engine.pool.num_free
    stats_before = engine.compile_stats()
    for bad in (Request(prompt=[1, 2, 999], max_new_tokens=2),  # id range
                Request(prompt=[-1], max_new_tokens=2),
                Request(prompt=list(range(1, 48)), max_new_tokens=2),
                Request(prompt=[1], max_new_tokens=0)):
        with pytest.raises(ValueError):
            sched.submit(bad)
    assert engine.pool.num_free == free_before
    assert sched.queue_depth == 0 and not sched.has_work()
    # No prefill/step program was even dispatched for the rejects.
    assert engine.compile_stats() == stats_before


def test_deadline_expiry_of_queued_request(engine):
    sched = Scheduler(engine)
    # Capacity 3: occupy every slot with long decodes, then queue one
    # request with an already-hopeless deadline.
    for i in range(SCFG.max_batch_size):
        sched.submit(Request(prompt=[5, 17], max_new_tokens=12,
                             request_id=f"long-{i}"))
    rid = sched.submit(Request(prompt=[1, 2], max_new_tokens=4,
                               deadline_s=0.0, request_id="doomed"))
    sched.step()
    res = sched.results[rid]
    assert res.finish_reason == "deadline"
    assert res.tokens == [] and res.ttft_s is None
    _drain(sched)


def test_eos_retirement_admits_waiter_same_iteration(engine):
    # Learn a seed-deterministic SAMPLED continuation (greedy repeats one
    # token on this random init), then plant its first fresh token as
    # EOS — the request must retire right there on the replay.
    probe_kw = dict(prompt=[5, 17, 3, 42], max_new_tokens=8,
                    temperature=0.9, top_k=10, seed=7)
    sched = Scheduler(engine)
    probe = sched.submit(Request(**probe_kw))
    _drain(sched)
    seq = sched.results[probe].tokens
    stop = next(i for i in range(1, len(seq)) if seq[i] not in seq[:i])
    eos, ref = seq[stop], seq[:stop + 1]
    # Fill all 3 slots; the EOS request retires first and must hand its
    # slot to the queued waiter WITHIN the same scheduler iteration.
    sched.submit(Request(prompt=[7, 7, 23], max_new_tokens=12,
                         request_id="long-a"))
    sched.submit(Request(prompt=[1, 2, 3], max_new_tokens=12,
                         request_id="long-b"))
    rid = sched.submit(Request(**probe_kw, eos_id=eos,
                               request_id="eos-req"))
    waiter = sched.submit(Request(prompt=[9, 9], max_new_tokens=2,
                                  request_id="waiter"))
    passes = 0
    while rid not in sched.results:
        # (one block in flight: the first pass launches and brings
        # nothing back yet)
        assert sched.step() > 0 or passes == 0
        passes += 1
        live_ids = {lv.request_id for lv in sched._live.values()}
        if rid not in sched.results:
            assert waiter not in live_ids  # no free slot before EOS
    res = sched.results[rid]
    assert res.finish_reason == "eos"
    assert res.tokens == ref  # ends WITH the eos token
    # Same iteration: the retiring step's trailing admit filled the slot.
    live_ids = {lv.request_id for lv in sched._live.values()}
    assert waiter in live_ids
    assert engine.pool.num_active == 3
    _drain(sched)


def test_per_row_sampling_isolation(engine):
    """A greedy request's tokens are bit-identical whether it runs alone
    or next to a temperature-1.0 neighbor (per-row RNG keys, per-row
    params: nothing leaks across slots)."""
    sched = Scheduler(engine)
    alone = sched.submit(Request(prompt=[5, 17, 3], max_new_tokens=10))
    _drain(sched)
    solo_tokens = sched.results[alone].tokens

    paired = sched.submit(Request(prompt=[5, 17, 3], max_new_tokens=10))
    sched.submit(Request(prompt=[8, 1, 4], max_new_tokens=10,
                         temperature=1.0, seed=11))
    sched.submit(Request(prompt=[2, 2], max_new_tokens=10,
                         temperature=1.0, top_k=5, seed=23))
    _drain(sched)
    assert sched.results[paired].tokens == solo_tokens

    # Sampling is seed-deterministic per request, also regardless of mix.
    a = sched.submit(Request(prompt=[4, 4, 4], max_new_tokens=6,
                             temperature=0.9, top_k=10, seed=7))
    _drain(sched)
    b = sched.submit(Request(prompt=[4, 4, 4], max_new_tokens=6,
                             temperature=0.9, top_k=10, seed=7))
    c = sched.submit(Request(prompt=[4, 4, 4], max_new_tokens=6,
                             temperature=0.9, top_k=10, seed=8))
    _drain(sched)
    assert sched.results[a].tokens == sched.results[b].tokens
    assert sched.results[b].tokens != sched.results[c].tokens


# ------------------------------------ e2e smoke + the frozen program set
def test_serving_smoke_program_count_and_artifacts(model_and_vars,
                                                   tmp_path):
    """The acceptance smoke: ≥3 concurrent requests with different
    sampling params and lengths, a LATE request admitted while earlier
    ones still decode (continuous batching observable via the occupancy
    gauge), greedy rows matching one-shot generate() token-for-token —
    and steady state compiles exactly ``1 + len(prefill_buckets)``
    programs (the batched step + one prefill per bucket), pinned through
    the obs compile-cache counters and FROZEN once every bucket has been
    warmed. The run dir must pass the frozen serving schema and render a
    serving report."""
    import os
    import sys

    from nezha_tpu import obs

    model, variables = model_and_vars
    run_dir = str(tmp_path / "run")
    obs.start_run(run_dir, meta={"kind": "serve_test"})
    try:
        engine = Engine(model, variables, SCFG)  # fresh compile counters
        sched = Scheduler(engine)
        r1 = sched.submit(Request(prompt=[5, 17, 3, 42],
                                  max_new_tokens=10))
        r2 = sched.submit(Request(prompt=[7, 7, 23], max_new_tokens=5,
                                  temperature=1.0, top_k=10, seed=3))
        r3 = sched.submit(Request(prompt=[1, 2, 3, 4, 5],
                                  max_new_tokens=7, temperature=0.8,
                                  top_p=0.9, seed=9))
        for _ in range(3):
            sched.step()
        # All three in flight, none finished: continuous batch is full.
        assert engine.pool.num_active == 3
        assert obs.gauge("serve.batch_occupancy").value == 1.0
        # r2 (5 tokens) retires first; the LATE request then joins while
        # r1/r3 are still decoding.
        late = sched.submit(Request(prompt=[6, 5], max_new_tokens=4,
                                    request_id="late"))
        while r2 not in sched.results:
            sched.step()
        live = {lv.request_id for lv in sched._live.values()}
        assert "late" in live and r1 not in sched.results
        assert engine.pool.num_active == 3  # refilled, mid-flight
        _drain(sched)

        # Greedy row == one-shot generate, token for token.
        ref = np.asarray(generate(
            model, variables, np.asarray([[5, 17, 3, 42]], np.int32),
            max_new_tokens=10, temperature=0.0,
            cache_dtype=jnp.float32))[0, 4:]
        assert sched.results[r1].tokens == ref.tolist()
        assert len(sched.results[r3].tokens) == 7

        # Exactly 1 + len(prefill_buckets) compiled programs for the
        # whole mixed-request run (prompt lengths 4/3/5/2 hit both the
        # 4- and 8-buckets), by the engine's own cache AND the
        # process-wide obs counters.
        n_programs = 1 + len(SCFG.prefill_buckets)
        stats = engine.compile_stats()
        assert stats == {"entries": n_programs,
                         "hits": stats["hits"], "misses": n_programs}
        assert stats["hits"] > 10
        assert obs.counter("compile_cache.misses").value == n_programs

        # Warmed means FROZEN: another mixed batch (including a chunked
        # 13-token prompt, which must reuse the bucket programs at
        # advancing offsets) adds hits, never misses.
        f1 = sched.submit(Request(prompt=[3, 1, 4], max_new_tokens=3))
        f2 = sched.submit(Request(prompt=list(range(2, 15)),
                                  max_new_tokens=3))
        _drain(sched)
        assert len(sched.results[f2].tokens) == 3
        stats2 = engine.compile_stats()
        assert stats2["entries"] == n_programs
        assert stats2["misses"] == n_programs
        assert stats2["hits"] > stats["hits"]

        assert obs.counter("serve.admitted_total").value == 6
        assert obs.counter("serve.retired_total").value == 6
        assert obs.counter("serve.tokens_total").value == \
            sum(len(sched.results[r].tokens)
                for r in (r1, r2, r3, "late", f1, f2))
        assert obs.histogram("serve.ttft_s").count == 6
        # Bucket telemetry: 5 single-chunk prefills + a 2-chunk prefill
        # (13 = 8 + a 5-tail in the 8-bucket) = 7 chunk calls.
        assert obs.counter("serve.prefill.chunks_total").value == 7
        assert obs.histogram("serve.prefill.bucket_len").count == 7
    finally:
        obs.end_run()

    # Frozen serving schema + report rendering.
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    from check_telemetry_schema import check_run_dir
    assert check_run_dir(run_dir) == []
    from nezha_tpu.obs.report import render_report
    report = render_report(run_dir)
    assert "serving:" in report and "ttft" in report and "tpot" in report
    assert "6 admitted" in report
    # Bucket-occupancy line, labeled with the active prefill impl
    # (CPU auto resolves to the composed XLA path) and the chunk
    # parallelism mode (replicated = classic, seq xM = sequence-
    # sharded over a mesh).
    assert "prefill[xla, replicated]: 7 chunk(s)" in report

    # Every batched decode step is labeled with its own span.
    with open(os.path.join(run_dir, "spans.jsonl")) as f:
        span_names = {json.loads(ln)["name"] for ln in f if ln.strip()}
    assert "serve.engine.dispatch" in span_names
    assert "serve.prefill" in span_names

    # The schema checker actually pins the serve names: dropping one
    # histogram from the summary must fail.
    with open(os.path.join(run_dir, "summary.json")) as f:
        summary = json.load(f)
    del summary["histograms"]["serve.ttft_s"]
    with open(os.path.join(run_dir, "summary.json"), "w") as f:
        json.dump(summary, f)
    assert any("serve.ttft_s" in e for e in check_run_dir(run_dir))


# --------------------------------------- bucketed and chunked prefill
def test_bucketed_prefill_matches_single_bucket(model_and_vars, engine):
    """A 3-token prompt lands in the 4-bucket on the module engine and
    in the 8-bucket on a single-bucket engine (the old padded-to-
    max_prefill_len behavior) — greedy tokens must be identical: the
    bucket is a pad width, never a semantic."""
    model, variables = model_and_vars
    wide = Engine(model, variables, ServeConfig(
        max_batch_size=1, max_len=48, max_prefill_len=8,
        prefill_buckets=(8,), cache_dtype=jnp.float32))
    prompt = [5, 17, 3]
    out = {}
    for name, eng in (("bucketed", engine), ("padded", wide)):
        sched = Scheduler(eng)
        rid = sched.submit(Request(prompt=prompt, max_new_tokens=8))
        _drain(sched)
        out[name] = sched.results[rid].tokens
    assert out["bucketed"] == out["padded"]
    ref = np.asarray(generate(
        model, variables, np.asarray([prompt], np.int32),
        max_new_tokens=8, cache_dtype=jnp.float32))[0, len(prompt):]
    assert out["bucketed"] == ref.tolist()


def test_chunked_long_prompt_matches_single_shot(model_and_vars, engine):
    """A prompt longer than max_prefill_len (20 > 8: two full 8-chunks
    + a 4-tail) prefills in successive chunks at traced offsets and must
    decode exactly like a single-shot prefill of the same prompt — both
    against an engine whose max_prefill_len covers it in one program,
    and against one-shot generate()."""
    model, variables = model_and_vars
    prompt = [(7 * i + 3) % 97 for i in range(20)]
    sched = Scheduler(engine)                   # max_prefill_len=8
    rid = sched.submit(Request(prompt=prompt, max_new_tokens=6))
    _drain(sched)
    chunked = sched.results[rid].tokens

    single = Engine(model, variables, ServeConfig(
        max_batch_size=1, max_len=48, max_prefill_len=32,
        prefill_buckets=(32,), cache_dtype=jnp.float32))
    sched1 = Scheduler(single)
    rid1 = sched1.submit(Request(prompt=prompt, max_new_tokens=6))
    _drain(sched1)
    assert chunked == sched1.results[rid1].tokens

    ref = np.asarray(generate(
        model, variables, np.asarray([prompt], np.int32),
        max_new_tokens=6, cache_dtype=jnp.float32))[0, len(prompt):]
    assert chunked == ref.tolist()


def test_chunked_tail_never_spills_past_capacity(model_and_vars):
    """max_len NOT a multiple of max_prefill_len + a near-capacity
    prompt: the padded tail chunk would write past the slot's KV
    capacity (dynamic_update_slice clamps the start — silent prefix
    corruption); the engine must slide the tail window back over real
    tokens instead. Greedy output still matches one-shot generate()."""
    model, variables = model_and_vars
    eng = Engine(model, variables, ServeConfig(
        max_batch_size=1, max_len=50, max_prefill_len=8,
        prefill_buckets=(8,), cache_dtype=jnp.float32))
    prompt = [(11 * i + 5) % 97 for i in range(49)]   # 6 full chunks + 1
    sched = Scheduler(eng)
    rid = sched.submit(Request(prompt=prompt, max_new_tokens=1))
    _drain(sched)
    ref = np.asarray(generate(
        model, variables, np.asarray([prompt], np.int32),
        max_new_tokens=1, cache_dtype=jnp.float32))[0, len(prompt):]
    assert sched.results[rid].tokens == ref.tolist()


def test_default_buckets_and_validation():
    from nezha_tpu.serve.engine import default_prefill_buckets
    assert default_prefill_buckets(32) == (8, 16, 32)
    assert default_prefill_buckets(24) == (8, 16, 24)
    assert default_prefill_buckets(8) == (8,)
    assert default_prefill_buckets(5) == (5,)
    assert ServeConfig(max_prefill_len=32).prefill_buckets == (8, 16, 32)
    with pytest.raises(ValueError, match="end exactly"):
        ServeConfig(max_prefill_len=16, prefill_buckets=(4, 8))
    with pytest.raises(ValueError, match="strictly increasing"):
        ServeConfig(max_prefill_len=16, prefill_buckets=(8, 4, 16))
    with pytest.raises(ValueError, match="decode_impl"):
        ServeConfig(decode_impl="pallas")


def test_engine_rejects_bad_shapes(model_and_vars):
    model, variables = model_and_vars
    with pytest.raises(ValueError, match="max_positions"):
        Engine(model, variables, ServeConfig(max_len=1024))
    with pytest.raises(ValueError, match="max_prefill_len"):
        ServeConfig(max_len=8, max_prefill_len=16)
    with pytest.raises(ValueError, match="decode_horizon"):
        ServeConfig(decode_horizon=0)


# ------------------------------------------------- decode horizon (PR 5)
def test_decode_horizon_parity_bit_identical(model_and_vars):
    """horizon=8 delivers bit-identical per-request outputs to horizon=1
    — for a greedy row, a sampled row (RNG streams advance per EMITTED
    token, so they are horizon-invariant), and a chunked-prompt row —
    and the greedy row matches one-shot generate() token for token.
    max_new_tokens=10 with H=8 also exercises the on-device budget
    stopping a block mid-horizon (8 + 2)."""
    model, variables = model_and_vars
    outs = {}
    for h in (1, 8):
        eng = Engine(model, variables,
                     dataclasses.replace(SCFG, decode_horizon=h))
        sched = Scheduler(eng)
        a = sched.submit(Request(prompt=[5, 17, 3, 42],
                                 max_new_tokens=10))
        b = sched.submit(Request(prompt=[7, 7], max_new_tokens=9,
                                 temperature=0.9, top_k=10, seed=7))
        c = sched.submit(Request(prompt=list(range(2, 15)),
                                 max_new_tokens=5))
        _drain(sched)
        outs[h] = {k: (sched.results[k].tokens,
                       sched.results[k].finish_reason)
                   for k in (a, b, c)}
    assert outs[1] == outs[8]
    ref = np.asarray(generate(
        model, variables, np.asarray([[5, 17, 3, 42]], np.int32),
        max_new_tokens=10, temperature=0.0,
        cache_dtype=jnp.float32))[0, 4:]
    greedy_tokens = list(outs[8].values())[0][0]
    assert greedy_tokens == ref.tolist()


def test_eos_mid_horizon_stops_kv_writes_and_overshoot(model_and_vars):
    """A row whose EOS lands at scan step k < H flips the carried done
    mask ON DEVICE: its emitted count stops at k+1, its cache position
    freezes there (no K/V appended for the rest of the block), the
    block's overshoot columns are pad — and through the scheduler the
    client sees tokens ending exactly at the EOS, never overshoot."""
    model, variables = model_and_vars
    cfg8 = dataclasses.replace(SCFG, decode_horizon=8)
    eng = Engine(model, variables, cfg8)
    # Learn a seed-deterministic SAMPLED continuation (distinct tokens;
    # greedy repeats one token on this random init), then plant a
    # mid-horizon token as EOS on the replay.
    kw = dict(prompt=[5, 17, 3, 42], max_new_tokens=8, temperature=0.9,
              top_k=10, seed=7)
    sched = Scheduler(eng)
    probe = sched.submit(Request(**kw))
    _drain(sched)
    seq = sched.results[probe].tokens
    stop = next(i for i in range(1, len(seq)) if seq[i] not in seq[:i])
    eos, ref = seq[stop], seq[:stop + 1]
    assert 1 <= stop < 7          # genuinely mid-horizon

    # Engine-level: one block, device-side stop.
    eng2 = Engine(model, variables, cfg8)
    eng2.prefill(0, kw["prompt"], seed=7, temperature=0.9, top_k=10,
                 eos_id=eos, max_new_tokens=8)
    active = np.zeros((SCFG.max_batch_size,), bool)
    active[0] = True
    tok, emitted = eng2.step(active)
    assert tok.shape == (SCFG.max_batch_size, 8)
    assert emitted[0] == stop + 1
    assert tok[0, :stop + 1].tolist() == ref    # ends WITH the eos
    # Overshoot columns are pad, sampled by nobody.
    assert (tok[0, stop + 1:] == SCFG.pad_id).all()
    # Inactive rows emit nothing.
    assert (emitted[1:] == 0).all()
    # KV writes stopped with the done flip: the cache position froze at
    # prompt + emitted instead of advancing through the whole block.
    assert int(np.asarray(eng2.positions)[0]) == len(kw["prompt"]) + stop + 1

    # Scheduler-level: the client never sees overshoot.
    sched2 = Scheduler(eng)
    rid = sched2.submit(Request(**kw, eos_id=eos))
    _drain(sched2)
    res = sched2.results[rid]
    assert res.finish_reason == "eos"
    assert res.tokens == ref


def test_horizon_frozen_programs_and_dispatch_amortization(
        model_and_vars):
    """horizon > 1 keeps the '1 step + len(prefill_buckets) programs,
    frozen after warmup' contract (the horizon is baked INTO the one
    step program), decodes bit-identically — and performs <= 1/8 the
    host dispatches per token of horizon=1, by the engine's own
    dispatch counter (the acceptance bound of ISSUE 5)."""
    model, variables = model_and_vars
    steps, tokens, all_tokens = {}, {}, {}
    n_programs = 1 + len(SCFG.prefill_buckets)
    for h in (1, 8):
        eng = Engine(model, variables,
                     dataclasses.replace(SCFG, decode_horizon=h))
        sched = Scheduler(eng)
        # Alternate prompt lengths 3/6 so BOTH prefill buckets (4, 8)
        # compile and the frozen-program assertion covers the full set.
        rids = [sched.submit(Request(
                    prompt=[3 + i, 1, 4] * (1 + i % 2),
                    max_new_tokens=16, request_id=f"r{i}"))
                for i in range(4)]
        _drain(sched)
        stats = eng.compile_stats()
        assert stats["entries"] == n_programs
        assert stats["misses"] == n_programs     # frozen after warmup
        steps[h] = eng.step_calls
        all_tokens[h] = {r: sched.results[r].tokens for r in rids}
        tokens[h] = sum(len(t) for t in all_tokens[h].values())
    assert all_tokens[1] == all_tokens[8]
    assert tokens[1] == tokens[8] == 64
    # <= 1/8 of the dispatches per token (4 requests x 16 tokens over
    # batch 3: 32 single-token dispatches vs 4 blocks of 8).
    assert steps[8] / tokens[8] <= (steps[1] / tokens[1]) / 8


def test_horizon_telemetry_host_gap_and_horizon_hist(model_and_vars,
                                                     tmp_path):
    """The two PR-5 instruments: serve.host_gap_s (host time between
    consecutive step dispatches) and serve.decode.horizon (tokens-per-
    dispatch ceiling) land in the run artifacts, pass the pinned schema,
    and render as the report's host-gap line."""
    import os
    import sys

    from nezha_tpu import obs

    model, variables = model_and_vars
    run_dir = str(tmp_path / "hrun")
    obs.start_run(run_dir, meta={"kind": "serve_test"})
    try:
        eng = Engine(model, variables,
                     dataclasses.replace(SCFG, decode_horizon=4))
        sched = Scheduler(eng)
        for i in range(3):
            sched.submit(Request(prompt=[1 + i, 2], max_new_tokens=8))
        _drain(sched)
        # 8 tokens at H=4 = 2 blocks -> at least one inter-dispatch gap.
        assert obs.histogram("serve.host_gap_s").count >= 1
        dh = obs.histogram("serve.decode.horizon")
        assert dh.count == eng.step_calls
        assert dh.summary()["max"] == 4
    finally:
        obs.end_run()
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    from check_telemetry_schema import check_run_dir
    assert check_run_dir(run_dir) == []
    from nezha_tpu.obs.report import render_report
    report = render_report(run_dir)
    assert "host gap" in report and "horizon p50 4" in report
    # The schema checker actually pins the new names.
    with open(os.path.join(run_dir, "summary.json")) as f:
        summary = json.load(f)
    del summary["histograms"]["serve.host_gap_s"]
    with open(os.path.join(run_dir, "summary.json"), "w") as f:
        json.dump(summary, f)
    assert any("serve.host_gap_s" in e for e in check_run_dir(run_dir))


def test_horizon_tpot_accounting_block_dt_split(model_and_vars,
                                                tmp_path):
    """serve.tpot_s folds block_dt / tokens_emitted once PER EMITTED
    token (not one block_dt per dispatch): at H=4 the per-token
    percentiles must sit near a quarter of the block cost, not at it —
    pinned by count (one observation per token) and by sum ~= total
    decode wall time regardless of horizon."""
    from nezha_tpu import obs

    model, variables = model_and_vars
    obs.start_run(str(tmp_path / "tpot"), meta={"kind": "serve_test"})
    try:
        eng = Engine(model, variables,
                     dataclasses.replace(SCFG, max_batch_size=1,
                                         decode_horizon=4))
        sched = Scheduler(eng)
        rid = sched.submit(Request(prompt=[5, 17, 3], max_new_tokens=8))
        _drain(sched)
        h = obs.histogram("serve.tpot_s")
        assert h.count == 8            # one observation per token...
        assert eng.step_calls == 2     # ...from only two dispatches
        # Each block contributes e * (dt / e) = dt to the sum, so the
        # mean tpot is (total decode time) / tokens — the number that
        # stays comparable across horizon settings.
        s = h.summary()
        assert s["p50"] <= s["sum"] / 2     # not one whole block per tok
        tt = obs.histogram("serve.ttft_s")
        assert tt.count == 1
        # TTFT used the first token's position within the first block:
        # strictly less than the full block would have charged.
        assert sched.results[rid].ttft_s < sched.results[rid].latency_s
    finally:
        obs.end_run()


def test_serving_benchmark_cli(tmp_path):
    """benchmarks/serving.py drives the stack end to end and writes
    schema-valid artifacts (the load-vs-latency record of the ISSUE)."""
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "benchmarks"))
    import serving as bench

    run_dir = str(tmp_path / "bench")
    rec = bench.run(bench.build_parser().parse_args(
        ["--requests", "6", "--concurrency", "2", "--prompt-len", "4",
         "--max-new-tokens", "4", "--max-batch-size", "2",
         "--max-len", "16", "--max-prefill-len", "8",
         "--run-dir", run_dir]))
    assert rec["finished"] == 6 and rec["tokens"] == 24
    assert rec["compile_cache"]["misses"] == 2
    assert rec["ttft_s"]["p50"] > 0 and rec["tokens_per_sec"] > 0
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    from check_telemetry_schema import check_run_dir
    assert check_run_dir(run_dir) == []


def test_nezha_serve_stdio_jsonl():
    """The nezha-serve stdio front end: JSONL requests in (including a
    bad line), streamed token + done events out, byte-level text."""
    import io

    from nezha_tpu.cli.serve import build_parser, run as serve_run

    lines = "\n".join([
        json.dumps({"id": "a", "prompt_tokens": [5, 17, 3, 42],
                    "max_new_tokens": 5}),
        json.dumps({"id": "b", "prompt": "hi", "max_new_tokens": 3,
                    "temperature": 1.0, "top_k": 9, "seed": 4}),
        "garbage line",
        json.dumps({"id": "c", "prompt_tokens": [999]}),  # out of vocab
    ]) + "\n"
    stdout = io.StringIO()
    args = build_parser().parse_args(
        ["--random-init", "--model-preset", "tiny", "--max-batch-size",
         "2", "--max-len", "32", "--max-prefill-len", "8",
         "--platform", "cpu"])
    assert serve_run(args, stdin=io.StringIO(lines), stdout=stdout) == 0
    events = [json.loads(ln) for ln in stdout.getvalue().splitlines()]
    done = {e["id"]: e for e in events if e["event"] == "done"}
    errors = [e for e in events if e["event"] == "error"]
    assert len(done["a"]["tokens"]) == 5
    assert done["a"]["finish_reason"] == "length"
    assert len(done["b"]["tokens"]) == 3
    assert isinstance(done["b"]["text"], str)
    assert len(errors) == 2
    # token events streamed before each done, tagged per request
    a_tokens = [e["token"] for e in events
                if e["event"] == "token" and e["id"] == "a"]
    assert a_tokens == done["a"]["tokens"]
