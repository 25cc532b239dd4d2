"""One decode block in flight: ``Engine.step`` launches block k before it
collects block k-1 for the one caller that can take a block one call late,
the ``Scheduler``; every other caller keeps launch-then-collect.

What is held here, at tiny size on the CPU rig: the overlapped scheduler
gives every request the tokens and the finish reason the launch-then-collect
form gives it (EOS mid-stream, length finishes, admissions into freed slots,
more requests than slots; GPT-2, a latent + expert model and a state model,
``decode_horizon`` 1 and 4); a row result whose request has changed while
its block was in flight is dropped and counted; an EOS outlives its block;
preemption, a park's resume and a block-exhaustion victim settle first and
go on with exact tokens; a drained scheduler leaves nothing in flight; a
direct caller and the speculative engine are launch-then-collect; and the
mechanism's three counters on a scripted run.

Sampled streams throughout (greedy repeats one token on these random
weights): a row's draws are a function of its seed and emitted count, so the
streams are the same whatever the batch beside them.
"""

import dataclasses
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nezha_tpu import faults, obs
from nezha_tpu.faults import FaultPlan
from nezha_tpu.models.gpt2 import GPT2, GPT2Config
from nezha_tpu.models.kimi_linear import kimi_linear
from nezha_tpu.models.mistral4 import mistral_small4
from nezha_tpu.serve import (Engine, FinishReason, Request, Scheduler,
                             ServeConfig, SpeculativeConfig)

CFG = dict(vocab_size=97, max_positions=64, num_layers=2, num_heads=4,
           hidden_size=64)
SCFG = ServeConfig(max_batch_size=2, max_len=48, max_prefill_len=8,
                   prefill_buckets=(4, 8), k_max=16, queue_capacity=16,
                   cache_dtype=jnp.float32, kv_block_size=4)


@pytest.fixture(scope="module")
def gpt2_tiny():
    model = GPT2(GPT2Config(**CFG))
    return model, model.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def models(gpt2_tiny):
    """name -> (model, variables, ServeConfig): GPT-2's per-head K/V, a
    latent table with routed experts, a recurrent state beside a latent
    table (no prefix cache there: a state cannot be shared)."""
    wide = dict(max_batch_size=2, max_len=64, max_prefill_len=16,
                prefill_buckets=(8, 16), queue_capacity=16,
                cache_dtype=jnp.float32)
    mistral = mistral_small4("tiny")
    kimi = kimi_linear("tiny")
    return {
        "gpt2": (*gpt2_tiny, SCFG),
        "mistral4": (mistral, mistral.init(jax.random.PRNGKey(0)),
                     ServeConfig(kv_block_size=8, **wide)),
        "kimi_linear": (kimi, kimi.init(jax.random.PRNGKey(0)),
                        ServeConfig(kv_block_size=4, prefix_cache=False,
                                    **wide)),
    }


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    faults.clear()
    yield
    faults.clear()


def _scheduler(model, variables, cfg, overlapped=True):
    """A scheduler over a fresh engine. ``overlapped=False``: the
    launch-then-collect form every direct caller of ``Engine.step`` has;
    no option of the program selects it for a scheduler, so what the
    scheduler asked for at construction is taken back here, for the
    comparison."""
    engine = Engine(model, variables, cfg)
    assert not engine.overlapped
    sched = Scheduler(engine)
    assert engine.overlapped
    if not overlapped:
        assert engine.overlap_blocks(False) is False
    return engine, sched


def _request(i, vocab, eos_id=None, max_new=None):
    rng = np.random.default_rng(1000 + i)
    return Request(
        prompt=rng.integers(1, vocab, 3 + i % 5).tolist(),
        max_new_tokens=max_new or 5 + (3 * i) % 6, temperature=0.9,
        top_k=12, seed=i, eos_id=eos_id, request_id=f"r{i}")


def _run(sched, requests, max_iters=400):
    for req in requests:
        sched.submit(req)
    sched.run_until_idle(max_iters=max_iters)
    assert not sched.has_work()
    return {r.request_id: (sched.results[r.request_id].tokens,
                           sched.results[r.request_id].finish_reason)
            for r in requests}


# (1) ---------------------------------------------------------------------
@pytest.mark.parametrize("horizon", [1, 4])
@pytest.mark.parametrize("name", ["gpt2", "mistral4", "kimi_linear"])
def test_overlapped_scheduler_matches_launch_then_collect(models, name,
                                                          horizon):
    """Seven requests over two slots: a stream each without EOS first,
    then an EOS planted mid-stream in three of them, through both forms:
    the same tokens and the same finish reason for every request."""
    model, variables, cfg = models[name]
    cfg = dataclasses.replace(cfg, decode_horizon=horizon)
    vocab = model.cfg.vocab_size if name == "gpt2" else model.cfg.vocab_held
    n = 7
    _, classic = _scheduler(model, variables, cfg, overlapped=False)
    free = _run(classic, [_request(i, vocab) for i in range(n)])
    eos = {}
    for i in (0, 3, 5):
        toks = free[f"r{i}"][0]
        # the first token not seen before it in the stream, from the
        # second on: the request then stops there and nowhere earlier
        at = next((j for j in range(1, len(toks) - 1)
                   if toks[j] not in toks[:j]), None)
        if at is not None:
            eos[i] = toks[at]
    assert eos, "no stream to plant an EOS in"
    requests = lambda: [_request(i, vocab, eos.get(i)) for i in range(n)]

    _, classic = _scheduler(model, variables, cfg, overlapped=False)
    want = _run(classic, requests())
    engine, sched = _scheduler(model, variables, cfg)
    got = _run(sched, requests())

    assert got == want
    reasons = [r for _, r in want.values()]
    assert reasons.count(FinishReason.EOS) == len(eos)
    assert reasons.count(FinishReason.LENGTH) == n - len(eos)
    for i, tok in eos.items():
        assert want[f"r{i}"][0][-1] == tok
        assert len(want[f"r{i}"][0]) < len(free[f"r{i}"][0])
    # every block but the first after a pass with nothing to launch was
    # launched with another in flight, and nothing is left behind
    assert engine.blocks_overlapped >= 0.75 * engine.step_calls
    assert not engine.in_flight and engine.stale_rows == 0
    assert engine.pool.num_free == cfg.max_batch_size
    engine.pool.leak_check()


# (2) ---------------------------------------------------------------------
def test_nan_frozen_row_does_not_retire_the_slots_next_request(gpt2_tiny):
    """A NaN burst freezes one of two rows; the block launched before the
    host has read the frozen one carries ``ok == False`` for that slot
    again, and comes back after the slot went to the queued request: the
    newcomer is neither retired by it nor handed anything, and the drop
    is counted."""
    model, variables = gpt2_tiny
    vocab = CFG["vocab_size"]
    reqs = lambda: [_request(i, vocab, max_new=10) for i in range(3)]
    _, classic = _scheduler(model, variables, SCFG, overlapped=False)
    want = _run(classic, reqs())

    engine, sched = _scheduler(model, variables, SCFG)
    faults.install(FaultPlan.parse("serve.step.logits:nan@3", seed=1))
    got = _run(sched, reqs())
    assert faults.active().injected_counts["serve.step.logits"] == 1
    errors = [rid for rid, (_, why) in got.items()
              if why == FinishReason.ERROR]
    assert len(errors) == 1 and errors[0] in ("r0", "r1")
    victim = errors[0]
    # the victim keeps what it had before the burst ...
    assert 1 <= len(got[victim][0]) < 10
    assert got[victim][0] == want[victim][0][:len(got[victim][0])]
    # ... its neighbour and the request that took its slot are untouched
    for rid in set(got) - {victim}:
        assert got[rid] == want[rid]
    assert engine.stale_rows == 1
    assert engine.pool.num_free == SCFG.max_batch_size
    engine.pool.leak_check()


# (3) ---------------------------------------------------------------------
@pytest.mark.parametrize("overlapped", [False, True])
def test_eos_row_emits_nothing_in_the_following_block(gpt2_tiny,
                                                      overlapped):
    """Engine level: ``done`` starts from zeros in every block, so the
    EOS has to ride in the carried budget. The row that emitted its EOS
    emits nothing in the next block, launched here before anybody has
    read the first one; its neighbour goes on."""
    model, variables = gpt2_tiny
    probe = Engine(model, variables, SCFG)
    active = np.ones((2,), bool)
    kw = dict(seed=3, temperature=0.9, top_k=12, max_new_tokens=8)
    for slot in (0, 1):
        probe.prefill(probe.pool.alloc(), [5, 17, 3 + slot], **kw)
    first = probe.step(active)[0][:, 0]

    eng = Engine(model, variables, SCFG)
    for slot in (0, 1):
        eng.prefill(eng.pool.alloc(), [5, 17, 3 + slot],
                    eos_id=int(first[0]) if slot == 0 else None, **kw)
    if overlapped:
        assert eng.overlap_blocks()
        tok, emitted = eng.step(active)         # launches 1, nothing back
        assert emitted.sum() == 0 and (tok == SCFG.pad_id).all()
    blocks = [eng.step(active) for _ in range(3)]
    tok, emitted = blocks[0]
    assert emitted.tolist() == [1, 1] and tok[0, 0] == first[0]
    for tok, emitted in blocks[1:]:
        assert emitted.tolist() == [0, 1]
        assert tok[0, 0] == SCFG.pad_id
    assert int(np.asarray(eng.budgets)[0]) == 0
    assert int(np.asarray(eng.positions)[0]) == 3 + 1
    if overlapped:
        assert eng.in_flight
        tok, emitted = eng.settle("test")
        assert emitted.tolist() == [0, 1] and not eng.in_flight
        assert eng.settle("test") is None
    # the mirrors stand where the device does
    assert eng.host_positions.tolist() == np.asarray(eng.positions).tolist()


# (4) ---------------------------------------------------------------------
PCFG = dataclasses.replace(SCFG, preemption=True, preemption_budget=2)


def _reference(model, variables, cfg, req):
    _, sched = _scheduler(model, variables, cfg, overlapped=False)
    return _run(sched, [req])[req.request_id]


def test_preemption_settles_first_and_resumes_exactly(gpt2_tiny):
    """The victim is suspended with the token of the block in flight in
    its stream and in its KV, and resumes into the uninterrupted
    stream."""
    model, variables = gpt2_tiny
    vocab = CFG["vocab_size"]
    # (greedy: a resume re-prefills prompt + tokens and restarts the
    # row's draws, so only a greedy stream resumes bit for bit)
    bg = lambda: dataclasses.replace(_request(0, vocab, max_new=12),
                                     priority="background",
                                     temperature=0.0, top_k=None)
    want = _reference(model, variables, PCFG, bg())

    engine, sched = _scheduler(model, variables, PCFG)
    sched.submit(bg())
    sched.step()
    sched.step()
    assert engine.in_flight
    (live,) = sched._live.values()
    held = len(live.tokens)
    for i in (1, 2):
        sched.submit(_request(i, vocab, max_new=3))
    sched.step()
    assert sched.preempted_count == 1 and engine.settles == 1
    assert len(live.tokens) == held + 1       # the block in flight's
    sched.run_until_idle(max_iters=200)
    assert not sched.has_work() and not engine.in_flight
    res = sched.results["r0"]
    assert (res.tokens, res.finish_reason) == want
    engine.pool.leak_check()


def test_parked_resume_settles_first_and_both_streams_are_exact(gpt2_tiny):
    model, variables = gpt2_tiny
    vocab = CFG["vocab_size"]
    parked = lambda **kw: dataclasses.replace(
        _request(1, vocab, max_new=6), **kw)
    want_run = _reference(model, variables, SCFG,
                          _request(0, vocab, max_new=10))
    want_parked = _reference(model, variables, SCFG, parked())

    engine, sched = _scheduler(model, variables, SCFG)
    sched.submit(parked(prefill_only=True))
    sched.run_until_idle()
    assert sched.results["r1"].finish_reason == FinishReason.PREFILLED
    sched.submit(_request(0, vocab, max_new=10))
    for _ in range(3):
        sched.step()
    assert engine.in_flight and engine.settles == 0
    assert sched.resume_parked("r1") is True
    assert not engine.in_flight and engine.settles == 1
    sched.run_until_idle(max_iters=200)
    assert not sched.has_work() and not engine.in_flight
    for rid, want in (("r0", want_run), ("r1", want_parked)):
        res = sched.results[rid]
        assert (res.tokens, res.finish_reason) == want
    engine.pool.leak_check()


def test_block_exhaustion_victim_keeps_the_block_in_flight(gpt2_tiny):
    """An injected bind failure while a block is in flight: the block
    comes home first, so the victim is retired with every token decoded
    for it, as many as the launch-then-collect form gives it under the
    same plan, and its neighbour's stream is whole."""
    model, variables = gpt2_tiny
    vocab = CFG["vocab_size"]
    cfg = dataclasses.replace(SCFG, prefix_cache=False)
    reqs = lambda: [_request(i, vocab, max_new=12) for i in range(2)]
    plan = "serve.kv.bind:error@5"

    _, classic = _scheduler(model, variables, cfg, overlapped=False)
    free = _run(classic, reqs())
    _, classic = _scheduler(model, variables, cfg, overlapped=False)
    faults.install(FaultPlan.parse(plan))
    want = _run(classic, reqs())
    faults.clear()

    engine, sched = _scheduler(model, variables, cfg)
    faults.install(FaultPlan.parse(plan))
    got = _run(sched, reqs())
    assert got == want
    (victim,) = [rid for rid, (_, why) in got.items()
                 if why == FinishReason.ERROR]
    assert "kv blocks exhausted" in sched.results[victim].error
    toks = got[victim][0]
    assert 1 <= len(toks) < 12 and toks == free[victim][0][:len(toks)]
    (other,) = set(got) - {victim}
    assert got[other] == free[other]
    assert engine.settles >= 1 and not engine.in_flight
    engine.pool.leak_check()


def test_a_failed_call_is_retried_from_a_settled_engine(gpt2_tiny):
    """``serve.step:error`` on one call: the block in flight is collected
    and handed out before the retry, and no token is lost or doubled."""
    model, variables = gpt2_tiny
    vocab = CFG["vocab_size"]
    reqs = lambda: [_request(i, vocab, max_new=8) for i in range(3)]
    _, classic = _scheduler(model, variables, SCFG, overlapped=False)
    want = _run(classic, reqs())
    engine, sched = _scheduler(model, variables, SCFG)
    sched.step_retry_backoff_s = 0.0
    faults.install(FaultPlan.parse("serve.step:error@4"))
    assert _run(sched, reqs()) == want
    assert faults.active().injected_counts["serve.step"] == 1
    assert engine.settles >= 1


# (5) ---------------------------------------------------------------------
def test_a_drained_scheduler_leaves_nothing_in_flight(gpt2_tiny):
    model, variables = gpt2_tiny
    vocab = CFG["vocab_size"]
    engine, sched = _scheduler(model, variables, SCFG)
    for i in range(3):
        sched.submit(_request(i, vocab))
    while sched.has_work():
        sched.step()
        # a block in flight always has a live request to go to
        assert not engine.in_flight or sched._live
    assert not engine.in_flight and sched._launched is None
    assert not sched.has_work()
    # ... and a cutoff delivers what was in flight before it cancels
    sched.submit(_request(5, vocab, max_new=12))
    for _ in range(3):
        sched.step()
    assert engine.in_flight
    assert len(next(iter(sched._live.values())).tokens) == 2
    assert sched.cancel_remaining() == 1
    assert not engine.in_flight and not sched.has_work()
    assert len(sched.results["r5"].tokens) == 3
    engine.pool.leak_check()


# (6) ---------------------------------------------------------------------
def test_direct_caller_and_speculative_engine_are_launch_then_collect(
        gpt2_tiny):
    model, variables = gpt2_tiny
    eng = Engine(model, variables, SCFG)
    slot = eng.pool.alloc()
    eng.prefill(slot, [5, 17, 3], max_new_tokens=4)
    active = np.zeros((2,), bool)
    active[slot] = True
    for k in range(1, 4):       # the block a call returns is its own
        _, emitted = eng.step(active)
        assert emitted[slot] == 1 and not eng.in_flight
        assert eng.host_positions[slot] == 3 + k
    assert eng.blocks_overlapped == 0 and eng.settle() is None

    spec = Engine(model, variables, dataclasses.replace(
        SCFG, speculative=SpeculativeConfig(draft_k=2, draft_layers=1)))
    assert spec.overlap_blocks() is False
    sched = Scheduler(spec)
    assert not spec.overlapped
    sched.submit(Request(prompt=[5, 17, 3], max_new_tokens=6))
    assert sched.step() >= 1            # tokens in the pass that ran them
    sched.run_until_idle(max_iters=50)
    assert not sched.has_work()
    assert spec.blocks_overlapped == spec.settles == 0


# (7) ---------------------------------------------------------------------
def test_counters_on_a_scripted_run(gpt2_tiny, tmp_path):
    """One request of four tokens alone: four launches, three of them
    with a block in flight; the fifth pass finds every budget spent,
    launches nothing and collects. Then one that stops at its second
    token: the third block was launched before the host read the EOS,
    and is drained when the batch empties. A last one cut off by its
    deadline: the token decoded meanwhile is dropped, and counted."""
    model, variables = gpt2_tiny
    vocab = CFG["vocab_size"]
    cfg = dataclasses.replace(SCFG, max_batch_size=1)
    want = _reference(model, variables, cfg, _request(0, vocab, max_new=4))
    obs.start_run(str(tmp_path / "run"), meta={"kind": "serve_test"})
    try:
        engine, sched = _scheduler(model, variables, cfg)
        passes = []
        sched.submit(_request(0, vocab, max_new=4))
        while sched.has_work():
            passes.append(sched.step())
        assert passes == [0, 1, 1, 1, 1]
        assert sched.results["r0"].tokens == want[0]
        assert (engine.step_calls, engine.blocks_overlapped,
                engine.settles, engine.stale_rows) == (4, 3, 0, 0)

        sched.submit(_request(0, vocab, eos_id=want[0][1], max_new=4))
        sched.run_until_idle()
        assert sched.results["r0"].tokens == want[0][:2]
        assert (engine.step_calls, engine.blocks_overlapped,
                engine.settles, engine.stale_rows) == (7, 5, 1, 0)

        sched.submit(dataclasses.replace(_request(0, vocab, max_new=12),
                                         deadline_s=0.25))
        assert sched.step() == 0
        time.sleep(0.3)
        sched.run_until_idle()
        res = sched.results["r0"]
        assert res.finish_reason == FinishReason.DEADLINE
        assert res.tokens == want[0][:1]
        assert (engine.step_calls, engine.blocks_overlapped,
                engine.settles, engine.stale_rows) == (9, 6, 2, 1)

        for name, value in (("blocks_overlapped", 6), ("settles", 2),
                            ("stale_rows", 1)):
            assert obs.counter(f"serve.engine.{name}_total").value == value
        assert obs.histogram("serve.decode.horizon").count == 9
    finally:
        obs.end_run()
