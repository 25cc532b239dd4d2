"""SLO-aware multi-tenant scheduling (PR 19): WFQ lane arithmetic
(weight conservation, starvation-freedom, tenant round-robin, the
single-lane-is-exact-FIFO compatibility pin), typed per-tenant queue
caps, preemption to the host KV tier (bit-identical preempt -> resume
on BOTH kv layouts, deadline-while-preempted, the preemption-budget
anti-thrash pin, the scheduler.preempt failed-demotion drill, the
SLO-burn quota widening), the elastic supervisor (autoscale ladder
with two-sided hysteresis, cooldown, bounds, the supervisor.scale
drill, config validation), and the 16-request seeded acceptance under
preemption churn: zero slot/block/host leaks and the frozen
``1 + len(prefill_buckets)`` program contract.

Serving tests run the tiny CPU GPT-2 from test_serve.py's config;
autoscale tests drive ``Supervisor.autoscale_tick(now=...)`` directly
against a fake backend — no sockets, no threads, no timing games."""

import dataclasses
import os
import sys
import time
import types

import jax
import jax.numpy as jnp
import pytest

from nezha_tpu import faults, obs
from nezha_tpu.faults import FaultPlan
from nezha_tpu.models.gpt2 import GPT2, GPT2Config
from nezha_tpu.serve import (
    Engine,
    FinishReason,
    PRIORITIES,
    QueueFull,
    Request,
    Scheduler,
    ServeConfig,
    TenantOverLimit,
)
from nezha_tpu.serve.scheduler import _Live
from nezha_tpu.serve.supervisor import (
    LIVE,
    STARTING,
    STOPPED,
    RouterConfig,
    Supervisor,
)

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "tools"))

CFG = dict(vocab_size=97, max_positions=64, num_layers=2, num_heads=4,
           hidden_size=64)
# Two slots on purpose: one background decode + one free slot means the
# SECOND interactive arrival is exactly the preemption trigger.
PCFG = ServeConfig(max_batch_size=2, max_len=48, max_prefill_len=8,
                   prefill_buckets=(4, 8), k_max=16, queue_capacity=8,
                   cache_dtype=jnp.float32, kv_block_size=4,
                   preemption=True, preemption_budget=2)


@pytest.fixture(scope="module")
def model_and_vars():
    model = GPT2(GPT2Config(**CFG))
    return model, model.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def paged_engine(model_and_vars):
    model, variables = model_and_vars
    return Engine(model, variables, PCFG)


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    faults.clear()
    yield
    faults.clear()


def _drain(sched, max_iters=300):
    iters = sched.run_until_idle(max_iters=max_iters)
    assert not sched.has_work(), "scheduler did not drain"
    return iters


def _submit(sched, rid, prompt, priority="interactive", tenant="default",
            max_new=4, deadline_s=None):
    return sched.submit(Request(
        prompt=prompt, max_new_tokens=max_new, priority=priority,
        tenant_id=tenant, deadline_s=deadline_s, request_id=rid))


# ------------------------------------------------------------------ WFQ
def test_wfq_weight_conservation(paged_engine):
    """Under a full backlog in every lane the default 4:2:1 weights
    grant exactly 4 interactive / 2 batch / 1 background per 7 — and
    the exact virtual-time order is deterministic. Background is
    granted within the first 7: starvation-freedom, not priority
    masking."""
    sched = Scheduler(paged_engine)
    # Adversarial submit order: lowest class first.
    _submit(sched, "g0", [1, 2, 3], priority="background")
    for i in range(2):
        _submit(sched, f"b{i}", [1, 2, 3], priority="batch")
    for i in range(4):
        _submit(sched, f"i{i}", [1, 2, 3], priority="interactive")
    with sched._lock:
        order = [sched._pop_next().req.priority for _ in range(7)]
    assert order == ["interactive", "batch", "background",
                     "interactive", "interactive", "batch",
                     "interactive"]
    assert sched.queue_depth == 0


def test_wfq_tenant_round_robin(paged_engine):
    """Within one lane, tenants share equally: a 3-deep tenant cannot
    starve a 2-deep one — grants alternate."""
    sched = Scheduler(paged_engine)
    for i in range(3):
        _submit(sched, f"a{i}", [1, 2], priority="batch", tenant="acme")
    for i in range(2):
        _submit(sched, f"x{i}", [1, 2], priority="batch", tenant="xcorp")
    with sched._lock:
        order = [sched._pop_next().request_id for _ in range(5)]
    assert order == ["a0", "x0", "a1", "x1", "a2"]


def test_wfq_single_lane_is_exact_fifo(paged_engine):
    """The compatibility pin: every pre-PR-19 caller lands in one lane
    and one tenant, where WFQ degenerates to the bounded FIFO —
    defaults preserve today's order bit-for-bit."""
    sched = Scheduler(paged_engine)
    for i in range(6):
        _submit(sched, f"r{i}", [1, 2, 3])
    with sched._lock:
        order = [sched._pop_next().request_id for _ in range(6)]
    assert order == [f"r{i}" for i in range(6)]


def test_priority_and_tenant_validation(paged_engine):
    sched = Scheduler(paged_engine)
    with pytest.raises(ValueError, match="priority"):
        sched.submit(Request(prompt=[1], priority="urgent"))
    with pytest.raises(ValueError, match="tenant_id"):
        sched.submit(Request(prompt=[1], tenant_id=""))
    assert tuple(PRIORITIES) == ("interactive", "batch", "background")


def test_tenant_over_limit_typed(model_and_vars):
    """The per-tenant cap fails typed — TenantOverLimit IS a QueueFull
    (existing 503 handlers keep working) but names the tenant, and
    other tenants keep admitting below the global bound."""
    model, variables = model_and_vars
    engine = Engine(model, variables,
                    dataclasses.replace(PCFG, tenant_queue_cap=2))
    sched = Scheduler(engine)
    _submit(sched, "a0", [1, 2], tenant="acme")
    _submit(sched, "a1", [1, 2], tenant="acme")
    with pytest.raises(TenantOverLimit):
        _submit(sched, "a2", [1, 2], tenant="acme")
    assert issubclass(TenantOverLimit, QueueFull)
    _submit(sched, "x0", [1, 2], tenant="xcorp")   # not affected
    assert sched.tenant_queue_depths() == {"acme": 2, "xcorp": 1}
    # The cap is per-tenant-across-lanes, not per (tenant, lane).
    with pytest.raises(TenantOverLimit):
        _submit(sched, "a3", [1, 2], tenant="acme", priority="batch")


def test_preemption_off_never_fires(model_and_vars):
    """The default config never preempts — _maybe_preempt is a no-op
    before it even looks for a victim."""
    model, variables = model_and_vars
    engine = Engine(model, variables,
                    dataclasses.replace(PCFG, preemption=False))
    sched = Scheduler(engine)
    target = _Live(req=Request(prompt=[1], priority="interactive"),
                   request_id="t", submit_t=0.0, deadline_t=None)
    with sched._lock:
        assert sched._maybe_preempt(target, 0) is False


# ----------------------------------------------------------- preemption
def _run_reference(engine, rid, prompt, max_new):
    """Uninterrupted greedy run of one request -> its token stream."""
    sched = Scheduler(engine)
    _submit(sched, rid, prompt, priority="background", max_new=max_new)
    _drain(sched)
    res = sched.results[rid]
    assert res.finish_reason == FinishReason.LENGTH
    return res.tokens


def _preempt_resume_case(engine):
    """Shared body of the bit-identical preempt -> resume check: a
    background decode is suspended mid-stream by two interactive
    arrivals, demoted (its blocks go to the trie; with the prefix
    cache off nothing is indexed and resume is a cold re-prefill),
    resumed, and must emit exactly the uninterrupted stream. Returns
    the prefix hits the resume took (the interactive prompts are
    shorter than a block, so they can take none)."""
    prompt = [5, 9, 14, 20, 27, 35]
    ref = _run_reference(engine, "ref", prompt, max_new=12)

    sched = Scheduler(engine)
    _submit(sched, "bg", prompt, priority="background", max_new=12)
    sched.step()
    sched.step()    # one block in flight: the second pass brings the first home
    with sched._lock:
        (bg_live,) = sched._live.values()
        assert len(bg_live.tokens) >= 1    # suspended MID-stream
    _submit(sched, "i0", [2, 4, 6], max_new=4)
    _submit(sched, "i1", [3, 5, 7], max_new=4)
    sched.step()
    # The second interactive could only get its slot by suspending the
    # strictly-lower-priority background decode.
    assert sched.preempted_count == 1
    hits_before = engine.pool.prefix_hits
    _drain(sched)
    assert sched.preempted_count == 0
    for rid in ("i0", "i1"):
        assert sched.results[rid].finish_reason == FinishReason.LENGTH
    res = sched.results["bg"]
    assert res.finish_reason == FinishReason.LENGTH
    assert res.tokens == ref, "resume is not bit-identical"
    assert engine.pool.num_free == engine.cfg.max_batch_size
    return engine.pool.prefix_hits - hits_before


@pytest.mark.parametrize("storage", ["float32", "int8"])
def test_preempt_resume_bit_identical_paged(model_and_vars, paged_engine,
                                            storage):
    """Resume through the trie: the victim's full blocks (on the int8
    pool its scale rows with them) are referenced again, not
    recomputed."""
    engine = paged_engine
    if storage == "int8":
        engine = Engine(*model_and_vars,
                        dataclasses.replace(PCFG, kv_dtype="int8"))
    assert _preempt_resume_case(engine) == 1
    engine.pool.leak_check()


def test_preempt_resume_bit_identical_cold(model_and_vars):
    """The other arm of ``_preempt``: with the prefix cache off nothing
    is indexed, the victim's blocks are freed and resume pays a cold
    re-prefill of prompt + emitted tokens."""
    engine = Engine(*model_and_vars,
                    dataclasses.replace(PCFG, prefix_cache=False))
    assert _preempt_resume_case(engine) == 0
    engine.pool.leak_check()


def test_deadline_while_preempted(paged_engine):
    """A deadline keeps ticking while a request sits suspended: it
    retires DEADLINE with the tokens it already emitted, never resumes,
    and leaks nothing."""
    # (every program built first: the block in flight comes home, and
    # meets the deadline check, when the preemption settles it, after
    # the first interactive's prefill)
    _run_reference(paged_engine, "warm", [1, 2, 3], max_new=2)
    sched = Scheduler(paged_engine)
    _submit(sched, "bg", [1, 2, 3, 4, 5, 6], priority="background",
            max_new=30, deadline_s=0.5)
    sched.step()
    _submit(sched, "i0", [2, 4, 6], max_new=3)
    _submit(sched, "i1", [3, 5, 7], max_new=3)
    sched.step()
    assert sched.preempted_count == 1
    time.sleep(0.6)
    sched.step()            # _expire_preempted runs before admission
    res = sched.results["bg"]
    assert res.finish_reason == FinishReason.DEADLINE
    assert 1 <= len(res.tokens) < 30
    assert sched.preempted_count == 0
    _drain(sched)
    assert paged_engine.pool.num_free == PCFG.max_batch_size


def test_preemption_budget_anti_thrash(paged_engine):
    """A victim at its preemption_budget is never suspended again — the
    interactive pick waits for ordinary retirement instead of thrashing
    one request between slot and host tier forever."""
    sched = Scheduler(paged_engine)
    _submit(sched, "bg", [1, 2, 3], priority="background", max_new=6)
    sched.step()
    with sched._lock:
        (victim,) = sched._live.values()
        victim.preempt_count = PCFG.preemption_budget
    _submit(sched, "i0", [2, 4, 6], max_new=3)
    _submit(sched, "i1", [3, 5, 7], max_new=3)
    sched.step()
    assert sched.preempted_count == 0      # budget pinned the victim
    assert sched.queue_depth == 1          # i1 waits its turn
    _drain(sched)
    assert sched.results["bg"].finish_reason == FinishReason.LENGTH
    assert len(sched.results["bg"].tokens) == 6


def test_scheduler_preempt_drill_victim_keeps_decoding(paged_engine):
    """The failed-demotion drill: an injected error at the
    scheduler.preempt fault point vetoes the suspend — the victim
    keeps decoding to completion, the interactive pick waits for a
    slot the ordinary way, and nobody sees an error."""
    faults.install(FaultPlan.parse("scheduler.preempt:error@1x*"))
    sched = Scheduler(paged_engine)
    _submit(sched, "bg", [1, 2, 3], priority="background", max_new=5)
    sched.step()
    _submit(sched, "i0", [2, 4, 6], max_new=3)
    _submit(sched, "i1", [3, 5, 7], max_new=3)
    sched.step()
    assert sched.preempted_count == 0      # every preempt vetoed
    _drain(sched)
    assert faults.active().injected_counts["scheduler.preempt"] >= 1
    for rid, n in (("bg", 5), ("i0", 3), ("i1", 3)):
        res = sched.results[rid]
        assert res.finish_reason == FinishReason.LENGTH
        assert len(res.tokens) == n
    assert paged_engine.pool.num_free == PCFG.max_batch_size


def test_slo_burn_widens_preemption_quota(paged_engine):
    """One admission pass preempts at most ONE victim — unless the
    wired interactive-TTFT SLO is burning, when the quota opens to the
    whole batch (the PR 16 control signal)."""
    sched = Scheduler(paged_engine)
    _submit(sched, "g0", [1, 2, 3], priority="background", max_new=10)
    _submit(sched, "g1", [4, 5, 6], priority="background", max_new=10)
    sched.step()
    with sched._lock:
        assert len(sched._live) == 2
    _submit(sched, "i0", [2, 4, 6], max_new=3)
    _submit(sched, "i1", [3, 5, 7], max_new=3)
    with sched._lock:
        sched._admit()                     # one pass, healthy SLO
    assert sched.preempted_count == 1      # gentle: one per pass
    assert sched.queue_depth == 1
    sched.slo_tracker = types.SimpleNamespace(
        cfg=types.SimpleNamespace(op="<", threshold=1e9),
        observe=lambda ok: None, burn_rate=lambda: 2.0)
    try:
        with sched._lock:
            sched._admit()                 # one pass, burning SLO
        assert sched.preempted_count == 2  # quota opened to the batch
        assert sched.queue_depth == 0
    finally:
        sched.slo_tracker = None
    _drain(sched)
    for rid in ("g0", "g1", "i0", "i1"):
        assert sched.results[rid].finish_reason == FinishReason.LENGTH
    assert len(sched.results["g0"].tokens) == 10
    assert len(sched.results["g1"].tokens) == 10
    assert paged_engine.pool.num_free == PCFG.max_batch_size


# ------------------------------------------------- chaos under churn
def test_chaos_16_requests_under_preemption_churn(model_and_vars,
                                                  tmp_path):
    """The PR 19 acceptance scenario: 16 mixed-priority requests from
    two tenants, open-loop at overcapacity on an int8 paged pool WITH
    a host tier, preemption on and a seeded scheduler.preempt veto in
    the middle of the churn. Every request completes to its full
    length (preempt -> resume is invisible to clients), zero
    slot/block/host leaks, the program set stays frozen at
    ``1 + len(prefill_buckets)``, and preemptions balance resumes."""
    model, variables = model_and_vars
    ccfg = dataclasses.replace(
        PCFG, max_batch_size=3, queue_capacity=4, kv_num_blocks=24,
        kv_dtype="int8", kv_host_blocks=8)
    run_dir = str(tmp_path / "churn")
    obs.start_run(run_dir, meta={"kind": "preemption_churn"})
    try:
        engine = Engine(model, variables, ccfg)
        sched = Scheduler(engine)
        faults.install(FaultPlan.parse("scheduler.preempt:error@2",
                                       seed=19))
        pris = ("background", "background", "background", "interactive")
        issued = 0
        while issued < 16 or sched.has_work():
            while issued < 16 and sched.queue_depth < ccfg.queue_capacity:
                n = 3 if issued % 2 == 0 else 6
                sched.submit(Request(
                    prompt=[(5 * issued + j + 1) % 97 for j in range(n)],
                    max_new_tokens=5, request_id=f"c{issued}",
                    priority=pris[issued % 4],
                    tenant_id="acme" if issued % 2 else "globex"))
                issued += 1
            sched.step()
        results = [sched.results[f"c{i}"] for i in range(16)]
        assert all(r.finish_reason == FinishReason.LENGTH
                   for r in results)
        assert all(len(r.tokens) == 5 for r in results)
        # Churn actually happened, and the books balance: every
        # suspension was resumed (no deadlines, no cancels).
        preempts = obs.counter("serve.preemptions_total").value
        resumes = obs.counter("serve.resumes_total").value
        assert preempts >= 1
        assert preempts == resumes
        assert sched.preempted_count == 0
        # Zero slot/block/host leaks; frozen program set.
        assert engine.pool.num_free == ccfg.max_batch_size
        engine.pool.leak_check()
        stats = engine.compile_stats()
        assert stats["entries"] == stats["misses"] == \
            1 + len(ccfg.prefill_buckets)
    finally:
        faults.clear()
        obs.end_run()
    from check_telemetry_schema import check_run_dir
    assert check_run_dir(run_dir) == []
    from nezha_tpu.obs.report import render_report
    report = render_report(run_dir)
    assert "preemption:" in report


# ------------------------------------------------------------ autoscale
class _FakeHandle:
    def __init__(self, port):
        self.port = port
        self._alive = True

    def alive(self):
        return self._alive

    def terminate(self):
        self._alive = False

    def kill(self):
        self._alive = False

    def wait(self, timeout_s=None):
        return True


class _FakeBackend:
    def __init__(self):
        self.spawned = []

    def spawn(self, rid, port):
        self.spawned.append(rid)
        return _FakeHandle(port)


def _fleet(cfg):
    """A supervisor over fake handles with every replica probed LIVE —
    no monitor thread, tests drive autoscale_tick(now=...) directly."""
    sup = Supervisor(_FakeBackend(), cfg)
    with sup._lock:
        for r in sup._replicas:
            sup._spawn(r)
    for r in sup.replicas():
        sup.mark_probe(r.rid, True, {"queued": 0})
    return sup


def _probe_all(sup, queued):
    for r in sup.replicas():
        if r.state in (STARTING, LIVE):
            sup.mark_probe(r.rid, True, {"queued": queued})


def _wait_stopped(sup, rid, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if sup.replicas()[rid].state == STOPPED:
            return
        time.sleep(0.01)
    raise AssertionError(f"replica {rid} never reached STOPPED")


def test_autoscale_off_by_default():
    cfg = RouterConfig(replicas=2)
    assert cfg.autoscale_enabled is False
    sup = _fleet(cfg)
    _probe_all(sup, queued=100)
    assert sup.autoscale_tick(now=1.0) is None
    assert len(sup.replicas()) == 2


def test_autoscale_config_validation():
    with pytest.raises(ValueError):
        RouterConfig(replicas=2, autoscale_min=1)       # one-sided
    with pytest.raises(ValueError):
        RouterConfig(replicas=2, autoscale_min=3, autoscale_max=4)
    with pytest.raises(ValueError):
        RouterConfig(replicas=2, autoscale_min=0, autoscale_max=3)
    with pytest.raises(ValueError):
        RouterConfig(replicas=2, autoscale_min=2, autoscale_max=1)
    with pytest.raises(ValueError):
        RouterConfig(replicas=2, roles=("prefill", "decode"),
                     autoscale_min=1, autoscale_max=3)
    cfg = RouterConfig(replicas=2, autoscale_min=1, autoscale_max=3)
    assert cfg.autoscale_enabled is True


def test_autoscale_ladder_up_and_down(tmp_path):
    """The elastic ladder: sustained queue pressure scales up one
    replica per action, a sustained fully-idle fleet scales back down,
    bounds hold at both ends, and scale-up after a drain REUSES the
    stopped record (the rid == index invariant the router's ledgers
    rely on)."""
    cfg = RouterConfig(replicas=2, autoscale_min=1, autoscale_max=3,
                       autoscale_sustain_ticks=2,
                       autoscale_cooldown_s=0.0)
    sup = _fleet(cfg)
    _probe_all(sup, queued=10)                 # per-live 5 >= 4: hot
    assert sup.autoscale_tick(now=1.0) is None  # sustain 1/2
    assert sup.autoscale_tick(now=2.0) == "up"
    assert len(sup.replicas()) == 3
    assert sup.replicas()[2].state == STARTING
    assert sup.autoscale_target() == 3
    sup.mark_probe(2, True, {"queued": 0})

    # At the max bound, sustained pressure holds scale.
    _probe_all(sup, queued=10)
    assert sup.autoscale_tick(now=3.0) is None
    assert sup.autoscale_tick(now=4.0) is None
    assert len(sup.replicas()) == 3

    # Fully idle (zero queued, zero in flight) -> drain the highest rid.
    _probe_all(sup, queued=0)
    assert sup.autoscale_tick(now=5.0) is None  # sustain 1/2
    assert sup.autoscale_tick(now=6.0) == "down"
    _wait_stopped(sup, 2)
    assert sup.autoscale_target() == 2
    assert [r.state for r in sup.replicas()[:2]] == [LIVE, LIVE]

    # Scale-up again: the STOPPED record is re-armed, not appended.
    _probe_all(sup, queued=10)
    assert sup.autoscale_tick(now=7.0) is None
    assert sup.autoscale_tick(now=8.0) == "up"
    assert len(sup.replicas()) == 3            # reused, not 4
    assert sup.replicas()[2].state == STARTING
    assert sup.backend.spawned == [0, 1, 2, 2]


def test_autoscale_hysteresis_deadband_and_cooldown():
    """A mixed reading resets BOTH sustain counters (the deadband), so
    a flapping queue never moves the fleet; after an action the
    cooldown gates the next one regardless of pressure."""
    cfg = RouterConfig(replicas=2, autoscale_min=1, autoscale_max=4,
                       autoscale_sustain_ticks=2,
                       autoscale_cooldown_s=0.0)
    sup = _fleet(cfg)
    for t in range(8):       # hot, neutral, hot, neutral ... never acts
        _probe_all(sup, queued=10 if t % 2 == 0 else 1)
        assert sup.autoscale_tick(now=float(t)) is None
    assert len(sup.replicas()) == 2
    assert sup.autoscale_target() == 2

    cfg2 = RouterConfig(replicas=2, autoscale_min=1, autoscale_max=4,
                        autoscale_sustain_ticks=1,
                        autoscale_cooldown_s=100.0)
    sup2 = _fleet(cfg2)
    _probe_all(sup2, queued=10)
    assert sup2.autoscale_tick(now=10.0) == "up"
    _probe_all(sup2, queued=10)
    assert sup2.autoscale_tick(now=11.0) is None    # inside cooldown
    assert sup2.autoscale_tick(now=111.0) == "up"   # cooldown elapsed
    assert len(sup2.replicas()) == 4


def test_supervisor_scale_drill_skips_action():
    """The supervisor.scale drill: an injected error at the decision
    skips that scale action — the fleet holds its size — and pressure
    simply re-evaluates next tick (the sustain counters are NOT
    consumed by a vetoed action)."""
    cfg = RouterConfig(replicas=2, autoscale_min=1, autoscale_max=3,
                       autoscale_sustain_ticks=1,
                       autoscale_cooldown_s=0.0)
    sup = _fleet(cfg)
    faults.install(FaultPlan.parse("supervisor.scale:error@1"))
    _probe_all(sup, queued=10)
    assert sup.autoscale_tick(now=1.0) is None      # vetoed
    assert len(sup.replicas()) == 2
    assert sup.autoscale_target() == 2
    assert faults.active().injected_counts == {"supervisor.scale": 1}
    _probe_all(sup, queued=10)
    assert sup.autoscale_tick(now=2.0) == "up"      # next tick acts
    assert len(sup.replicas()) == 3
