"""Distributed request tracing + the live /stats fleet view (ISSUE 12).

Layers under test, bottom up: trace minting/sampling and span adoption
(obs.registry — unit coverage lives in test_obs.py), the scheduler's
per-request lifecycle fragments (queue wait / prefill / park / export /
decode windows / retire), the router's mint-and-forward propagation
across a DISAGGREGATED 1-prefill + 1-decode fleet (the acceptance: every
completed request stitches into a complete timeline whose segment sum
tiles its TTFT exactly, no orphan fragments), partial/orphan-trace
rendering (a killed replica's surviving fragments must render, not
crash), the zero-overhead pins (telemetry disabled, or sampled out,
adds ZERO spans), and the ``GET /stats`` payloads — replica and fleet —
held to the pinned stats schema mid-load.
"""

import json
import os
import sys
import threading
import time
import urllib.request

import pytest

import jax

from nezha_tpu import faults, obs
from nezha_tpu.obs.report import (TRACE_SEGMENTS, render_trace_report,
                                  stitch_run_dir, trace_summary)
from nezha_tpu.serve import Engine, Request, Scheduler, ServeConfig
from nezha_tpu.serve.router import Router, register_router_instruments
from nezha_tpu.serve.scheduler import register_serve_instruments
from nezha_tpu.serve.supervisor import (RouterConfig, Supervisor,
                                        ThreadBackend)

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "tools"))
from check_telemetry_schema import check_run_dir, check_stats_payload  # noqa: E402

# The per-request lifecycle fragments a clean disaggregated migration
# leaves behind, per trace (decode_window is per-dispatch; at least one).
_DISAGG_LIFECYCLE = {"router.request", "serve.queue_wait",
                     "serve.prefill", "serve.park", "serve.kv_export",
                     "serve.kv_install", "serve.decode_window",
                     "serve.decode"}


@pytest.fixture(autouse=True)
def _clean_obs():
    faults.clear()
    obs.end_run()
    obs.REGISTRY.reset()
    obs.set_trace_sample(1.0)
    yield
    faults.clear()
    obs.end_run()
    obs.REGISTRY.reset()
    obs.set_trace_sample(1.0)


@pytest.fixture(scope="module")
def tiny_model():
    from nezha_tpu.cli.train import TINY_GPT2_KW
    from nezha_tpu.models.gpt2 import GPT2, GPT2Config
    model = GPT2(GPT2Config(**TINY_GPT2_KW))
    return model, model.init(jax.random.PRNGKey(0))


def _engine(tiny_model, **kw):
    model, variables = tiny_model
    base = dict(max_batch_size=2, max_len=64, max_prefill_len=16,
                kv_block_size=8, queue_capacity=8)
    base.update(kw)
    return Engine(model, variables, ServeConfig(**base))


def _prompt(n, vocab=512, salt=0):
    return [(7 * i + 3 + 11 * salt) % vocab for i in range(n)]


def _assert_tiles(timeline):
    """The tiling invariant: a complete timeline's segments sum to its
    TTFT exactly — no hidden gap between consecutive milestones."""
    assert timeline["complete"], timeline
    assert set(timeline["segments"]) == set(TRACE_SEGMENTS)
    assert all(v >= 0.0 for v in timeline["segments"].values()), timeline
    assert (sum(timeline["segments"].values())
            == pytest.approx(timeline["ttft_s"], abs=1e-9))


# ------------------------------------------------------- single replica
def test_single_replica_stitched_timelines(tiny_model, tmp_path):
    """A router-less scheduler is its own admission edge: with a run
    active it mints per-request trace ids at submit, and every request
    stitches into a complete timeline whose segment sum matches the
    scheduler-measured TTFT."""
    run_dir = str(tmp_path / "run")
    obs.start_run(run_dir, meta={"kind": "serve"})
    eng = _engine(tiny_model)
    sched = Scheduler(eng)
    for i in range(4):
        sched.submit(Request(prompt=_prompt(5 + 9 * i, salt=i),
                             max_new_tokens=4, request_id=f"t{i}"))
    sched.run_until_idle()
    results = dict(sched.results)
    obs.end_run()

    timelines = {t["request_id"]: t for t in stitch_run_dir(run_dir)}
    assert sorted(timelines) == ["t0", "t1", "t2", "t3"]
    for rid, t in timelines.items():
        _assert_tiles(t)
        assert t["migrated"] is False
        assert t["segments"]["migration_transfer"] == 0.0
        # The stitched TTFT (wall clock, admission edge -> first token)
        # agrees with the scheduler's own measurement (monotonic clock,
        # submit -> first token): same interval, two clocks.
        assert t["ttft_s"] == pytest.approx(results[rid].ttft_s,
                                            abs=0.25)
        assert {"serve.queue_wait", "serve.prefill",
                "serve.prefill.chunk", "serve.decode_window",
                "serve.decode"} <= set(t["span_names"])
    # The capture (trace fields + new span names included) stays
    # schema-valid.
    assert check_run_dir(run_dir) == []
    report = render_trace_report(run_dir)
    assert "4 complete, 0 partial" in report
    assert "prefill_compute" in report and "critical path" in report
    summary = trace_summary(run_dir)
    assert summary["count"] == 4 and summary["complete"] == 4
    assert set(summary["segments"]) == set(TRACE_SEGMENTS)


def test_trace_chain_parents_nest(tiny_model, tmp_path):
    """Fragment lineage: serve.prefill.chunk spans are children of the
    serve.prefill span (parent_id chains), and every fragment of one
    request shares one trace_id."""
    run_dir = str(tmp_path / "run")
    obs.start_run(run_dir)
    eng = _engine(tiny_model)
    sched = Scheduler(eng)
    # 21 tokens -> 2 chunks through the 16-wide prefill (16 + tail)
    sched.submit(Request(prompt=_prompt(21), max_new_tokens=2,
                         request_id="chain"))
    sched.run_until_idle()
    obs.end_run()
    with open(os.path.join(run_dir, "spans.jsonl")) as f:
        spans = [json.loads(ln) for ln in f if ln.strip()]
    traced = [s for s in spans if s.get("trace_id")]
    tids = {s["trace_id"] for s in traced}
    assert len(tids) == 1
    prefill = [s for s in traced if s["name"] == "serve.prefill"]
    chunks = [s for s in traced if s["name"] == "serve.prefill.chunk"]
    assert len(prefill) == 1 and len(chunks) == 2
    assert all(c["parent_id"] == prefill[0]["span_id"] for c in chunks)


# ------------------------------------------------------ zero-span pins
def test_telemetry_disabled_serving_adds_zero_spans(tiny_model):
    """The branch-only no-op pin at the serving layer: with no run
    active a full serve cycle records NOTHING — no spans, no trace ids
    minted, no per-request state retained."""
    assert not obs.enabled()
    eng = _engine(tiny_model)
    sched = Scheduler(eng)
    rid = sched.submit(Request(prompt=_prompt(9), max_new_tokens=3))
    sched.run_until_idle()
    assert sched.results[rid].finish_reason == "length"
    assert obs.REGISTRY.spans == []
    assert obs.mint_trace_id() is None
    assert obs.span("serve.drain") is obs.NULL_SPAN
    assert obs.traced_span("serve.decode") is obs.NULL_SPAN


def test_trace_sampled_out_adds_zero_trace_spans(tiny_model, tmp_path):
    """--trace-sample 0: the run still captures the classic spans
    (serve.prefill, serve.engine.dispatch) but NOT ONE per-request
    trace fragment — tracing cost scales with the sample knob."""
    run_dir = str(tmp_path / "run")
    obs.set_trace_sample(0.0)
    obs.start_run(run_dir)
    eng = _engine(tiny_model)
    sched = Scheduler(eng)
    sched.submit(Request(prompt=_prompt(9), max_new_tokens=3,
                         request_id="s0"))
    sched.run_until_idle()
    obs.end_run()
    with open(os.path.join(run_dir, "spans.jsonl")) as f:
        spans = [json.loads(ln) for ln in f if ln.strip()]
    names = {s["name"] for s in spans}
    assert "serve.prefill" in names and "serve.engine.dispatch" in names
    assert not any(s.get("trace_id") for s in spans)
    assert not names & {"serve.queue_wait", "serve.decode",
                        "serve.decode_window", "serve.prefill.chunk"}
    assert stitch_run_dir(run_dir) == []
    assert trace_summary(run_dir) is None
    assert "no trace fragments" in render_trace_report(run_dir)


def test_router_sampled_out_marker_is_honored(tiny_model, tmp_path):
    """The router is the fleet's SINGLE sampling edge: a routed request
    the router sampled out arrives with trace_id == "" and the replica
    scheduler must honor the verdict — no re-mint, zero trace
    fragments — else --trace-sample P would really trace ~P+(1-P)P of
    traffic with root-less timelines."""
    run_dir = str(tmp_path / "run")
    obs.start_run(run_dir)
    eng = _engine(tiny_model)
    sched = Scheduler(eng)
    sched.submit(Request(prompt=_prompt(9), max_new_tokens=2,
                         request_id="routed-out", trace_id=""))
    sched.run_until_idle()
    obs.end_run()
    with open(os.path.join(run_dir, "spans.jsonl")) as f:
        spans = [json.loads(ln) for ln in f if ln.strip()]
    assert not any(s.get("trace_id") for s in spans)
    assert stitch_run_dir(run_dir) == []
    # the wire parser keeps "" distinct from absent
    from nezha_tpu.cli.serve import _parse_request, build_parser
    args = build_parser().parse_args(["--random-init"])
    req = _parse_request({"prompt_tokens": [1, 2], "trace_id": ""},
                         args, None, None, 512)
    assert req.trace_id == ""
    req = _parse_request({"prompt_tokens": [1, 2]}, args, None, None,
                         512)
    assert req.trace_id is None


def test_router_scrubs_malformed_client_trace_id(tmp_path):
    """A client-supplied non-string trace_id must neither poison the
    span schema nor crash the forward path: the router scrubs it and
    mints its own."""
    from nezha_tpu.serve.supervisor import Supervisor

    run_dir = str(tmp_path / "run")
    obs.start_run(run_dir)
    register_router_instruments()
    cfg = RouterConfig(replicas=1, probe_timeout_s=0.5)

    class _NoSpawnBackend:
        kind = "stub"

        def spawn(self, rid, port):
            raise RuntimeError("never spawned")

    sup = Supervisor(_NoSpawnBackend(), cfg)   # no replicas started
    router = Router(sup, cfg)
    for bad in (123, {"x": 1}, ["y"], None):
        status, obj = router.route(
            {"id": "bad", "prompt_tokens": [1], "trace_id": bad})
        assert status == 503 and obj["error_type"] == "no_live_replicas"
    obs.end_run()
    with open(os.path.join(run_dir, "spans.jsonl")) as f:
        spans = [json.loads(ln) for ln in f if ln.strip()]
    roots = [s for s in spans if s["name"] == "router.request"]
    assert len(roots) == 4
    for s in roots:
        assert isinstance(s["trace_id"], str) and s["trace_id"]
    assert check_run_dir(run_dir) == []


# -------------------------------------------------- disaggregated fleet
def _worker_args(extra=()):
    from nezha_tpu.cli.serve import build_parser
    return build_parser().parse_args(
        ["--random-init", "--model-preset", "tiny", "--max-batch-size",
         "2", "--max-len", "64", "--max-prefill-len", "8",
         "--kv-block-size", "8", "--queue-capacity", "8",
         "--platform", "cpu", *extra])


def _cfg(**kw):
    base = dict(replicas=2, roles=("prefill", "decode"),
                probe_interval_s=0.1, probe_misses=3, route_retries=2,
                retry_backoff_base_s=0.01, retry_backoff_max_s=0.05,
                restart_backoff_base_s=0.05, restart_backoff_max_s=0.5,
                drain_timeout_s=20.0, seed=0)
    base.update(kw)
    return RouterConfig(**base)


def _cluster(cfg):
    sup = Supervisor(ThreadBackend(_worker_args(), drain_timeout_s=20.0,
                                   roles=cfg.roles), cfg)
    router = Router(sup, cfg)
    sup.start()
    assert router.wait_live(cfg.replicas, timeout_s=600), sup.describe()
    return sup, router


def test_disaggregated_fleet_stitch_acceptance(tiny_model, tmp_path):
    """THE acceptance run: 1 prefill + 1 decode replicas with
    migration, concurrent traced load. Every completed request stitches
    into a COMPLETE timeline covering every lifecycle segment (park,
    export, install, both queue waits), with zero orphan fragments; the
    segment sum tiles the stitched TTFT exactly and brackets the
    independently measured latencies; and GET /stats (replica + fleet)
    answers schema-valid payloads MID-LOAD."""
    cfg = _cfg()
    sup, router = _cluster(cfg)
    run_dir = str(tmp_path / "fleet")
    obs.start_run(run_dir, meta={"kind": "tracing_acceptance"})
    register_router_instruments()
    register_serve_instruments()
    N = 6
    results = {}
    lock = threading.Lock()
    next_idx = {"n": 0}
    stats_payloads = []
    try:
        def client():
            while True:
                with lock:
                    i = next_idx["n"]
                    if i >= N:
                        return
                    next_idx["n"] += 1
                t_req = time.monotonic()
                code, obj = router.route(
                    {"id": f"tr-{i}", "prompt_tokens": _prompt(21, salt=i),
                     "max_new_tokens": 4, "seed": i})
                with lock:
                    results[f"tr-{i}"] = (code, obj,
                                          time.monotonic() - t_req)

        threads = [threading.Thread(target=client) for _ in range(2)]
        for t in threads:
            t.start()
        # Mid-load live view: the fleet snapshot (what the router's
        # GET /stats answers) and one replica's own /stats over real
        # HTTP, both while requests are in flight.
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            with lock:
                if results:
                    break
            time.sleep(0.005)
        stats_payloads.append(router.fleet_stats())
        port = sup.replicas()[0].port
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/stats", timeout=30) as resp:
            stats_payloads.append(json.loads(resp.read()))
        for t in threads:
            t.join(timeout=600)
        assert not any(t.is_alive() for t in threads)
    finally:
        obs.end_run()
        router.stop()
        sup.shutdown()

    assert len(results) == N
    assert all(code == 200 for code, _, _ in results.values()), results

    # ---- live /stats: schema-valid mid-load, fleet roll-up present
    fleet, replica = stats_payloads
    assert check_stats_payload(fleet) == []
    assert check_stats_payload(replica) == []
    assert fleet["kind"] == "fleet" and fleet["enabled"] is True
    assert len(fleet["replicas"]) == 2
    assert {r["role"] for r in fleet["replicas"]} == {"prefill",
                                                      "decode"}
    # thread-backed replicas share the process registry, so the serve
    # instruments are visible in every payload
    assert "serve.admitted_total" in fleet["fleet"]["counters"]
    assert "serve.admitted_total" in replica["counters"]
    assert replica["role"] in ("prefill", "decode")

    # ---- stitched timelines: complete, tiled, no orphans
    timelines = {t["request_id"]: t for t in stitch_run_dir(run_dir)}
    assert sorted(timelines) == sorted(results)
    for rid, t in timelines.items():
        _assert_tiles(t)
        assert t["migrated"] is True
        assert t["segments"]["migration_transfer"] > 0.0
        assert _DISAGG_LIFECYCLE <= set(t["span_names"]), t
        code, obj, wall = results[rid]
        # The stitched end-to-end TTFT brackets the independent
        # measurements: at least the decode replica's own TTFT
        # (a strict component of it), at most the whole measured
        # route round trip.
        assert t["ttft_s"] >= obj["ttft_s"] - 0.05, (t, obj)
        assert t["ttft_s"] <= wall + 0.05, (t, wall)
        assert t["finish_reason"] == "length"
    # No orphan fragments: every traced span record stitched into a
    # COMPLETE timeline (partial count 0).
    summary = trace_summary(run_dir)
    assert summary["count"] == N
    assert summary["complete"] == N and summary["partial"] == 0
    assert summary["segments"]["migration_transfer"]["p50"] > 0

    # ---- the capture stays schema-valid end to end
    assert check_run_dir(run_dir) == []
    report = render_trace_report(run_dir)
    assert f"{N} complete, 0 partial" in report
    assert "migration_transfer" in report


def test_partial_and_orphan_trace_rendering(tiny_model, tmp_path):
    """A request whose lifecycle was cut short (parked, puller killed
    before decoding — the drain sweeps the park) must surface as a
    PARTIAL trace, and a lone surviving fragment from a killed
    replica's run dir as an orphan — both rendered, never crashing the
    stitcher, never counted complete."""
    run_dir = str(tmp_path / "partial")
    obs.start_run(run_dir)
    eng = _engine(tiny_model)
    sched = Scheduler(eng)
    sched.submit(Request(prompt=_prompt(21), max_new_tokens=4,
                         request_id="cut", prefill_only=True))
    sched.run_until_idle()
    assert sched.results["cut"].finish_reason == "prefilled"
    assert sched.parked_count == 1
    sched.cancel_remaining()            # the drain sweep: park released
    obs.end_run()

    # A killed decode replica's only surviving fragment, in its own
    # per-replica subdir (the layout a --replicas run-dir writes).
    orphan_dir = os.path.join(run_dir, "replica9")
    os.makedirs(orphan_dir)
    with open(os.path.join(orphan_dir, "spans.jsonl"), "w") as f:
        f.write(json.dumps({
            "name": "serve.kv_install", "t0": 1.0, "t1": 2.0,
            "dur_s": 1.0, "attrs": {"request_id": "ghost"},
            "trace_id": "feedfacefeedface",
            "span_id": "0123456789abcdef"}) + "\n")

    timelines = stitch_run_dir(run_dir)
    assert len(timelines) == 2
    by_rid = {t["request_id"]: t for t in timelines}
    cut = by_rid["cut"]
    assert not cut["complete"]
    assert "serve.park" in cut["span_names"]      # outcome fragment
    assert "serve.decode" in cut["missing"] or \
        "first token" in cut["missing"]
    ghost = by_rid["ghost"]
    assert not ghost["complete"]
    assert ghost["fragments"] == 1
    assert ghost["replicas"] == ["replica9"]
    report = render_trace_report(run_dir)
    assert "partial traces (2" in report
    assert "cut" in report and "ghost" in report
    # the park resolution is recorded
    with open(os.path.join(run_dir, "spans.jsonl")) as f:
        parks = [json.loads(ln) for ln in f
                 if ln.strip() and "serve.park" in ln]
    assert parks and parks[-1]["attrs"]["outcome"] == "drained"
    assert check_run_dir(run_dir) == []


def test_failed_install_does_not_count_as_migration():
    """A ``serve.kv_install`` fragment recorded with an ``error`` attr
    (the pull failed; the router degraded to a retry or local decode)
    must not flip the timeline to migrated=true with a positive
    transfer segment — that would mask exactly the degradation the
    trace report exists to surface. A clean retry fragment alongside
    the failed one still counts."""
    from nezha_tpu.obs.report import trace_timeline

    def frag(name, t0, t1, **attrs):
        return {"name": name, "t0": t0, "t1": t1, "dur_s": t1 - t0,
                "attrs": {"request_id": "r", **attrs}, "_src": "."}

    base = [
        frag("router.request", 0.0, 3.0),
        frag("serve.queue_wait", 0.1, 0.2),
        frag("serve.prefill", 0.2, 1.0),
        frag("serve.decode", 1.8, 3.0, first_token=2.0,
             finish_reason="length"),
    ]
    failed = frag("serve.kv_install", 1.0, 1.5, error="MigrationError")
    t = trace_timeline("a" * 16, base + [failed])
    assert t["complete"], t
    assert t["migrated"] is False
    assert t["segments"]["migration_transfer"] == 0.0
    ok = frag("serve.kv_install", 1.0, 1.6)
    t2 = trace_timeline("a" * 16, base + [failed, ok])
    assert t2["migrated"] is True
    assert t2["segments"]["migration_transfer"] == pytest.approx(0.6)


def test_trace_propagates_per_request_not_per_park_ttl(tiny_model,
                                                      tmp_path):
    """Scheduler-level migration lifecycle: park -> export -> install
    -> ack across two engines stitches export and install fragments
    into ONE trace (the pull reference carries the id), and the park
    span resolves 'acked'."""
    from nezha_tpu.serve import migrate
    run_dir = str(tmp_path / "mig")
    obs.start_run(run_dir)
    a, b = _engine(tiny_model), _engine(tiny_model)
    sa, sb = Scheduler(a), Scheduler(b)
    prompt = _prompt(21)
    tid = "aaaabbbbccccdddd"
    sa.submit(Request(prompt=prompt, max_new_tokens=4, request_id="m",
                      prefill_only=True, trace_id=tid))
    sa.run_until_idle()
    with obs.trace_context(None):       # no ambient leakage either way
        tokens, layers, nbytes = migrate.decode_wire(
            sa.export_parked("m"))
    with obs.trace_context(tid):
        sb.install_migrated(tokens, layers, nbytes)
    assert sa.ack_parked("m") is True
    obs.end_run()
    with open(os.path.join(run_dir, "spans.jsonl")) as f:
        spans = [json.loads(ln) for ln in f if ln.strip()]
    export = [s for s in spans if s["name"] == "serve.kv_export"]
    parks = [s for s in spans if s["name"] == "serve.park"]
    assert export and export[0]["trace_id"] == tid
    assert export[0]["attrs"]["bytes"] > 0
    assert parks and parks[0]["trace_id"] == tid
    assert parks[0]["attrs"]["outcome"] == "acked"
    a.pool.leak_check()
    b.pool.leak_check()


# ----------------------------------------------------- CLI front ends
def test_cli_front_end_stats_and_trace(tmp_path):
    """nezha-serve --replicas 2 end to end: GET /stats on the router
    answers the schema-valid fleet payload over real HTTP, a traced
    POST /generate tagged via the X-Nezha-Trace header at the FLEET
    entry point (the RUNBOOK repro workflow) leaves a stitchable
    complete timeline under the operator's id in the run dir, and
    nezha-telemetry --trace renders it."""
    from nezha_tpu.cli.serve import build_parser, run

    run_dir = str(tmp_path / "router_run")
    args = build_parser().parse_args(
        ["--random-init", "--model-preset", "tiny", "--max-batch-size",
         "2", "--max-len", "48", "--max-prefill-len", "8", "--platform",
         "cpu", "--replicas", "2", "--replica-backend", "thread",
         "--http", "0", "--probe-interval", "0.1", "--drain-timeout",
         "20", "--run-dir", run_dir])
    ready, rc = {}, {}
    ready_evt, drain = threading.Event(), threading.Event()

    def ready_cb(server):
        ready["port"] = server.server_address[1]
        ready_evt.set()

    t = threading.Thread(
        target=lambda: rc.update(rc=run(args, ready_cb=ready_cb,
                                        drain_event=drain)),
        daemon=True)
    t.start()
    assert ready_evt.wait(timeout=300)
    base = f"http://127.0.0.1:{ready['port']}"
    deadline = time.monotonic() + 300
    while time.monotonic() < deadline:
        try:
            with urllib.request.urlopen(f"{base}/healthz",
                                        timeout=5) as r:
                if json.loads(r.read())["replicas_live"] == 2:
                    break
        except Exception:
            pass
        time.sleep(0.1)
    tid = "beadbeadbeadbead"
    req = urllib.request.Request(
        f"{base}/generate",
        data=json.dumps({"id": "cli-trace", "prompt_tokens": [5, 17, 3],
                         "max_new_tokens": 4}).encode(),
        headers={"Content-Type": "application/json",
                 "X-Nezha-Trace": tid})
    with urllib.request.urlopen(req, timeout=120) as r:
        obj = json.loads(r.read())
    assert obj["finish_reason"] == "length"
    with urllib.request.urlopen(f"{base}/stats", timeout=30) as r:
        fleet = json.loads(r.read())
    assert check_stats_payload(fleet) == []
    assert fleet["kind"] == "fleet" and len(fleet["replicas"]) == 2
    drain.set()
    t.join(timeout=300)
    assert not t.is_alive() and rc.get("rc") == 0

    timelines = {t_["request_id"]: t_
                 for t_ in stitch_run_dir(run_dir)}
    assert "cli-trace" in timelines
    _assert_tiles(timelines["cli-trace"])
    # The router honored the header: the timeline stitches under the
    # operator-supplied id, not a router-minted one.
    assert timelines["cli-trace"]["trace_id"] == tid
    from nezha_tpu.cli.telemetry import main as telemetry_main
    assert telemetry_main([run_dir, "--trace"]) == 0


def test_worker_stats_endpoint_and_trace_header(tiny_model, tmp_path):
    """The single-replica HTTP front end (cli/serve.run_http): GET
    /stats answers the replica stats payload, and a request whose
    trace rides ONLY in the X-Nezha-Trace header (no payload field)
    still stitches under that id."""
    from nezha_tpu.cli.serve import build_parser, run_worker

    run_dir = str(tmp_path / "worker")
    args = build_parser().parse_args(
        ["--random-init", "--model-preset", "tiny", "--max-batch-size",
         "2", "--max-len", "48", "--max-prefill-len", "8",
         "--platform", "cpu", "--http", "0", "--drain-timeout", "10",
         "--run-dir", run_dir])
    ready, rc = {}, {}
    ready_evt, drain = threading.Event(), threading.Event()

    def ready_cb(server):
        ready["port"] = server.server_address[1]
        ready_evt.set()

    t = threading.Thread(
        target=lambda: rc.update(rc=run_worker(args, ready_cb=ready_cb,
                                               drain_event=drain)),
        daemon=True)
    t.start()
    assert ready_evt.wait(timeout=600)
    base = f"http://127.0.0.1:{ready['port']}"
    tid = "cafecafecafecafe"
    req = urllib.request.Request(
        f"{base}/generate",
        data=json.dumps({"id": "hdr", "prompt_tokens": [5, 17, 3],
                         "max_new_tokens": 3}).encode(),
        headers={"Content-Type": "application/json",
                 "X-Nezha-Trace": tid})
    with urllib.request.urlopen(req, timeout=600) as r:
        assert json.loads(r.read())["finish_reason"] == "length"
    with urllib.request.urlopen(f"{base}/stats", timeout=30) as r:
        stats = json.loads(r.read())
    assert check_stats_payload(stats) == []
    assert stats["kind"] == "replica" and stats["enabled"] is True
    assert stats["counters"].get("serve.admitted_total") == 1
    drain.set()
    t.join(timeout=300)
    assert not t.is_alive() and rc.get("rc") == 0
    timelines = stitch_run_dir(run_dir)
    assert [t_["trace_id"] for t_ in timelines] == [tid]
    _assert_tiles(timelines[0])


# ----------------------------------------------------------- benchmark
def test_bench_record_trace_block(tmp_path):
    """benchmarks/serving.py --run-dir: the record's ``trace`` block
    carries per-segment p50/p90/p99 over the stitched timelines —
    the numbers nezha-bench's TTFT-decomposition gate compares."""
    sys.path.insert(0, os.path.join(_ROOT, "benchmarks"))
    import serving as bench

    run_dir = str(tmp_path / "bench")
    rec = bench.run(bench.build_parser().parse_args(
        ["--requests", "4", "--concurrency", "2", "--max-batch-size",
         "2", "--max-len", "48", "--max-prefill-len", "8",
         "--max-new-tokens", "3", "--run-dir", run_dir]))
    tr = rec["trace"]
    assert tr is not None
    assert tr["count"] == 4 and tr["complete"] == 4
    assert set(tr["segments"]) == set(TRACE_SEGMENTS)
    for seg in TRACE_SEGMENTS:
        assert {"p50", "p90", "p99"} <= set(tr["segments"][seg])
    assert tr["ttft_s"]["p50"] > 0
    # The nezha-bench gate helper reads exactly these keys.
    from nezha_tpu.cli.bench import _serving_trace_p50s
    p50s = _serving_trace_p50s({"closed_loop_horizon_sweep": rec})
    assert "trace.prefill_compute_p50@h1" in p50s
    assert check_run_dir(run_dir) == []


def test_bench_trace_gate_floor():
    """The TTFT-decomposition gate's noise floor: a segment whose
    BASELINE p50 is sub-millisecond gates nothing (CPU scheduler
    jitter moves microsecond waits past any sane threshold — the gate
    would flap), while a >=1ms segment gates normally in both
    directions."""
    from nezha_tpu.cli.bench import _gate

    def rec(p50s):
        return {"closed_loop_horizon_sweep": {"by_horizon": {"1": {
            "tokens_per_sec": 100.0,
            "trace": {"segments": {
                seg: {"p50": v} for seg, v in p50s.items()}}}}}}

    base = {"serving": {"by_platform": {"cpu": rec(
        {"prefill_compute": 0.010, "decode_wait": 0.0004})}}}
    ok = _gate({"serving": rec({"prefill_compute": 0.011,
                                "decode_wait": 0.4})},
               base, "cpu", 0.30)["serving"]
    # 1000x regression on the 0.4ms-baseline segment: not gated.
    assert "trace.decode_wait_p50@h1" not in ok
    assert ok["trace.prefill_compute_p50@h1"]["ok"] is True
    bad = _gate({"serving": rec({"prefill_compute": 0.020,
                                 "decode_wait": 0.0004})},
                base, "cpu", 0.30)["serving"]
    assert bad["trace.prefill_compute_p50@h1"]["ok"] is False


# ------------------------------------- layer spans on the profiler's clock
# PR 24: obs.annotate puts the host side of each layer on the profiler's
# own timeline (the clock the device ops of an .xplane.pb share), under
# fixed names the benchmark's metric files refer to.
SERVE_LAYER_SPANS = ("serve.sched.pass", "serve.sched.admit",
                     "serve.sched.emit", "serve.engine.prefill",
                     "serve.engine.dispatch", "serve.engine.wait")
TRAIN_LAYER_SPANS = ("train.step", "train.data", "train.dispatch",
                     "train.fetch")


def _profiler_options():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0    # the annotations only, not every call
    opts.host_tracer_level = 2
    return opts


def _host_events(trace_dir, names):
    """[(name, start_ns, end_ns, stats)] of the host events named in
    ``names``, by start time, read back with nothing but jax."""
    import glob
    files = sorted(glob.glob(os.path.join(
        str(trace_dir), "plugins", "profile", "*", "*.xplane.pb")))
    assert files, "the profiler wrote no .xplane.pb"
    data = jax.profiler.ProfileData.from_file(files[-1])
    out = []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in names:
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return sorted(out, key=lambda r: r[1])


def _inside(events, inner, outer):
    """Every ``inner`` event lies within some ``outer`` event."""
    outers = [(a, b) for n, a, b, _ in events if n == outer]
    inners = [(a, b) for n, a, b, _ in events if n == inner]
    return bool(inners) and all(
        any(oa <= a and b <= ob for oa, ob in outers) for a, b in inners)


def test_annotate_puts_bare_name_and_attrs_on_the_profiler_timeline(
        tmp_path):
    """The primitive itself: the host event's name is the bare span name
    (the benchmark's reducer keeps a host event only by exact name), the
    attrs given at open and by set() arrive as its stats, and an
    annotation that began before the session is not recorded."""
    early = obs.annotate("probe.early")
    early.__enter__()
    jax.profiler.start_trace(str(tmp_path),
                             profiler_options=_profiler_options())
    try:
        with obs.annotate("probe.outer", live=3, queued=7) as ann:
            with obs.annotate("probe.inner"):
                time.sleep(0.001)
            assert ann.set(emitted=5) is ann
        with obs.annotate_step("probe.step", 12):
            pass
        early.__exit__(None, None, None)
    finally:
        jax.profiler.stop_trace()
    ev = _host_events(tmp_path, {"probe.early", "probe.outer",
                                 "probe.inner", "probe.step"})
    by_name = {n: s for n, _, _, s in ev}
    assert set(by_name) == {"probe.outer", "probe.inner", "probe.step"}
    assert by_name["probe.outer"] == {"live": 3, "queued": 7, "emitted": 5}
    assert by_name["probe.step"]["step_num"] == 12
    assert _inside(ev, "probe.inner", "probe.outer")


def test_serve_layer_spans_reach_the_profiler_timeline(tiny_model,
                                                       tmp_path):
    """A tiny Scheduler run under a real profiler session, registry
    DISABLED (as in the benchmark): all six serve spans are host events,
    dispatch and wait nest inside the pass, the attrs are there."""
    sched = Scheduler(_engine(tiny_model))
    sched.submit(Request(prompt=_prompt(5), max_new_tokens=2))
    sched.run_until_idle()                      # programs built
    assert not obs.enabled()
    jax.profiler.start_trace(str(tmp_path),
                             profiler_options=_profiler_options())
    try:
        rids = [sched.submit(Request(prompt=_prompt(9, salt=i),
                                     max_new_tokens=4)) for i in range(3)]
        sched.run_until_idle()
    finally:
        jax.profiler.stop_trace()
    assert all(sched.results[r].finish_reason == "length" for r in rids)
    assert obs.REGISTRY.spans == []
    ev = _host_events(tmp_path, set(SERVE_LAYER_SPANS))
    assert {n for n, *_ in ev} == set(SERVE_LAYER_SPANS)
    for inner in SERVE_LAYER_SPANS[1:]:
        assert _inside(ev, inner, "serve.sched.pass"), inner
    stats = {}
    for n, _, _, s in ev:
        stats.setdefault(n, []).append(s)
    assert all({"live", "queued"} <= set(s) for s in stats["serve.sched.pass"])
    # three requests over two slots: the first pass admits two, a later
    # one the third; every admitted request went through one prefill
    assert sum(s["admitted"] for s in stats["serve.sched.admit"]) == 3
    assert len(stats["serve.engine.prefill"]) == 3
    assert all(s["tokens"] == 9 and s["chunks"] >= 1 and "cached" in s
               for s in stats["serve.engine.prefill"])
    assert sum(s["emitted"] for s in stats["serve.sched.emit"]) == 12
    assert all(1 <= s["rows"] <= 2 for s in stats["serve.engine.dispatch"])
    # 9-token prompts decoding positions 9..12 over blocks of 8: every
    # active row holds two table entries (of the table's eight) at each
    # step, which is all the paged decode kernel visits for it
    assert all(s["blocks"] == 2 * s["rows"]
               for s in stats["serve.engine.dispatch"])
    # one wait per dispatch, each after its dispatch closed
    assert len(stats["serve.engine.wait"]) == len(
        stats["serve.engine.dispatch"])


def test_train_layer_spans_reach_the_profiler_timeline(tmp_path):
    """A four-step Trainer.fit whose Tracer opens the window between
    steps 1 and 2, before step 2 is dispatched, and closes it before
    step 4: steps 2 and 3 — every step of the window, its first included
    — are train.step events with their data / dispatch / fetch phases
    inside, and the barrier that drained the device before the window
    closed is one more train.fetch after the last of them."""
    from nezha_tpu import data, optim
    from nezha_tpu.models import MLP
    from nezha_tpu.obs import Tracer
    from nezha_tpu.train.loop import Trainer

    def loss_fn(logits, batch):
        from nezha_tpu import ops
        return ops.softmax_cross_entropy_with_integer_labels(
            logits, batch["label"])

    tracer = Tracer(str(tmp_path), start_step=2, num_steps=2)
    trainer = Trainer(MLP(hidden=(16,)), optim.momentum(0.1), loss_fn,
                      rng=jax.random.PRNGKey(0), log_every=1, tracer=tracer)
    trainer.fit(data.mnist_batches(8, seed=0), steps=4)
    assert tracer._done and not tracer._active
    ev = _host_events(tmp_path, set(TRAIN_LAYER_SPANS))
    assert {n for n, *_ in ev} == set(TRAIN_LAYER_SPANS)
    steps = [s["step_num"] for n, _, _, s in ev if n == "train.step"]
    assert steps == [2, 3]
    last_step_end = max(b for n, _, b, _ in ev if n == "train.step")
    *in_steps, drain = [e for e in ev if e[0] == "train.fetch"]
    assert drain[1] >= last_step_end
    ev.remove(drain)
    for inner in TRAIN_LAYER_SPANS[1:]:
        assert _inside(ev, inner, "train.step"), inner
        assert sum(1 for n, *_ in ev if n == inner) == 2


def test_layer_spans_mirror_into_spans_jsonl_under_a_run_dir(tiny_model,
                                                             tmp_path):
    """With the registry enabled the same names land in spans.jsonl with
    the same attrs — one vocabulary on two clocks — and the capture
    passes the pinned-span schema."""
    run_dir = str(tmp_path / "run")
    obs.start_run(run_dir)
    sched = Scheduler(_engine(tiny_model))
    sched.submit(Request(prompt=_prompt(9), max_new_tokens=3))
    sched.run_until_idle()
    obs.end_run()
    with open(os.path.join(run_dir, "spans.jsonl")) as f:
        spans = [json.loads(ln) for ln in f if ln.strip()]
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s["attrs"])
    assert set(SERVE_LAYER_SPANS) <= set(by_name)
    assert by_name["serve.engine.prefill"][0] == {
        "tokens": 9, "cached": 0, "chunks": 1}
    assert sum(a["emitted"] for a in by_name["serve.sched.emit"]) == 3
    assert check_run_dir(run_dir) == []


def test_idle_passes_record_no_registry_span(tiny_model):
    """A serving loop polls step() every 2 ms while idle. With the
    registry on (--run-dir), an idle pass must leave no span record: not
    a line of spans.jsonl, not a slot of the registry's bounded span
    list (10,000 records: idle polls would fill it in seconds and push
    serve.prefill / checkpoint.* out of summary.json). A pass with work
    mirrors its layer spans as before."""
    sched = Scheduler(_engine(tiny_model))
    obs.enable()
    try:
        for _ in range(100):
            assert sched.step() == 0
        assert obs.REGISTRY.spans == []
        sched.submit(Request(prompt=_prompt(5), max_new_tokens=2))
        sched.run_until_idle()
        busy = {r["name"] for r in obs.REGISTRY.spans}
        n_busy = len(obs.REGISTRY.spans)
        for _ in range(100):
            assert sched.step() == 0
        assert len(obs.REGISTRY.spans) == n_busy
    finally:
        obs.disable()
    assert set(SERVE_LAYER_SPANS) <= busy


def test_host_gap_leaves_out_time_inside_engine_prefill(tiny_model,
                                                        monkeypatch):
    """serve.host_gap_s is the host pass between one block's fetch and
    the next dispatch MINUS the time inside Engine.prefill during it: a
    prefill that takes 0.3 s between two decode blocks must not show."""
    eng = _engine(tiny_model)
    sched = Scheduler(eng)
    sched.submit(Request(prompt=_prompt(5), max_new_tokens=2))
    sched.submit(Request(prompt=_prompt(5, salt=1), max_new_tokens=2))
    sched.run_until_idle()                      # programs built
    body = eng._prefill

    def slow(*a, **kw):
        time.sleep(0.3)
        return body(*a, **kw)

    monkeypatch.setattr(eng, "_prefill", slow)
    obs.enable()
    try:
        sched.submit(Request(prompt=_prompt(9), max_new_tokens=6))
        sched.step()
        sched.step()
        # admitted by the pass that follows a decode block: its prefill
        # sits between that block's fetch and the next dispatch
        sched.submit(Request(prompt=_prompt(9, salt=2), max_new_tokens=2))
        sched.run_until_idle()
        gap = obs.histogram("serve.host_gap_s").summary()
    finally:
        obs.disable()
    assert gap["count"] >= 3
    assert 0.0 <= gap["max"] < 0.3


# ---------------------------------------------------------------------
# PR 34: the children of dispatch / wait / prefill, one span record a
# pass instead of one a row, and the decode windows the report joins.
_DATA = os.path.join(_ROOT, "tests", "data")


def _read_spans(run_dir):
    with open(os.path.join(run_dir, "spans.jsonl")) as f:
        return [json.loads(ln) for ln in f if ln.strip()]


def test_layer_spans_is_the_one_list():
    """obs.LAYER_SPANS names every layer span and the span it opens
    inside; the schema's pins are read from it (from the source: that
    module must load without jax), and every parent is itself a span."""
    from nezha_tpu.analysis import telemetry_schema as ts
    assert ts.layer_spans() == obs.LAYER_SPANS
    assert len(obs.LAYER_SPANS) == 16
    assert set(SERVE_LAYER_SPANS + TRAIN_LAYER_SPANS) <= set(obs.LAYER_SPANS)
    assert all(parent is None or parent in obs.LAYER_SPANS
               for parent in obs.LAYER_SPANS.values())
    assert {n for n in obs.LAYER_SPANS if n.startswith("serve.")} \
        <= ts.PINNED_SPANS
    assert "serve.decode_step" not in ts.PINNED_SPANS


def test_children_nest_inside_their_parents_in_spans_jsonl(tiny_model,
                                                           tmp_path):
    """Under a run dir every child record lies inside a record of the
    parent LAYER_SPANS gives it, and carries its attrs: the blocks a
    pass bound, the bytes it uploaded, a chunk's width, what a prefill
    found cached and bound, and the rows an emit saw and retired."""
    run_dir = str(tmp_path / "run")
    obs.start_run(run_dir)
    sched = Scheduler(_engine(tiny_model))
    # 21 tokens: two chunks through the 16-wide prefill; 9 + 4 tokens
    # cross the 8-token block edge at position 16, so a pass binds one
    rids = [sched.submit(Request(prompt=_prompt(21), max_new_tokens=3)),
            sched.submit(Request(prompt=_prompt(13, salt=1),
                                 max_new_tokens=5))]
    sched.run_until_idle()
    obs.end_run()
    assert all(sched.results[r].finish_reason == "length" for r in rids)
    spans = _read_spans(run_dir)
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    children = [n for n, p in obs.LAYER_SPANS.items()
                if n.startswith("serve.") and p is not None]
    for child in children:
        parents = by_name[obs.LAYER_SPANS[child]]
        assert by_name.get(child), child
        for c in by_name[child]:
            assert any(p["t0"] <= c["t0"] and c["t1"] <= p["t1"]
                       for p in parents), (child, c)
    passes = len(by_name["serve.engine.dispatch"])
    for name in ("serve.engine.bind", "serve.engine.tables",
                 "serve.engine.launch", "serve.engine.wait",
                 "serve.engine.fetch", "serve.sched.emit"):
        assert len(by_name[name]) == passes, name
    binds = [s["attrs"] for s in by_name["serve.engine.bind"]]
    assert all(1 <= a["rows"] <= 2 and a["bound"] >= 0 for a in binds)
    # the 13-token prompt's row writes position 16, the first of a block
    # its prefill did not bind, in one pass
    assert sum(a["bound"] for a in binds) == 1
    # two rows of a 64 / 8 = 8-entry int32 table and the two-row mask
    assert {s["attrs"]["bytes"] for s in by_name["serve.engine.tables"]} \
        == {2 * 8 * 4 + 2}
    assert sorted(s["attrs"]["width"]
                  for s in by_name["serve.engine.prefill.launch"]) \
        == [8, 16, 16]         # 21 = 16 + a tail of 5 in the 8-wide bucket
    assert sorted(s["attrs"]["bound"]
                  for s in by_name["serve.engine.prefill.bind"]) \
        == [2, 3]               # blocks of 8 under 16 and 16 + 8 positions
    emits = [s["attrs"] for s in by_name["serve.sched.emit"]]
    assert sum(a["emitted"] for a in emits) == 8
    assert sum(a["retired"] for a in emits) == 2
    assert all(a["rows"] >= a["retired"] for a in emits)
    assert {s["attrs"]["engine"] for s in by_name["serve.engine.dispatch"]
            + by_name["serve.engine.wait"]} == {sched.engine.engine_id}
    assert check_run_dir(run_dir) == []


PASS_RECORDS = ["serve.engine.bind", "serve.engine.tables",
                "serve.engine.launch", "serve.engine.dispatch",
                "serve.engine.fetch", "serve.engine.wait",
                "serve.sched.emit", "serve.sched.pass"]


@pytest.mark.parametrize("rows", [8, 256])
def test_a_decode_pass_writes_the_same_span_records_whatever_its_rows(
        tiny_model, tmp_path, rows):
    """Registry and sink on, every request traced (the default sample):
    a decode pass with 8 live rows and one with 256 write the same eight
    span records, one a layer span, none a row; and serve.tpot_s still
    counts one observation a token."""
    run_dir = str(tmp_path / "run")
    obs.start_run(run_dir)
    sched = Scheduler(_engine(tiny_model, max_batch_size=256, max_len=32,
                              queue_capacity=256))
    for i in range(rows):
        sched.submit(Request(prompt=_prompt(5, salt=i), max_new_tokens=8))
    sched.step()                    # admits every row, decodes once
    assert len(sched._live) == rows
    assert all(l.trace_id for l in sched._live.values())
    tpot0 = obs.histogram("serve.tpot_s").count
    n0 = len(obs.REGISTRY.spans)
    assert sched.step() == rows
    written = [s["name"] for s in obs.REGISTRY.spans[n0:]]
    assert written == PASS_RECORDS
    assert obs.histogram("serve.tpot_s").count == tpot0 + rows
    sched.run_until_idle()
    n_all = len(obs.REGISTRY.spans)
    obs.end_run()
    assert len(_read_spans(run_dir)) == n_all
    t = stitch_run_dir(run_dir)
    assert len(t) == rows
    assert all(x["decode_windows"]["count"] == 8 for x in t)


def test_buffered_sink_holds_every_record(tmp_path, monkeypatch):
    """spans.jsonl is written in whole lines, a buffer at a time: nothing
    reaches the file for a few records, everything is there after
    end_run(), a burst past the buffer's size flushes itself, and after
    a silence longer than the flush interval the next record takes the
    waiting ones with it. Events still flush one by one."""
    from nezha_tpu.obs import sink as sink_mod
    run_dir = str(tmp_path / "run")
    path = os.path.join(run_dir, "spans.jsonl")
    lines = lambda: sum(1 for _ in open(path))
    obs.start_run(run_dir)
    for i in range(5):
        obs.emit_span("probe.span", 1.0, 2.0, i=i)
    obs.record_event("watchdog.stall", severity="warning", idle_s=1.0)
    assert lines() == 0
    assert sum(1 for _ in open(os.path.join(run_dir, "events.jsonl"))) == 1
    for i in range(5, 2000):
        obs.emit_span("probe.span", 1.0, 2.0, i=i)
    flushed = lines()
    assert 0 < flushed < 2000       # 64 KB at a time, whole lines
    assert all(json.loads(ln)["name"] == "probe.span" for ln in open(path))
    monkeypatch.setattr(sink_mod, "SPAN_FLUSH_SECONDS", 0.05)
    time.sleep(0.06)
    obs.emit_span("probe.span", 1.0, 2.0, i=2000)
    assert lines() == 2001
    obs.emit_span("probe.span", 1.0, 2.0, i=2001)
    obs.end_run()
    assert [json.loads(ln)["attrs"]["i"] for ln in open(path)] \
        == list(range(2002))


def test_joined_decode_windows_equal_the_recorded_per_row_records():
    """tests/data/serve_spans_pr33.jsonl is the spans.jsonl of a tiny
    two-slot run recorded with the parent of PR 34, which wrote one
    serve.decode_window record a traced row a pass beside the pass's own
    records. With those 23 records taken out, the report's join gives
    every request the same windows back: as many, each the pass (dispatch
    start to fetch end) inside the interval the scheduler had clocked
    around it."""
    from nezha_tpu.obs.report import stitch_traces, trace_timeline
    with open(os.path.join(_DATA, "serve_spans_pr33.jsonl")) as f:
        recorded = [json.loads(ln) for ln in f if ln.strip()]
    per_row = {}
    for s in recorded:
        if s["name"] == "serve.decode_window":
            per_row.setdefault(s["trace_id"], []).append(s)
    assert sum(len(v) for v in per_row.values()) == 23
    kept = [s for s in recorded if s["name"] != "serve.decode_window"]
    traces = stitch_traces(kept)
    assert set(traces) == set(per_row) and len(traces) == 5
    for tid, frags in traces.items():
        joined = [f for f in frags if f["name"] == "serve.decode_window"]
        want = sorted(per_row[tid], key=lambda s: s["t0"])
        assert len(joined) == len(want), tid
        for got, rec in zip(joined, want):
            assert got["derived"] and got["trace_id"] == tid
            assert got["attrs"]["request_id"] == rec["attrs"]["request_id"]
            assert rec["t0"] <= got["t0"] <= got["t1"] <= rec["t1"]
            assert rec["dur_s"] - got["dur_s"] < 2e-3
        t = trace_timeline(tid, frags)
        assert "serve.decode_window" in t["span_names"]
        assert t["decode_windows"]["count"] == len(want) == t["tokens"]
        assert t["decode_windows"]["slowest_s"] == max(
            g["dur_s"] for g in joined)


def test_joined_windows_keep_two_engines_of_one_registry_apart(tiny_model,
                                                               tmp_path):
    """Thread-backend replicas share one registry and one spans.jsonl:
    the pass records say which engine ran them, so a request's windows
    are its own engine's passes only, however the two interleave."""
    run_dir = str(tmp_path / "run")
    obs.start_run(run_dir)
    a, b = Scheduler(_engine(tiny_model)), Scheduler(_engine(tiny_model))
    assert a.engine.engine_id != b.engine.engine_id
    a.submit(Request(prompt=_prompt(5), max_new_tokens=3, request_id="a"))
    b.submit(Request(prompt=_prompt(7, salt=1), max_new_tokens=6,
                     request_id="b"))
    while a.has_work() or b.has_work():
        a.step()
        b.step()
    obs.end_run()
    t = {x["request_id"]: x for x in stitch_run_dir(run_dir)}
    assert t["a"]["decode_windows"]["count"] == 3
    assert t["b"]["decode_windows"]["count"] == 6
    report = render_trace_report(run_dir)
    assert "decode 3 pass(es)" in report and "decode 6 pass(es)" in report
