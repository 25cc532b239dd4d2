"""Telemetry-subsystem tests: registry counter/span semantics, the
run-scoped sink's JSONL/summary round trip, the disabled-mode no-op fast
paths, and the frozen-schema validator (tools/check_telemetry_schema.py)."""

import json
import os
import sys

import pytest

from nezha_tpu import obs
from nezha_tpu.obs import registry as obs_registry

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "tools"))
from check_telemetry_schema import check_run_dir  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_obs():
    """Telemetry is process-wide: every test starts disabled and empty,
    and cannot leak an enabled registry into the rest of the suite."""
    obs.end_run()
    obs.REGISTRY.reset()
    yield
    obs.end_run()
    obs.REGISTRY.reset()


# ------------------------------------------------------ registry semantics
def test_counter_gauge_histogram_when_enabled():
    obs.enable()
    try:
        c = obs.counter("c")
        c.inc()
        c.inc(4)
        assert c.value == 5
        assert obs.counter("c") is c  # get-or-create, process-wide
        obs.gauge("g").set(3)
        assert obs.gauge("g").value == 3.0
        h = obs.histogram("h")
        for v in [1.0, 2.0, 3.0, 4.0]:
            h.observe(v)
        s = h.summary()
        assert s["count"] == 4 and s["min"] == 1.0 and s["max"] == 4.0
        assert s["sum"] == 10.0 and 1.0 <= s["p50"] <= 4.0
    finally:
        obs.disable()


def test_histogram_reservoir_bounds_memory():
    obs.enable()
    try:
        h = obs_registry.Histogram("big", cap=64)
        for i in range(10000):
            h.observe(float(i))
        assert h.count == 10000 and h.max == 9999.0
        assert len(h._samples) < 128  # decimated, not unbounded
        assert h.percentile(50) == pytest.approx(5000, rel=0.2)
    finally:
        obs.disable()


def test_histogram_reservoir_unbiased_over_long_runs():
    """The regression the reservoir switch fixes: a distribution shift
    AFTER the reservoir first fills must dominate the percentiles when
    it dominates the stream — the old stride decimation anchored its
    kept set to the startup prefix, biasing long-run percentiles toward
    the first ~cap observations."""
    obs.enable()
    try:
        h = obs_registry.Histogram("shift", cap=256)
        # Fill the reservoir entirely with the startup regime, then
        # stream 20x as many observations of the steady-state regime.
        for _ in range(256):
            h.observe(1.0)
        for _ in range(256 * 20):
            h.observe(100.0)
        assert h.count == 256 * 21
        assert len(h._samples) == 256        # still bounded
        # ~95% of the stream is the late regime: p50 (and even p10)
        # must sit there. Exact streaming stats are unaffected.
        assert h.percentile(50) == 100.0
        s = h.summary()
        assert s["min"] == 1.0 and s["max"] == 100.0
        late_frac = sum(1 for v in h._samples if v == 100.0) / 256
        assert late_frac == pytest.approx(20 / 21, abs=0.08)
        # Seeded per-name RNG: the same stream reproduces the same
        # reservoir (captures are deterministic).
        h2 = obs_registry.Histogram("shift", cap=256)
        for _ in range(256):
            h2.observe(1.0)
        for _ in range(256 * 20):
            h2.observe(100.0)
        assert h2._samples == h._samples
    finally:
        obs.disable()


def test_span_records_duration_and_attrs():
    obs.enable()
    try:
        with obs.span("work", phase="test") as sp:
            sp.set(extra=1)
        rec = obs.REGISTRY.spans[-1]
        assert rec["name"] == "work"
        assert rec["attrs"] == {"phase": "test", "extra": 1}
        assert rec["t1"] >= rec["t0"] and rec["dur_s"] >= 0.0
    finally:
        obs.disable()


def test_span_marks_errors():
    obs.enable()
    try:
        with pytest.raises(ValueError):
            with obs.span("boom"):
                raise ValueError("x")
        assert obs.REGISTRY.spans[-1]["attrs"]["error"] == "ValueError"
    finally:
        obs.disable()


# --------------------------------------------------- disabled-mode no-ops
def test_disabled_mode_is_noop_without_allocation():
    """The zero-overhead contract: disabled spans are ONE shared
    singleton (identity, not equality — no per-call allocation) and
    counters/gauges/histograms never record."""
    assert not obs.enabled()
    assert obs.span("a") is obs.NULL_SPAN
    assert obs.span("b", k=1) is obs.NULL_SPAN  # attrs don't allocate one
    with obs.span("c") as sp:
        assert sp is obs.NULL_SPAN
        sp.set(x=2)  # no-op, chainable
    c = obs.counter("n")
    c.inc(100)
    assert c.value == 0
    obs.gauge("g").set(9)
    assert obs.gauge("g").value == 0.0
    h = obs.histogram("h")
    h.observe(5.0)
    assert h.count == 0 and not h._samples
    obs.record_metrics(1, {"loss": 1.0})
    obs.record_collective("all_reduce", 1024)
    assert obs.REGISTRY.spans == []
    # Instruments exist (get-or-create) but recorded nothing.
    assert all(v == 0 for v in obs.REGISTRY.snapshot()["counters"].values())
    # The layer-boundary primitive: with the registry disabled and no
    # profiler session, obs.annotate holds the shared NULL_SPAN (no
    # registry object allocated), records nothing, and set() chains.
    ann = obs.annotate("serve.sched.pass", live=1)
    assert ann._span is obs.NULL_SPAN
    with ann as entered:
        assert entered is ann and ann.set(queued=2) is ann
    with obs.annotate_step("train.step", 7) as step:
        assert step._span is obs.NULL_SPAN
    assert obs.REGISTRY.spans == []
    assert obs.REGISTRY.snapshot()["num_spans"] == 0


def test_annotate_records_the_registry_span_of_the_same_name_when_enabled():
    """Under a run (registry enabled) obs.annotate also records the
    registry span of the same name, attrs from open and from set() both,
    so spans.jsonl and the profiler's trace carry one vocabulary."""
    obs.enable()
    try:
        with obs.annotate("serve.engine.dispatch", rows=3) as ann:
            ann.set(extra=1)
        with obs.annotate_step("train.step", 7):
            with obs.annotate("train.fetch"):
                pass
    finally:
        obs.disable()
    recs = {r["name"]: r for r in obs.REGISTRY.spans}
    assert recs["serve.engine.dispatch"]["attrs"] == {"rows": 3, "extra": 1}
    assert recs["train.step"]["attrs"] == {"step": 7}
    assert recs["train.fetch"]["dur_s"] <= recs["train.step"]["dur_s"]
    # record=False (an idle poll) keeps the registry half out.
    obs.enable()
    try:
        with obs.annotate("serve.sched.pass", record=False, live=0) as idle:
            assert idle._span is obs.NULL_SPAN
    finally:
        obs.disable()
    assert "serve.sched.pass" not in {r["name"] for r in obs.REGISTRY.spans}


# ------------------------------------------------------- trace context
def test_span_adopts_ambient_trace_and_nests():
    obs.enable()
    try:
        with obs.trace_context("t" * 16):
            with obs.span("outer") as sp:
                assert sp.trace_id == "t" * 16
                assert sp.parent_id is None
                with obs.span("inner") as child:
                    assert child.trace_id == "t" * 16
                    assert child.parent_id == sp.span_id
        outer = [r for r in obs.REGISTRY.spans if r["name"] == "outer"]
        inner = [r for r in obs.REGISTRY.spans if r["name"] == "inner"]
        assert outer[0]["trace_id"] == "t" * 16
        assert inner[0]["parent_id"] == outer[0]["span_id"]
        # outside any context, spans carry no trace fields at all
        with obs.span("plain"):
            pass
        plain = [r for r in obs.REGISTRY.spans if r["name"] == "plain"]
        assert "trace_id" not in plain[0]
    finally:
        obs.disable()


def test_traced_span_gates_on_ambient_trace():
    obs.enable()
    try:
        assert obs.traced_span("x") is obs.NULL_SPAN  # no ambient trace
        with obs.trace_context("a" * 16):
            with obs.traced_span("x"):
                pass
        assert [r["name"] for r in obs.REGISTRY.spans] == ["x"]
    finally:
        obs.disable()
    assert obs.traced_span("x") is obs.NULL_SPAN      # disabled


def test_emit_span_retroactive_record():
    obs.enable()
    try:
        obs.emit_span("later", 10.0, 12.5, trace_id="b" * 16, k=1)
        rec = obs.REGISTRY.spans[-1]
        assert rec["t0"] == 10.0 and rec["dur_s"] == 2.5
        assert rec["trace_id"] == "b" * 16 and rec["span_id"]
        assert rec["attrs"] == {"k": 1}
    finally:
        obs.disable()
    obs.emit_span("noop", 0.0, 1.0)            # disabled: records nothing
    assert obs.REGISTRY.spans[-1]["name"] == "later"


def test_mint_trace_id_sampling_and_disable():
    assert obs.mint_trace_id() is None         # disabled -> no tracing
    obs.enable()
    try:
        tid = obs.mint_trace_id()
        assert isinstance(tid, str) and len(tid) == 16
        obs.set_trace_sample(0.0)
        assert obs.mint_trace_id() is None     # sampled out entirely
        obs.set_trace_sample(1.0)
        assert obs.mint_trace_id() is not None
        with pytest.raises(ValueError):
            obs.set_trace_sample(1.5)
    finally:
        obs.set_trace_sample(1.0)
        obs.disable()


def test_stats_snapshot_matches_pinned_schema():
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", "tools"))
    from check_telemetry_schema import check_stats_payload
    obs.enable()
    try:
        obs.counter("train.steps").inc(3)
        obs.gauge("g").set(2.0)
        obs.histogram("h").observe(1.0)
        payload = obs.stats_snapshot()
    finally:
        obs.disable()
    assert check_stats_payload(payload) == []
    assert payload["kind"] == "replica" and payload["enabled"] is True
    assert payload["counters"]["train.steps"] == 3
    assert payload["histograms"]["h"]["count"] == 1
    # disabled snapshots still validate (enabled: false, curl-able)
    assert check_stats_payload(obs.stats_snapshot()) == []
    # malformed payloads are named, not waved through
    assert check_stats_payload({"kind": "replica"}) != []
    assert check_stats_payload({"kind": "fleet", "ts": 1.0}) != []


# ------------------------------------------------------- run-scoped sink
def test_run_sink_roundtrip(tmp_path):
    d = str(tmp_path / "run")
    obs.start_run(d, meta={"config": "test"})
    obs.counter("train.steps").inc(10)
    obs.record_collective("all_reduce", 4096)
    with obs.span("step0"):
        pass
    obs.record_metrics(5, {"loss": 2.5, "steps_per_sec": 7.0})
    obs.end_run()
    assert not obs.enabled()

    recs = obs.read_metrics(os.path.join(d, "metrics.jsonl"))
    assert recs[0]["step"] == 5 and recs[0]["loss"] == 2.5
    spans = obs.read_metrics(os.path.join(d, "spans.jsonl"))
    assert [s["name"] for s in spans] == ["step0"]
    with open(os.path.join(d, "summary.json")) as f:
        summary = json.load(f)
    assert summary["schema_version"] == 1
    assert summary["counters"]["train.steps"] == 10
    assert summary["collectives"]["all_reduce"]["payload_bytes"] == 4096
    assert summary["histograms"]["metric.steps_per_sec"]["count"] == 1
    assert summary["run"]["config"] == "test"
    assert check_run_dir(d) == []  # the frozen schema accepts it


def test_run_dir_reuse_overwrites_previous_capture(tmp_path):
    """Retrying with the same --run-dir must not mix captures: start_run
    truncates the streams and drops any stale summary, so the dir always
    holds exactly one run."""
    d = str(tmp_path / "run")
    obs.start_run(d)
    obs.record_metrics(1, {"loss": 9.0})
    obs.end_run()
    obs.start_run(d)
    obs.record_metrics(1, {"loss": 1.0})
    with obs.span("only-run-2"):
        pass
    obs.end_run()
    recs = obs.read_metrics(os.path.join(d, "metrics.jsonl"))
    assert [r["loss"] for r in recs] == [1.0]
    spans = obs.read_metrics(os.path.join(d, "spans.jsonl"))
    assert [s["name"] for s in spans] == ["only-run-2"]


def test_start_run_resets_prior_instruments(tmp_path):
    obs.enable()
    obs.counter("stale").inc(3)
    obs.disable()
    obs.start_run(str(tmp_path / "r"))
    obs.end_run()
    with open(tmp_path / "r" / "summary.json") as f:
        assert "stale" not in json.load(f)["counters"]


def test_schema_checker_rejects_drift(tmp_path):
    d = str(tmp_path / "bad")
    os.makedirs(d)
    with open(os.path.join(d, "metrics.jsonl"), "w") as f:
        f.write(json.dumps({"step": "four", "ts": 1.0}) + "\n")
    with open(os.path.join(d, "spans.jsonl"), "w") as f:
        f.write(json.dumps({"name": "x", "t0": 2.0, "t1": 1.0,
                            "dur_s": -1.0, "attrs": {}}) + "\n")
    with open(os.path.join(d, "summary.json"), "w") as f:
        json.dump({"schema_version": 2}, f)
    errors = check_run_dir(d)
    assert any("'step'" in e for e in errors)
    assert any("t1 < t0" in e for e in errors)
    assert any("schema_version" in e for e in errors)
    assert check_run_dir(str(tmp_path / "missing")) != []


# --------------------------------------- absorbed primitives (re-exports)
def test_metrics_logger_close_reopen(tmp_path):
    path = str(tmp_path / "m.jsonl")
    log = obs.MetricsLogger(path)
    log(1, {"a": 1})
    log.close()
    with pytest.raises(ValueError):
        log.log(2, {"a": 2})
    with obs.MetricsLogger(path) as log2:  # reopen appends
        log2(2, {"a": 2})
    assert [r["step"] for r in obs.read_metrics(path)] == [1, 2]


def test_utils_names_are_thin_reexports():
    from nezha_tpu import utils
    assert utils.MetricsLogger is obs.MetricsLogger
    assert utils.StepTimer is obs.StepTimer
    assert utils.Tracer is obs.Tracer


def test_step_timer_lap_windows():
    t = obs.StepTimer(window=4)
    assert t.lap(0.0, 5) is None  # no open window yet
    t.start()
    rate = t.lap(0.0, 10)
    assert rate is not None and rate > 0
    assert t.lap(0.0, 0) is None  # empty window -> no rate
    t.reset()
    assert t.lap(0.0, 3) is None  # reset forgets the window


def test_telemetry_json_recomputes_for_crashed_run(tmp_path, capsys):
    """A run that died before end_run() has only the JSONL streams;
    --json emits the summary recomputed from them, not null."""
    from nezha_tpu.cli.telemetry import main as telemetry_main
    d = str(tmp_path / "crashed")
    os.makedirs(d)
    with open(os.path.join(d, "metrics.jsonl"), "w") as f:
        f.write(json.dumps({"step": 1, "ts": 1.0, "loss": 2.0,
                            "steps_per_sec": 5.0}) + "\n")
        f.write(json.dumps({"step": 2, "ts": 2.0, "loss": 1.5,
                            "steps_per_sec": 7.0}) + "\n")
    with open(os.path.join(d, "spans.jsonl"), "w") as f:
        f.write(json.dumps({"name": "x", "t0": 0.0, "t1": 1.0,
                            "dur_s": 1.0, "attrs": {}}) + "\n")
    assert telemetry_main([d, "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["recomputed"] is True
    assert out["histograms"]["metric.steps_per_sec"]["count"] == 2
    assert out["histograms"]["metric.loss"]["max"] == 2.0
    assert out["slowest_spans"][0]["name"] == "x"


def test_record_collective_bandwidth(tmp_path):
    obs.start_run(str(tmp_path / "bw"))
    obs.record_collective("all_reduce", 1 << 20, seconds=0.01,
                          bus_bytes=float(1 << 20))
    obs.end_run()
    with open(tmp_path / "bw" / "summary.json") as f:
        row = json.load(f)["collectives"]["all_reduce"]
    assert row["calls"] == 1 and row["payload_bytes"] == 1 << 20
    assert row["bus_gbps"]["count"] == 1
    assert row["bus_gbps"]["p50"] == pytest.approx((1 << 20) / 0.01 / 1e9)


def test_adopt_trace_header_rule():
    """THE shared header-adoption rule (obs.adopt_trace_header — one
    definition, used by all three HTTP front ends): the header fills an
    absent trace_id, never overrides a non-empty payload field, and
    leaves non-dict payloads for the caller's validation."""
    p = {"prompt_tokens": [1]}
    obs.adopt_trace_header({obs.TRACE_HEADER: "abc"}, p)
    assert p["trace_id"] == "abc"
    p = {"trace_id": "keep"}
    obs.adopt_trace_header({obs.TRACE_HEADER: "abc"}, p)
    assert p["trace_id"] == "keep"
    p = {}
    obs.adopt_trace_header({}, p)
    assert "trace_id" not in p
    obs.adopt_trace_header({obs.TRACE_HEADER: "abc"}, [1, 2])  # no-op


def test_histogram_weighted_observe_is_n_observations():
    """observe(v, n) is n observations of v under one lock (a decode
    block's tokens that share a per-token latency, PR 34): count and sum
    are those of n calls, the reservoir stays bounded and uniform over
    the whole stream, and the rolling windows count n too."""
    from nezha_tpu.obs import timeseries
    obs.enable()
    store = timeseries.install_windows()
    try:
        one, many = (obs_registry.Histogram(n, cap=256)
                     for n in ("w.one", "w.many"))
        for v, n in ((1.0, 300), (100.0, 256 * 20), (3.0, 1)):
            many.observe(v, n)
            for _ in range(n):
                one.observe(v)
        assert many.count == one.count == 300 + 256 * 20 + 1
        assert many.total == pytest.approx(one.total)
        assert (many.min, many.max) == (one.min, one.max) == (1.0, 100.0)
        assert len(many._samples) == 256
        late = sum(1 for v in many._samples if v == 100.0) / 256
        assert late == pytest.approx(256 * 20 / many.count, abs=0.08)
        assert many.percentile(50) == one.percentile(50) == 100.0
        view = store.view(60.0)["histograms"]
        assert view["w.many"]["count"] == view["w.one"]["count"]
        assert view["w.many"]["sum"] == pytest.approx(view["w.one"]["sum"])
    finally:
        timeseries.uninstall_windows()
        obs.disable()
