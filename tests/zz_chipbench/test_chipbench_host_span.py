"""The ``host_span`` reader and the three metrics that use it (PR 24): the
program's own layer spans, read off the profiler's clock. Hand-made events
whose answers are known here; a slice recorded on the chip below. The
metrics are in waiting: no cell lists them and no driver keeps the spans
yet, so ``chipbench/layer_spans.py`` reads them from a kept trace."""

import json
import os
import time

import pytest

from chipbench import layer_spans, manifest
from chipbench.obs import Obs
from chipbench.trace import reduce
from chipbench.trace.reduce import Event, Trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1e6
NEW_METRICS = layer_spans.METRICS


def ev(name, start_ms, dur_ms):
    return Event(name, start_ms * MS, dur_ms * MS)


def _obs(host):
    obs = Obs()
    obs.trace = Trace({}, sorted(host, key=lambda e: e.start_ns), {})
    return obs


def _metric_files(names=NEW_METRICS):
    out = []
    for name in names:
        with open(os.path.join(manifest.ROOT, "metrics", f"{name}.json")) as f:
            out.append(json.load(f))
    return out


@pytest.fixture(scope="module")
def read():
    return manifest.load_reader("host_span")


def test_a_span_minus_what_lies_inside_it(read):
    """Two passes with unequal waits: each pass counts for its own length
    less the waits INSIDE it; a wait outside any pass, or one that only
    overlaps a pass's edge, subtracts nothing."""
    obs = _obs([
        ev("serve.sched.pass", 0, 100), ev("serve.engine.wait", 10, 90),
        ev("serve.sched.pass", 200, 50), ev("serve.engine.wait", 210, 20),
        ev("serve.engine.wait", 215, 5),      # a second fetch, same pass
        ev("serve.engine.wait", 160, 30),     # between the passes
        ev("serve.engine.wait", 245, 10),     # hangs over the pass's end
        ev("serve.engine.dispatch", 1, 4)])   # not in `minus`
    params = {"span": "serve.sched.pass", "minus": ["serve.engine.wait"],
              "scale": 1e-6}
    # pass 1: 100 - 90 = 10 ms; pass 2: 50 - 20 - 5 = 25 ms
    assert read(obs, {**params, "q": 0}) == pytest.approx(10.0)
    assert read(obs, {**params, "q": 100}) == pytest.approx(25.0)
    assert read(obs, {**params, "q": 50}) == pytest.approx(17.5)
    # without `minus` the span's own duration; scale defaults to 1 (ns)
    assert read(obs, {"span": "serve.sched.pass", "q": 100}) == \
        pytest.approx(100 * MS)


def test_no_event_of_that_name_reads_as_nothing(read):
    """The parent of PR 24 has no such span, a CPU rehearsal prints no
    span metric, and an untraced run has no trace at all: None each time,
    so the metric is left out of the line and nothing raises."""
    params = {"span": "train.step", "minus": ["train.fetch"], "q": 50}
    assert read(Obs(), params) is None                      # not traced
    assert read(_obs([]), params) is None
    assert read(_obs([ev("scheduler.step", 0, 5),
                      ev("train.fetch", 1, 2)]), params) is None
    metrics = manifest.read_metrics(_metric_files(), _obs(
        [ev("scheduler.step", 0, 5), ev("engine.step", 1, 3)]))
    assert metrics == {}


def test_the_three_metric_files_read_their_spans():
    serve = _obs([
        ev("scheduler.step", 0, 750), ev("serve.sched.pass", 0.1, 749),
        ev("serve.sched.admit", 0.2, 0.1), ev("engine.step", 0.5, 742),
        ev("serve.engine.dispatch", 0.6, 2.5),
        ev("serve.engine.wait", 3.2, 739), ev("serve.sched.emit", 743, 5.5),
        ev("serve.sched.admit", 748.6, 0.2)])
    got = manifest.read_metrics(_metric_files(), serve)
    assert set(got) == {"sched.host_ms_per_pass", "engine.dispatch_ms_p50"}
    assert got["sched.host_ms_per_pass"] == {
        "value": pytest.approx(10.0), "unit": "ms"}
    assert got["engine.dispatch_ms_p50"]["value"] == pytest.approx(2.5)
    train = _obs(
        [ev("train.step", 150 * i, 2.0) for i in range(4)]
        + [ev("train.step", 600, 148), ev("train.data", 600.1, 0.4),
           ev("train.dispatch", 600.6, 1.2), ev("train.fetch", 602, 145.5)])
    got = manifest.read_metrics(_metric_files(), train)
    # four steps of 2 ms and the log-boundary step: 148 - 145.5 = 2.5 ms
    assert got == {"trainer.host_ms_per_step": {
        "value": pytest.approx(2.0), "unit": "ms"}}


def test_gaps_go_to_the_innermost_program_span():
    """With the program's spans among the kept host events an idle gap's
    owner is the innermost span open at its middle."""
    ops = [Event("%fusion.1 = f32[8]{0} fusion()", 0, 10 * MS),
           Event("%fusion.2 = f32[8]{0} fusion()", 13 * MS, 7 * MS),
           Event("%fusion.3 = f32[8]{0} fusion()", 24 * MS, 1 * MS)]
    host = [ev("train.step", 9, 6), ev("train.data", 10.5, 1.5),
            ev("train.step", 16, 9), ev("train.fetch", 18, 6.5)]
    gaps = dict(reduce.gaps_by_annotation(Trace({0: ops}, host, {})))
    assert gaps == {"train.data": pytest.approx(0.003),
                    "train.fetch": pytest.approx(0.004)}


def test_the_metrics_in_waiting_load_and_no_cell_lists_them():
    """The three metrics are files only, like the cell in waiting: a cell
    reports a metric when its own file lists it and the driver's
    ANNOTATIONS keep the span, and a PR that changes the program may edit
    neither (PERF.md, open questions). Their files must stay loadable, so
    that admitting them is names appended and nothing else."""
    with open(os.path.join(manifest.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    e2e = {m["name"] for m in bench["end_to_end"]}
    listed = {m["name"] for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"]}
    for m in _metric_files():
        assert m["name"] not in listed
        assert m["source"] == "program_span" and m["unit"] == "ms"
        assert m["moves"] in e2e and m["layer"] in layers
        assert m["params"]["span"] in layer_spans.SPANS
        assert set(m["params"].get("minus", ())) <= set(layer_spans.SPANS)
        manifest.load_reader(m["reader"])
    for w in bench["workloads"]:
        with open(os.path.join(manifest.ROOT, "cells",
                               f"{w['name']}.json")) as f:
            assert not set(json.load(f)["per_layer"]) & set(NEW_METRICS)


def test_layer_spans_reads_a_kept_trace_by_hand(tmp_path):
    """``layer_spans.read`` on a real ``.xplane.pb``: a profiler session
    on the CPU around spans opened by the program's own primitive. The
    metrics come out under their names in milliseconds; a CPU trace has
    no device plane, so there are no gaps to own."""
    import jax
    from nezha_tpu import obs as program_obs

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for step in range(3):
            with program_obs.annotate_step("train.step", step):
                with program_obs.annotate("train.dispatch"):
                    time.sleep(0.002)
                with program_obs.annotate("train.fetch"):
                    time.sleep(0.01)
        with program_obs.annotate("not.a.layer.span"):
            pass
    finally:
        jax.profiler.stop_trace()
    got = layer_spans.read(reduce.find_xplane(str(tmp_path)))
    assert got["host_events"] == {"train.step": 3, "train.dispatch": 3,
                                  "train.fetch": 3}
    assert set(got["metrics"]) == {"trainer.host_ms_per_step"}
    host_ms = got["metrics"]["trainer.host_ms_per_step"]
    # a step less its fetch: the 2 ms dispatch and whatever the box adds
    assert host_ms["unit"] == "ms" and 2.0 <= host_ms["value"] < 500.0
    assert got["idle_gaps"] == {} and got["busy_s"] == 0.0


# ------------------------------------------------ recorded on the chip
SLICES = "v5e_pr24_layer_span_slices"


def _recorded(name):
    with open(os.path.join(DATA, f"{SLICES}.json")) as f:
        trace = Trace.from_json(json.load(f)["slices"][name])
    with open(os.path.join(DATA, f"{SLICES}.expect.json")) as f:
        return trace, json.load(f)["slices"][name]


@pytest.mark.parametrize("name", ["batch_gen", "train", "train_drained",
                                  "bert_x4"])
def test_recorded_chip_trace_carries_the_programs_spans(name):
    """Slices of the first traced runs with the program's own spans (my
    chip runs, PR 24): every kept host event of the traced window, and the
    device ops around one turnaround (a decode step's end and the pass
    that follows it; a log boundary of the train loop — from the first
    runs and, ``train_drained`` / ``bert_x4``, from the final tree's,
    whose profile window opens and closes on a drained device). The new
    metrics read the recorded numbers, each idle gap has a program span
    for an owner, and the kernels carry their names while the patterns
    PR 22 wrote still find them."""
    trace, want = _recorded(name)
    chips = want["chips"]
    assert sorted(trace.device_ops) == list(range(chips))
    assert sum(map(len, trace.device_ops.values())) == want["events"]
    obs = Obs()
    obs.trace = trace
    obs.set("chips", chips)
    got = manifest.read_metrics(_metric_files(), obs)
    assert {k: v["value"] for k, v in got.items()} == pytest.approx(
        want["metrics"], rel=1e-9)
    assert all(0 < v < want["step_ms"] for v in want["metrics"].values())
    counts = {}
    for e in trace.host:
        counts[e.name] = counts.get(e.name, 0) + 1
    assert counts == want["host_events"]
    s = reduce.summary(trace, chips)
    assert s["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert s["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    gaps = reduce.gaps_by_annotation(trace)
    assert gaps[0][0] == want["heaviest_gap_owner"]
    assert gaps[0][1] == pytest.approx(want["heaviest_gap_s"], rel=1e-9)
    assert "(no annotation)" not in dict(gaps)
    kinds = [n for n, _ in reduce.op_seconds(trace)]
    for kernel in want["kernels"]:
        assert any(k.startswith(kernel + " ") for k in kinds), kernel
    metrics = os.path.join(manifest.ROOT, "metrics")
    for metric, (seconds, events) in want["patterns"].items():
        with open(os.path.join(metrics, f"{metric}.json")) as f:
            patterns = json.load(f)["params"]["patterns"]
        got_s, got_n = reduce.matching_seconds(trace, patterns, chips)
        assert got_n == events > 0, metric
        assert got_s == pytest.approx(seconds, rel=1e-9), metric


def test_recorded_pass_is_mostly_the_wait_and_the_rest_is_the_hosts():
    """The arithmetic on one recorded pass, by hand: a 1,117 ms pass whose
    fetch blocked for 1,106 ms leaves 11.05 ms of host time — dispatch,
    the emit loop and an admission with its prefill."""
    trace, _ = _recorded("batch_gen")
    passes = [e for e in trace.host if e.name == "serve.sched.pass"]
    waits = [e for e in trace.host if e.name == "serve.engine.wait"]
    assert len(passes) == len(waits) == 5
    rest = sorted((p.dur_ns - w.dur_ns) / MS for p, w in zip(passes, waits))
    assert all(p.start_ns <= w.start_ns and w.end_ns <= p.end_ns
               for p, w in zip(passes, waits))
    assert rest[2] == pytest.approx(11.049098)
    assert 4.0 < rest[0] < rest[-1] < 20.0
