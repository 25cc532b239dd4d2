"""A serving pass span by span (PR 34): the ``idle_per_span`` reader, the six
metric files in waiting, and ``chipbench/pass_spans.py``'s self times and
idle-by-overlap. Hand-made events whose answers are worked out in the
comments; then a slice of a trace recorded on the chip
(``data/v5e_pr34_pass_span_slice.json``, dumped with ``reduce.py --dump``)
against numbers read off it by hand (``.expect.json``). The metrics are in
waiting: no cell lists them and the drivers keep only six of the sixteen
spans, so ``pass_spans.py`` reads them from a kept trace (ROADMAP Reach
B2)."""

import glob
import json
import os

import pytest

from chipbench import manifest, pass_spans
from chipbench.obs import Obs
from chipbench.trace import reduce
from chipbench.trace.reduce import Event, Trace
from nezha_tpu.obs import LAYER_SPANS

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
US = 1e3
NEW_METRICS = ("device.idle_ms_per_pass", "engine.bind_ms_p50",
               "engine.tables_ms_p50", "engine.launch_ms_p50",
               "engine.fetch_ms_p50", "sched.emit_ms_p50")


def ev(name, start_us, dur_us):
    return Event(name, start_us * US, dur_us * US)


def _metric_files(names=NEW_METRICS):
    out = []
    for name in names:
        with open(os.path.join(manifest.ROOT, "metrics", f"{name}.json")) as f:
            out.append(json.load(f))
    return out


def two_passes():
    """Two decode passes of 10 ms on one chip, times in us. The device
    runs a step 0-8,000, 10,000-18,000 and 20,000-21,000 (the window's
    last op), so it idles 8,000-10,000 and 18,000-20,000: 2 ms a
    turnaround, 4 ms in all.

    pass 1 (0-9,950): dispatch 100-1,400 (bind 150-450, tables 450-650,
    launch 650-1,350), wait 1,450-8,400 (fetch 8,100-8,380), emit
    8,450-9,050. Its turnaround's idle 8,000-10,000 runs through
    wait's own tail 8,000-8,100 (100; the first fetch returned at
    8,100), fetch 8,100-8,380 (280), wait's tail 8,380-8,400 (20), the
    pass's own code 8,400-8,450 (50), emit (600), the pass 9,050-9,950
    less admit 9,100-9,130 (870 + 30), and no span 9,950-10,000 (50).
    pass 2 (10,050-19,900) is pass 1 shifted by 10,050 less the ops'
    10,000: its idle 18,000-20,000 starts 50 us EARLIER in the pass."""
    ops = [ev("%fusion.1 = f32[8]{0} fusion()", 0, 8000),
           ev("%fusion.1 = f32[8]{0} fusion()", 10000, 8000),
           ev("%fusion.2 = f32[8]{0} fusion()", 20000, 1000)]
    host = []
    for t in (0, 10050):
        host += [ev("serve.sched.pass", t, 9950 if t == 0 else 9850),
                 ev("serve.engine.dispatch", t + 100, 1300),
                 ev("serve.engine.bind", t + 150, 300),
                 ev("serve.engine.tables", t + 450, 200),
                 ev("serve.engine.launch", t + 650, 700),
                 ev("serve.engine.wait", t + 1450, 6950),
                 ev("serve.engine.fetch", t + 8100, 280),
                 ev("serve.sched.emit", t + 8450, 600),
                 ev("serve.sched.admit", t + 9100, 30)]
    host.append(ev("serve.sched.pass", 19950, 30))      # an idle poll
    return Trace({0: ops}, sorted(host, key=lambda e: e.start_ns), {})


def _obs(trace):
    obs = Obs()
    obs.trace = trace
    return obs


# ------------------------------------------------------------ the reader
def test_idle_per_span_counts_the_passes_that_decoded():
    read = manifest.load_reader("idle_per_span")
    params = {"per": "serve.sched.pass", "holding": "serve.engine.dispatch",
              "owners": None, "scale": 1e-6}
    # 4 ms idle over the TWO passes that hold a dispatch: the idle poll
    # at 19,950 is a serve.sched.pass too and must not dilute it
    assert read(_obs(two_passes()), params) == pytest.approx(2.0)
    # by owner: the midpoint rule gives gap 1 (middle 9,000) to emit and
    # gap 2 (middle 19,000 = pass 2's 8,950) to emit too: 4 ms; nothing
    # to dispatch
    assert read(_obs(two_passes()),
                {**params, "owners": ["serve.sched.emit"]}) \
        == pytest.approx(2.0)
    assert read(_obs(two_passes()),
                {**params, "owners": ["serve.engine.dispatch"]}) == 0.0
    # scale defaults to 1: nanoseconds
    assert read(_obs(two_passes()), {k: v for k, v in params.items()
                                     if k != "scale"}) \
        == pytest.approx(2e6)


def test_idle_per_span_reads_nothing_where_there_is_nothing():
    """An untraced run, a trace without device ops (a CPU rehearsal), a
    driver that keeps neither span (GPT-2's today), and a window whose
    passes all polled: None each time, and the metric leaves the line."""
    read = manifest.load_reader("idle_per_span")
    params = {"per": "serve.sched.pass", "holding": "serve.engine.dispatch"}
    assert read(Obs(), params) is None
    full = two_passes()
    assert read(_obs(Trace({}, full.host, {})), params) is None
    outer = [ev("scheduler.step", 0, 9950), ev("engine.step", 100, 8300)]
    assert read(_obs(Trace(full.device_ops, outer, {})), params) is None
    polls = [e for e in full.host if e.name == "serve.sched.pass"]
    assert read(_obs(Trace(full.device_ops, polls, {})), params) is None
    got = manifest.read_metrics(_metric_files(),
                                _obs(Trace(full.device_ops, outer, {})))
    assert got == {}


# ----------------------------------------------------- the metric files
def test_the_six_metric_files_read_the_spans_they_name():
    got = manifest.read_metrics(_metric_files(), _obs(two_passes()))
    assert {k: v["value"] for k, v in got.items()} == pytest.approx({
        "device.idle_ms_per_pass": 2.0, "engine.bind_ms_p50": 0.3,
        "engine.tables_ms_p50": 0.2, "engine.launch_ms_p50": 0.7,
        "engine.fetch_ms_p50": 0.28, "sched.emit_ms_p50": 0.6})
    assert {v["unit"] for v in got.values()} == {"ms"}


def test_every_span_a_metric_file_names_is_a_layer_span():
    """The names are the program's, in one place: a metric file that
    reads a span (reader host_span or idle_per_span, these six and PR
    24's three) names only members of obs.LAYER_SPANS."""
    seen = set()
    for path in glob.glob(os.path.join(manifest.ROOT, "metrics", "*.json")):
        with open(path) as f:
            m = json.load(f)
        if m["reader"] not in ("host_span", "idle_per_span"):
            continue
        p = m["params"]
        named = [p.get("span"), p.get("per"), p.get("holding"),
                 *p.get("minus", ()), *(p.get("owners") or ())]
        assert all(n in LAYER_SPANS for n in named if n), m["name"]
        seen.add(m["name"])
    assert set(NEW_METRICS) <= seen and len(seen) == 9
    assert seen == set(pass_spans.METRICS)
    for m in _metric_files():
        assert m["source"] == "program_span" and m["better"] == "lower"
        assert m["moves"] == "out_tok_s" and m["kind"] == "per_layer"
        assert m["layer"] in ("device", "engine", "scheduler")
        assert "In waiting (PR 34)" in m["what"]


def test_no_cell_lists_the_six_yet():
    """A cell's file and BENCHMARK.json are the benchmark's: the entries
    are a `benchmark` issue's to add (ROADMAP Reach B2)."""
    for path in glob.glob(os.path.join(manifest.ROOT, "cells", "*.json")):
        with open(path) as f:
            cell = json.load(f)
        assert not set(NEW_METRICS) & set(cell["per_layer"]), path
    with open(os.path.join(manifest.REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert not set(NEW_METRICS) & {m["name"] for m in bench["per_layer"]}


# ------------------------------------------- self time and the overlap
def test_self_time_is_a_span_less_its_children_by_the_parent_map():
    table = pass_spans.span_table(two_passes(), LAYER_SPANS)
    # dispatch 1,300 less bind 300, tables 200, launch 700
    assert table["serve.engine.dispatch"] == pytest.approx(
        {"count": 2, "p50_ms": 1.3, "self_p50_ms": 0.1})
    # wait 6,950 less fetch 280
    assert table["serve.engine.wait"]["self_p50_ms"] == pytest.approx(6.67)
    # a pass less dispatch, wait, emit, admit: 9,950 - 8,880 = 1,070 and
    # 9,850 - 8,880 = 970; the poll is its own 30: the median of three
    assert table["serve.sched.pass"]["count"] == 3
    assert table["serve.sched.pass"]["self_p50_ms"] == pytest.approx(0.97)
    assert table["serve.engine.launch"] == pytest.approx(
        {"count": 2, "p50_ms": 0.7, "self_p50_ms": 0.7})


def test_idle_goes_to_self_time_by_overlap_not_whole_to_a_midpoint():
    trace = two_passes()
    got = dict(pass_spans.idle_by_overlap(trace, LAYER_SPANS))
    # gap 1 as the docstring has it; gap 2 (18,000 is pass 2's 7,950)
    # has 50 us more of wait's own tail, pass 2 ends at 19,900 (770 of
    # its own code after emit, not 870), and the idle poll's 30 us are a
    # serve.sched.pass's self time too: no span 19,900-19,950 and
    # 19,980-20,000
    want_us = {"serve.engine.wait": 120 + 170,
               "serve.engine.fetch": 280 + 280,
               "serve.sched.emit": 600 + 600,
               "serve.sched.admit": 30 + 30,
               "serve.sched.pass": (50 + 870) + (50 + 770 + 30),
               pass_spans.NO_SPAN: 50 + 70}
    assert {k: v * 1e6 for k, v in got.items()} == pytest.approx(want_us)
    assert sum(got.values()) == pytest.approx(4e-3)
    # the midpoint rule gives both gaps whole to emit
    assert reduce.gaps_by_annotation(trace) == [
        ("serve.sched.emit", pytest.approx(4e-3))]
    r = pass_spans.read(trace, LAYER_SPANS)
    assert r["idle_s"] == pytest.approx(4e-3)
    assert r["idle_named_share"] == pytest.approx(1 - 120 / 4000)
    assert r["metrics"]["device.idle_ms_per_pass"] == pytest.approx(2.0)


def test_the_midpoint_owner_flips_where_the_overlap_hardly_moves():
    """The same two passes with the second step 700 us longer: gap 2 is
    18,700-20,000, its middle 19,350 now falls in the pass's own code
    after emit, and the midpoint rule moves 1.3 ms from emit to the pass;
    by overlap emit loses the 200 us of it the step now covers."""
    trace = two_passes()
    trace.device_ops[0][1].dur_ns = 8700 * US
    mid = dict(reduce.gaps_by_annotation(trace))
    assert mid == pytest.approx({"serve.sched.emit": 2e-3,
                                 "serve.sched.pass": 1.3e-3})
    got = dict(pass_spans.idle_by_overlap(trace, LAYER_SPANS))
    assert got["serve.sched.emit"] * 1e6 == pytest.approx(600 + 400)
    assert got["serve.sched.pass"] * 1e6 == pytest.approx(920 + 800)


def test_segments_cut_an_event_that_only_overlaps_anothers_end():
    """Events of two threads may overlap without nesting: each piece of
    the timeline still has one owner and no time is counted twice."""
    seg = pass_spans.self_segments([ev("a", 0, 10), ev("b", 5, 10),
                                    ev("c", 30, 5)])
    assert [(s / US, e / US, n) for s, e, n in seg] == [
        (0, 5, "a"), (5, 15, "b"), (30, 35, "c")]


# ------------------------------------------------- the slice off the chip
@pytest.fixture(scope="module")
def chip_slice():
    with open(os.path.join(DATA, "v5e_pr34_pass_span_slice.json")) as f:
        trace = Trace.from_json(json.load(f))
    with open(os.path.join(DATA, "v5e_pr34_pass_span_slice.expect.json")) as f:
        return trace, json.load(f)


def test_recorded_slice_reads_the_six_metrics(chip_slice):
    """gpt2-124m.batch-gen on a v5e (PR 34): the six metrics in waiting and
    PR 24's dispatch span, through their files and readers, against the
    values worked out apart from this code (the expect file says how)."""
    trace, expect = chip_slice
    assert len(trace.device_ops[0]) == 5314 and len(trace.host) == 44
    files = _metric_files(NEW_METRICS + ("engine.dispatch_ms_p50",))
    got = manifest.read_metrics(files, _obs(trace))
    assert {k: v["value"] for k, v in got.items()} == pytest.approx(
        expect["metrics_ms"], rel=1e-6)
    # the drivers' outer annotations in the slice are not layer spans:
    # pass_spans leaves them out of everything but `also`
    r = pass_spans.read(trace, LAYER_SPANS, also=["engine.step"])
    assert r["also"]["engine.step"]["count"] == 3
    assert r["metrics"]["device.idle_ms_per_pass"] == pytest.approx(
        expect["idle_ns"] / expect["passes_that_hold_a_dispatch"] / 1e6)
    assert r["window_s"] == pytest.approx(expect["window_ns"] / 1e9)
    for name, want in expect["self_p50_ms"].items():
        assert r["spans"][name]["self_p50_ms"] == pytest.approx(want), name
    # the children cover a dispatch but for its own 0.24 ms
    d = r["spans"]["serve.engine.dispatch"]
    assert d["self_p50_ms"] / d["p50_ms"] < 0.1


def test_recorded_slice_overlap_table(chip_slice):
    """The slice's 25.75 ms of idle by the span whose self time it falls
    in: all but 9 us of it named, the whole equal to the gaps' sum, and
    the one 9.5 ms turnaround that the midpoint rule gives whole to
    serve.sched.admit cut into its seven owners."""
    trace, expect = chip_slice
    got = pass_spans.idle_by_overlap(trace, LAYER_SPANS)
    assert [n for n, _ in got] == list(expect["idle_by_overlap_ns"])
    assert {n: s * 1e9 for n, s in got} == pytest.approx(
        expect["idle_by_overlap_ns"], abs=1.0)
    assert sum(s for _, s in got) * 1e9 == pytest.approx(expect["idle_ns"])
    r = pass_spans.read(trace, LAYER_SPANS)
    assert r["idle_named_share"] == pytest.approx(
        1 - expect["idle_by_overlap_ns"]["(no span)"] / expect["idle_ns"])
    first = r["idle_by_midpoint"][0]
    assert [first[0], round(first[1] * 1e9)] == expect["idle_by_midpoint_first"]
    # that gap alone, by overlap
    gap = max(reduce.idle_gaps(trace), key=lambda g: g[1] - g[0])
    only = Trace({0: [Event("before", gap[0] - 10, 10),
                      Event("after", gap[1], 10)]}, trace.host, {})
    cut = {n: s * 1e6 for n, s in pass_spans.idle_by_overlap(only,
                                                             LAYER_SPANS)}
    assert cut == pytest.approx(expect["largest_gap_us"], abs=0.06)
    assert sum(cut.values()) == pytest.approx((gap[1] - gap[0]) / 1e3)


# ------------------------------------------- a cell with the registry on
def test_a_tiny_cell_runs_with_the_registry_and_a_sink_on(
        capsys, monkeypatch, tmp_path):
    """experiments/registry_on.py is how PERF.md's "registry on" readings
    are made (chipbench/run.py inside obs.start_run: the drivers open no
    run of their own). A tiny backlog cell through it: the cell's result
    line as ever, then the registry's line; spans.jsonl complete after
    the run, schema-valid, every traced request stitched with the decode
    passes it rode, and serve.tpot_s counting a token an observation."""
    import importlib.util
    import sys

    import chipbench_tiny
    from chipbench import device
    from nezha_tpu import obs
    from nezha_tpu.obs.report import stitch_run_dir

    sys.path.insert(0, os.path.join(manifest.REPO, "tools"))
    from check_telemetry_schema import check_run_dir

    spec = importlib.util.spec_from_file_location(
        "registry_on", os.path.join(manifest.REPO, "experiments",
                                    "registry_on.py"))
    registry_on = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(registry_on)
    root = chipbench_tiny.make_root(str(tmp_path / "root"))
    run_dir = str(tmp_path / "run")
    monkeypatch.setenv(device.REHEARSAL_ENV, "cpu")
    try:
        rc = registry_on.main([run_dir, "--", "--root", root, "--workload",
                               "tiny.gen", "--seed", "3", "--seconds", "1",
                               "--trace", "0"])
    finally:
        obs.end_run()
        obs.REGISTRY.reset()
    assert rc == 0 and not obs.enabled()
    *_, result, reg = [json.loads(l) for l in
                       capsys.readouterr().out.splitlines() if l.strip()]
    assert result["correct"] is True and result["failed"] == 0
    reg = reg["registry_on"]
    assert reg["spans_jsonl_lines"] == reg["span_records_kept"] > 0
    assert reg["tpot_s"]["count"] == reg["counters"]["serve.tokens_total"] > 0
    assert check_run_dir(run_dir) == []
    done = [t for t in stitch_run_dir(run_dir) if t["complete"]]
    assert done and all(t["decode_windows"]["count"] == t["tokens"]
                        for t in done if t.get("finish_reason") == "length")
