"""The reduction from trace events to numbers, on hand-made events whose
answers are known, and on a slice of a trace recorded on the chip."""

import json
import os
import re

import pytest

from chipbench.trace import kernel_costs, reduce
from chipbench.trace.reduce import Event, Trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1e6


def ev(name, start_ms, dur_ms, label=""):
    return Event(name, start_ms * MS, dur_ms * MS, label or name)


@pytest.fixture
def toy():
    ops = [ev("fusion.1", 0, 2), ev("while.3", 3, 6),       # while holds:
           ev("fusion.2", 3, 1), ev("custom-call.7", 4.5, 3,
                                    "custom-call.7 jit(step)/decode_attn"),
           ev("all-gather-start.1", 12, 0.5), ev("fusion.4", 12.5, 1.5),
           ev("all-gather-done.1", 14, 1), ev("fusion.5", 19, 1)]
    host = [ev("scheduler.step", 0, 16), ev("engine.step", 8.5, 4),
            ev("generator.sleep", 15.5, 3)]
    return Trace({0: ops}, host, {})


def test_busy_is_a_union_and_idle_is_its_complement(toy):
    assert reduce.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    s = reduce.summary(toy, 1)
    assert s["window_s"] == pytest.approx(0.020)
    assert s["busy_s"] == pytest.approx(0.012)      # 2 + 6 + 3 + 1 ms
    gaps = reduce.idle_gaps(toy)
    assert [(a / MS, b / MS) for a, b in gaps] == pytest.approx(
        [(2, 3), (9, 12), (15, 19)])


def test_op_time_is_self_time(toy):
    seconds = dict(reduce.op_seconds(toy))
    assert seconds["while"] == pytest.approx(0.002)       # 6 - 1 - 3
    assert seconds["custom-call"] == pytest.approx(0.003)
    assert seconds["fusion"] == pytest.approx(0.0055)
    got, n = reduce.matching_seconds(toy, ["decode_attn"], 1)
    assert (got, n) == (pytest.approx(0.003), 1)
    got, n = reduce.matching_seconds(toy, ["all-gather", "all-reduce"], 1)
    assert (got, n) == (pytest.approx(0.0015), 2)


def test_gaps_go_to_the_innermost_open_annotation(toy):
    gaps = dict(reduce.gaps_by_annotation(toy))
    assert gaps["engine.step"] == pytest.approx(0.003)     # 9..12
    assert gaps["scheduler.step"] == pytest.approx(0.001)  # 2..3
    assert gaps["generator.sleep"] == pytest.approx(0.004)
    b = reduce.breakdown(toy)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["idle_gaps"][0] == ["generator.sleep", pytest.approx(0.004)]


def test_json_round_trip(toy):
    again = Trace.from_json(json.loads(json.dumps(toy.to_json())))
    assert reduce.summary(again, 1) == reduce.summary(toy, 1)


def test_kernel_costs():
    c = kernel_costs.decode_attention(resident_tokens=1000, rows=4,
                                      num_heads=12, head_dim=64)
    assert c["bytes"] == 2 * 1000 * 768 * 2 + 2 * 4 * 768 * 2
    assert c["flops"] == 2 * 2 * 1000 * 768
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    assert kernel_costs.min_seconds(c, peaks)["bound"] == "bandwidth"
    assert kernel_costs.transformer_train_flops_per_token(
        124_000_000, 12, 768, 1024) == 6 * 124e6 + 6 * 12 * 768 * 1024


def test_recorded_chip_trace_reduces():
    """A slice of a v5e trace of the chat-prefix cell (my chip run, PR 22):
    the plane, line and op names the reducer and the metric files' patterns
    match today are really there, and reduce to the recorded numbers."""
    with open(os.path.join(DATA, "v5e_chat_prefix_slice.json")) as f:
        trace = Trace.from_json(json.load(f))
    with open(os.path.join(DATA, "v5e_chat_prefix_slice.expect.json")) as f:
        want = json.load(f)
    assert "XLA Ops" in trace.structure["/device:TPU:0"]
    assert sum(map(len, trace.device_ops.values())) == want["events"]
    s = reduce.summary(trace, 1)
    assert s["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert s["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert 0 < s["busy_s"] <= s["window_s"]
    metrics = os.path.join(os.path.dirname(reduce.__file__), "..", "metrics")
    for key, metric in (("decode_kernel", "kernel.decode_time_share"),
                        ("prefill_kernel", "kernel.prefill_time_share"),
                        ("pool_copy", "engine.pool_copy_time_share")):
        with open(os.path.join(metrics, f"{metric}.json")) as f:
            patterns = json.load(f)["params"]["patterns"]
        seconds, events = reduce.matching_seconds(trace, patterns, 1)
        assert events == want[f"{key}_events"] > 0, key
        assert seconds == pytest.approx(want[f"{key}_s"], rel=1e-9), key
    # one decode-attention call a layer: 24.5 ms for 256 rows x 12 heads x
    # 64 table entries of grid, whatever the rows hold
    assert want["decode_kernel_s"] == pytest.approx(0.0245, rel=0.01)
    names = [n for n, _ in reduce.op_seconds(trace)]
    assert names[0] == "copy bf16[16385,12,16,64]"
    assert "step bf16[256,12,1,64]" in names
    gaps = dict(reduce.gaps_by_annotation(trace))
    assert set(gaps) <= {"engine.step", "generator.sleep", "engine.prefill",
                         "scheduler.step", "submit", "(no annotation)"}


@pytest.mark.parametrize("name", ["gather_and_consumers",
                                  "reduce_and_gathers"])
def test_recorded_four_chip_trace_counts_collectives_by_their_own_name(name):
    """Two windows of a traced ZeRO-1 step on the four chips of a v5e host
    (my chip run, PR 22). A v5e trace names an op by its whole HLO line,
    operands included, so a pattern that is not anchored on the op's own
    name also counts the ops that consume a collective's result: the
    metric file's pattern must count the collectives and nothing else."""
    with open(os.path.join(DATA, "v5e_bert_zero1_x4_slices.json")) as f:
        trace = Trace.from_json(json.load(f)["slices"][name])
    with open(os.path.join(DATA, "v5e_bert_zero1_x4_slices.expect.json")) as f:
        expect = json.load(f)
    want = expect["slices"][name]
    assert sorted(trace.device_ops) == [0, 1, 2, 3]
    assert sum(map(len, trace.device_ops.values())) == want["events"]
    s = reduce.summary(trace, 4)
    assert s["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert s["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    with open(os.path.join(os.path.dirname(reduce.__file__), "..", "metrics",
                           "trainer.collective_exposed_share.json")) as f:
        patterns = json.load(f)["params"]["patterns"]
    seconds, events = reduce.matching_seconds(trace, patterns, 4)
    assert events == want["collective_events"] > 0
    assert seconds == pytest.approx(want["collective_s"], rel=1e-9)
    # every op the metric counts IS a collective, by its own name ...
    rx = [re.compile(p) for p in patterns]
    own = re.compile(r"^(all-reduce|all-gather|async-collective-(start|done))"
                     r"[.\d]*$")
    for e in trace.device_ops[0]:
        if any(r.search(e.label or e.name) for r in rx):
            assert own.match(reduce.short_name(e.name)), e.name[:80]
    # ... while the unanchored patterns this metric had first do not agree
    loose_s, loose_n = reduce.matching_seconds(
        trace, expect["unanchored_patterns"], 4)
    assert loose_n == want["unanchored_events"]
    assert loose_s == pytest.approx(want["unanchored_s"], rel=1e-9)
    if name == "gather_and_consumers":
        # the reduce that consumes the all-gather: 0.44 ms of compute that
        # the unanchored pattern counted as collective time
        consumers = [e for e in trace.device_ops[0]
                     if reduce.base_name(e.name) == "reduce"
                     and "%all-gather" in e.name]
        assert len(consumers) == 1 and consumers[0].dur_ns > 400e3
        assert loose_n > events and loose_s > 1.5 * seconds
    else:
        # the -done wait of an async collective has no 'all-' in its name
        assert any(reduce.short_name(e.name) == "async-collective-done"
                   for e in trace.device_ops[0])
        assert events == loose_n + 1
