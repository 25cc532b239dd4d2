#!/usr/bin/env python3
"""A serving pass span by span, read by hand from a kept trace.

    python3 chipbench/run.py --workload <cell> --seed <n> --trace 1 \\
        --keep-trace chiprun_out/<dir>
    python3 chipbench/pass_spans.py chiprun_out/<dir>/*.xplane.pb

The program opens sixteen layer spans on the profiler's clock
(``nezha_tpu.obs.annotate``; their names and each one's parent are
``nezha_tpu.obs.LAYER_SPANS``: the ten of PR 24 and, since PR 34, the
children of ``serve.engine.dispatch`` / ``wait`` / ``prefill``). A run of
a cell does not read them yet: the drivers' ``ANNOTATIONS`` keep six of
them in three cells and none in the others, and no cell's file lists the
nine metrics made for them; both are the benchmark's to change (ROADMAP
Reach B2). Until then this prints, for one chip's trace, what the result
line will carry and what a ``perf_opt`` issue on the host needs:

- ``spans``: for each span its count, the p50 of its duration and the p50
  of its SELF time, its duration minus its children's by ``LAYER_SPANS``
  (a child counts where it lies inside the span);
- ``metrics``: the nine metric files in waiting, through their readers;
- ``idle_by_overlap``: the window's idle seconds charged to the spans'
  self time BY OVERLAP: each gap of the chip is cut along the host's
  timeline and every piece goes to the innermost span open over it
  (``(no span)``: between two passes, the driver's own loop). One
  turnaround of a decode pass runs through the tail of the fetch, the
  emit, the pass's own code, the admission and the dispatch, so its
  pieces add up to the same total however the gap's middle falls;
- ``idle_by_midpoint``: ``reduce.gaps_by_annotation``'s owners, a gap
  whole to the span open at its middle, as a result line's
  ``breakdown.idle_gaps`` has them: which span that is flips with a few
  hundred microseconds (PERF.md section 6, PR 34), so read the overlap.

It supersedes ``chipbench/layer_spans.py`` in use.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import manifest  # noqa: E402
from chipbench.obs import Obs  # noqa: E402
from chipbench.stats import percentile  # noqa: E402
from chipbench.trace import reduce  # noqa: E402
from nezha_tpu.obs import LAYER_SPANS  # noqa: E402

METRICS = ("sched.host_ms_per_pass", "engine.dispatch_ms_p50",
           "trainer.host_ms_per_step", "device.idle_ms_per_pass",
           "engine.bind_ms_p50", "engine.tables_ms_p50",
           "engine.launch_ms_p50", "engine.fetch_ms_p50",
           "sched.emit_ms_p50")
NO_SPAN = "(no span)"


def span_table(trace: reduce.Trace, parents: Dict[str, str]) -> dict:
    """{span: {"count", "p50_ms", "self_p50_ms"}}: a span's self time is
    its duration minus the durations of the events inside it whose name
    has the span's name as parent."""
    by_name: Dict[str, List[reduce.Event]] = {}
    for e in trace.host:
        if e.name in parents:
            by_name.setdefault(e.name, []).append(e)
    out = {}
    for name, events in by_name.items():
        kids = [k for child, parent in parents.items() if parent == name
                for k in by_name.get(child, ())]
        selfs = [e.dur_ns - sum(k.dur_ns for k in kids
                                if e.start_ns <= k.start_ns
                                and k.end_ns <= e.end_ns)
                 for e in events]
        out[name] = {"count": len(events),
                     "p50_ms": percentile([e.dur_ns for e in events], 50)
                     / 1e6,
                     "self_p50_ms": percentile(selfs, 50) / 1e6}
    return out


def self_segments(events: List[reduce.Event]
                  ) -> List[Tuple[float, float, str]]:
    """The host's timeline cut into (start, end, name) pieces, each
    owned by the innermost event open over it: an event's pieces are its
    self time. Events of one thread nest; one that only overlaps an
    earlier one's end (another thread's) is cut at that end."""
    out: List[Tuple[float, float, str]] = []
    stack: List[reduce.Event] = []
    t = None

    def advance(to: float) -> None:
        nonlocal t
        if t is not None and to <= t:
            return
        if stack:
            out.append((t, to, stack[-1].name))
        t = to

    for e in sorted(events, key=lambda e: (e.start_ns, -e.dur_ns)):
        while stack and stack[-1].end_ns <= e.start_ns:
            advance(stack[-1].end_ns)
            stack.pop()
        advance(e.start_ns)
        stack.append(e)
    while stack:
        advance(stack[-1].end_ns)
        stack.pop()
    return out


def idle_by_overlap(trace: reduce.Trace, parents: Dict[str, str],
                    chip=None) -> List[Tuple[str, float]]:
    """Idle seconds of ``chip`` by the span whose self time they fall
    in, heaviest first; what no span covers is ``(no span)``."""
    segments = self_segments([e for e in trace.host if e.name in parents])
    totals: Dict[str, float] = {}
    i = 0
    for a, b in reduce.idle_gaps(trace, chip):
        covered = 0.0
        while i < len(segments) and segments[i][1] <= a:
            i += 1
        j = i
        while j < len(segments) and segments[j][0] < b:
            lo, hi, name = segments[j]
            part = min(hi, b) - max(lo, a)
            totals[name] = totals.get(name, 0.0) + part / 1e9
            covered += part
            j += 1
        if b - a > covered:
            totals[NO_SPAN] = totals.get(NO_SPAN, 0.0) \
                + (b - a - covered) / 1e9
    return sorted(totals.items(), key=lambda kv: -kv[1])


def read(trace, parents: Dict[str, str] = LAYER_SPANS, also=()) -> dict:
    """``trace``: an ``.xplane.pb``'s path or a loaded ``reduce.Trace``.
    ``also``: other host events (the drivers' own annotations) whose
    count and p50 are printed beside the spans and nothing else."""
    if isinstance(trace, str):
        trace = reduce.load(trace, set(parents) | set(also))
    extra = {name: [e.dur_ns for e in trace.host if e.name == name]
             for name in also}
    trace = reduce.Trace(trace.device_ops,
                         [e for e in trace.host if e.name in parents],
                         trace.structure)
    obs = Obs()
    obs.trace = trace
    files = []
    for name in METRICS:
        with open(os.path.join(manifest.ROOT, "metrics", f"{name}.json")) as f:
            files.append(json.load(f))
    overlap = idle_by_overlap(trace, parents)
    idle = sum(s for _, s in overlap)
    named = sum(s for n, s in overlap if n != NO_SPAN)
    return {"spans": span_table(trace, parents),
            "also": {name: {"count": len(d),
                            "p50_ms": percentile(d, 50) / 1e6}
                     for name, d in extra.items() if d},
            "metrics": {k: v["value"] for k, v in
                        manifest.read_metrics(files, obs).items()},
            "idle_s": idle,
            "idle_named_share": named / idle if idle else None,
            "idle_by_overlap": overlap,
            "idle_by_midpoint": reduce.gaps_by_annotation(trace),
            **reduce.summary(trace)}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("xplane")
    p.add_argument("--also", default="",
                   help="other host events to count, comma-separated "
                        "(engine.step: the driver's own span of a step)")
    args = p.parse_args(argv)
    also = [n for n in args.also.split(",") if n]
    print(json.dumps(read(args.xplane, also=also), indent=1), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
