"""From a profiler trace to numbers: the reduction every PR shares.

``load(path)`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote (with
nothing but jax) into a :class:`Trace` of plain events; everything else
here is arithmetic on those events, so it is checked against a small
recorded trace kept with the tests (``tests/zz_chipbench/data``).

What a v5e trace looks like (read by hand, PR 22): one plane per chip,
``/device:TPU:<n>``, with the lines ``Steps``, ``XLA Modules``, ``XLA
Ops`` and ``Async XLA Ops``. ``XLA Ops`` holds one event per executed HLO
op, named by its whole HLO line (``%fusion.12 = (f32[...]) fusion(...)``;
a Pallas kernel is ``%<traced function>.N = ... custom-call(...),
custom_call_target="tpu_custom_call"``, told apart only by its shapes);
nested ops such as a ``while`` and its body overlap, which is why busy
time is a union and op time is self time. Host threads are lines of the
plane ``/host:CPU``, where the benchmark's ``TraceAnnotation`` spans
appear under their own names if they BEGAN inside the traced window. All
planes share one clock.

    python3 chipbench/trace/reduce.py <file.xplane.pb> [--dump out.json]

prints the planes, lines and the heaviest ops of a trace, and can dump a
slice of it as the JSON the tests keep.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
# Stats that say more about an op than its name does (a Pallas kernel is
# an anonymous custom-call; its scope path is in one of these).
LABEL_STATS = ("tf_op", "long_name", "hlo_category", "name")


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float
    label: str = ""        # name + the stats above, for pattern matching

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    device_ops: Dict[int, List[Event]]      # chip -> events of "XLA Ops"
    host: List[Event]                       # every host-thread event kept
    structure: Dict[str, Dict[str, int]]    # plane -> line -> event count

    def to_json(self) -> dict:
        ev = lambda e: [e.name, e.start_ns, e.dur_ns, e.label]
        return {"device_ops": {str(k): [ev(e) for e in v]
                               for k, v in self.device_ops.items()},
                "host": [ev(e) for e in self.host],
                "structure": self.structure}

    @staticmethod
    def from_json(obj: dict) -> "Trace":
        ev = lambda r: Event(r[0], float(r[1]), float(r[2]), r[3])
        return Trace({int(k): [ev(r) for r in v]
                      for k, v in obj["device_ops"].items()},
                     [ev(r) for r in obj["host"]], obj.get("structure", {}))


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def load(path: str, host_names: Iterable[str] = ()) -> Trace:
    """Read an ``.xplane.pb``. Host events are kept only when their name
    is in ``host_names`` (the benchmark's own annotations): a host plane
    holds every traced runtime call."""
    import jax

    keep = set(host_names)
    data = jax.profiler.ProfileData.from_file(path)
    device_ops: Dict[int, List[Event]] = {}
    host: List[Event] = []
    structure: Dict[str, Dict[str, int]] = {}
    for plane in data.planes:
        lines = structure.setdefault(plane.name, {})
        chip = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            events = list(line.events)
            lines[line.name] = len(events)
            if chip and line.name == OPS_LINE:
                out = device_ops.setdefault(int(chip.group(1)), [])
                for e in events:
                    stats = dict(e.stats)
                    label = " ".join(
                        [e.name] + [str(stats[k]) for k in LABEL_STATS
                                    if k in stats])
                    out.append(Event(e.name, e.start_ns, e.duration_ns,
                                     label))
            elif plane.name == HOST_PLANE and keep:
                host.extend(Event(e.name, e.start_ns, e.duration_ns)
                            for e in events if e.name in keep)
    for events in device_ops.values():
        events.sort(key=lambda e: e.start_ns)
    host.sort(key=lambda e: e.start_ns)
    return Trace(device_ops, host, structure)


# ------------------------------------------------------------ arithmetic
def union(intervals: Sequence[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Merged, sorted intervals."""
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def window_of(trace: Trace) -> Tuple[float, float]:
    """First start to last end over every device op: the traced window as
    the devices saw it."""
    starts = [ev[0].start_ns for ev in trace.device_ops.values() if ev]
    ends = [max(e.end_ns for e in ev)
            for ev in trace.device_ops.values() if ev]
    if not starts:
        return (0.0, 0.0)
    return (min(starts), max(ends))


def busy_s(trace: Trace, chips: Optional[int] = None) -> float:
    """Seconds in which an op ran on the device, averaged over the chips
    used (the first ``chips`` that recorded ops)."""
    used = sorted(k for k, ev in trace.device_ops.items() if ev)[:chips]
    if not used:
        return 0.0
    total = 0.0
    for k in used:
        total += sum(b - a for a, b in union(
            [(e.start_ns, e.end_ns) for e in trace.device_ops[k]]))
    return total / len(used) / 1e9


def self_times(events: Sequence[Event]) -> List[Tuple[Event, float]]:
    """Each event with its duration minus the part its nested events
    cover (events sorted by start; nesting by containment)."""
    out: List[List] = []
    stack: List[int] = []
    for e in sorted(events, key=lambda e: (e.start_ns, -e.dur_ns)):
        while stack and out[stack[-1]][0].end_ns <= e.start_ns:
            stack.pop()
        if stack and e.end_ns <= out[stack[-1]][0].end_ns:
            out[stack[-1]][1] -= e.dur_ns
        out.append([e, e.dur_ns])
        stack.append(len(out) - 1)
    return [(e, max(t, 0.0)) for e, t in out]


def short_name(name: str) -> str:
    """A v5e trace names an op by its whole HLO line, ``%fusion.12 =
    (f32[...]) fusion(...)``: the part before `` = `` is its name."""
    return name.split(" = ", 1)[0].lstrip("%")


def base_name(name: str) -> str:
    """``%fusion.123 = ...`` -> ``fusion``: ops grouped by what they are."""
    short = short_name(name)
    return re.sub(r"[.\-_]?\d+$", "", short) or short


def op_kind(name: str) -> str:
    """``%fusion.12 = (f32[50257,768]{...}, ...) fusion(...)`` ->
    ``fusion f32[50257,768]``: ops grouped by what they are and the shape
    of their (first) result, so the twelve layers' copies of one op fall
    together and a thousand different fusions do not."""
    shape = re.match(r"\(?(\w+\[[\d,]*\])", name.split(" = ", 1)[-1])
    return base_name(name) + (" " + shape.group(1) if shape else "")


def op_seconds(trace: Trace, chip: Optional[int] = None
               ) -> List[Tuple[str, float]]:
    """Self time by op kind on one chip (the first with ops), heaviest
    first."""
    used = sorted(k for k, ev in trace.device_ops.items() if ev)
    if not used:
        return []
    totals: Dict[str, float] = {}
    for e, t in self_times(trace.device_ops[used[0] if chip is None
                                            else chip]):
        key = op_kind(e.name)
        totals[key] = totals.get(key, 0.0) + t / 1e9
    return sorted(totals.items(), key=lambda kv: -kv[1])


def matching_seconds(trace: Trace, patterns: Sequence[str],
                     chips: Optional[int] = None) -> Tuple[float, int]:
    """(self-time seconds, events) of the ops whose label matches any of
    ``patterns`` (regular expressions), averaged over the chips used."""
    rx = [re.compile(p) for p in patterns]
    used = sorted(k for k, ev in trace.device_ops.items() if ev)[:chips]
    if not used:
        return 0.0, 0
    total, count = 0.0, 0
    for k in used:
        for e, t in self_times(trace.device_ops[k]):
            if any(r.search(e.label or e.name) for r in rx):
                total += t
                count += 1
    return total / len(used) / 1e9, count // len(used)


def idle_gaps(trace: Trace, chip: Optional[int] = None
              ) -> List[Tuple[float, float]]:
    """The intervals inside the window in which no op ran on the chip."""
    used = sorted(k for k, ev in trace.device_ops.items() if ev)
    if not used:
        return []
    ev = trace.device_ops[used[0] if chip is None else chip]
    busy = union([(e.start_ns, e.end_ns) for e in ev])
    lo, hi = window_of(trace)
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def gaps_by_annotation(trace: Trace, chip: Optional[int] = None
                       ) -> List[Tuple[str, float]]:
    """Idle seconds by what the host was doing: each gap goes to the
    innermost benchmark annotation open at its middle."""
    totals: Dict[str, float] = {}
    host = trace.host
    for a, b in idle_gaps(trace, chip):
        mid = (a + b) / 2.0
        owner, owner_start = "(no annotation)", -1.0
        for h in host:
            if h.start_ns > mid:
                break
            if h.end_ns >= mid and h.start_ns >= owner_start:
                owner, owner_start = h.name, h.start_ns
        totals[owner] = totals.get(owner, 0.0) + (b - a) / 1e9
    return sorted(totals.items(), key=lambda kv: -kv[1])


def breakdown(trace: Trace) -> dict:
    return {"device_ops": [[n, s] for n, s in op_seconds(trace)[:10]],
            "idle_gaps": [[n, s] for n, s in gaps_by_annotation(trace)[:10]]}


def summary(trace: Trace, chips: Optional[int] = None) -> dict:
    lo, hi = window_of(trace)
    return {"busy_s": busy_s(trace, chips), "window_s": (hi - lo) / 1e9}


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("xplane")
    p.add_argument("--dump", help="write a slice of the trace as JSON")
    p.add_argument("--dump-ms", type=float, default=50.0,
                   help="length of the dumped slice, from the first op")
    p.add_argument("--host-names", default="")
    args = p.parse_args(argv)
    names = [n for n in args.host_names.split(",") if n]
    trace = load(args.xplane, names)
    print(json.dumps({"structure": trace.structure,
                      **summary(trace), **breakdown(trace)}, indent=1))
    labels: Dict[str, str] = {}
    for ev in trace.device_ops.values():
        for e in ev:
            labels.setdefault(base_name(e.name), e.label[:300])
    print(json.dumps({"labels": labels}, indent=1))
    if args.dump:
        lo, _ = window_of(trace)
        hi = lo + args.dump_ms * 1e6
        cut = Trace({k: [e for e in v if lo <= e.start_ns < hi]
                     for k, v in trace.device_ops.items()},
                    [e for e in trace.host if e.start_ns < hi
                     and e.end_ns > lo], trace.structure)
        with open(args.dump, "w") as f:
            json.dump(cut.to_json(), f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
