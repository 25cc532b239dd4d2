"""The share of the traced window's device time spent in prefill programs
(admissions), from the device trace alone. A kept trace has no line that
tells one program's ops from another's, so the programs are told apart by a
kernel that every serve program of the model calls a known number of times:
``marker`` (a regex) matches the op that runs ``per_program`` times in every
program (``nezha_mhc_pre``: twice a layer, in the step and in every prefill
bucket). The matching ops, in device order, are cut into runs of
``per_program``: a run is one program's execution. The first dimension of
the marker's result is the program's tokens: ``slots`` (the driver's
counter) for a decode step, a bucket's width for a prefill chunk. A
program's device time is the union of the op intervals from its first
marker to the next program's first marker (so a step's ops before its first
marker, the sampling and the embedding, count to the program before it:
~0.1% of a chunk). -> prefill programs' seconds over all programs'.
params: {"marker": regex, "per_program_from": "sublayers", "scale": 100}"""

import re

from chipbench.trace import reduce

_TOKENS = re.compile(r" = \(?f32\[(\d+),")


def read(obs, params):
    per = int(obs.model.get(params["per_program_from"], 0) or 0)
    slots = int(obs.counters.get("slots", 0))
    if obs.trace is None or not obs.trace.device_ops or not per or not slots:
        return None
    chip = min(k for k, ev in obs.trace.device_ops.items() if ev)
    ops = obs.trace.device_ops[chip]
    rx = re.compile(params["marker"])
    marks = [e for e in ops if rx.search(e.name)]
    if len(marks) < 2 * per:
        return None
    # (start of the program's first marker, its tokens)
    programs = []
    for i in range(0, len(marks) - per + 1, per):
        tokens = _TOKENS.search(marks[i].name)
        if tokens is None:
            return None
        programs.append((marks[i].start_ns, int(tokens.group(1))))
    busy = reduce.union([(e.start_ns, e.end_ns) for e in ops])
    edges = [p[0] for p in programs] + [busy[-1][1]]
    prefill = total = 0.0
    for (start, tokens), end in zip(programs, edges[1:]):
        seconds = sum(min(b, end) - max(a, start) for a, b in busy
                      if b > start and a < end)
        total += seconds
        if tokens != slots:
            prefill += seconds
    if total <= 0:
        return None
    return prefill / total * params.get("scale", 1.0)
