"""A percentile of a program span's host time on the profiler's clock: for
each host event named ``span`` in the traced window, its duration minus
the durations of the events named in ``minus`` that lie inside it (a pass
minus the time it was blocked on the device). The program opens these
spans itself (``nezha_tpu.obs.annotate``); a program that has none, or a
run that was not traced, reads as nothing (None).
params: {"span": name, "minus": [names], "q": 0..100, "scale": 1e-6}"""

from chipbench.stats import percentile


def read(obs, params):
    if obs.trace is None:
        return None
    minus = set(params.get("minus", ()))
    inner = [e for e in obs.trace.host if e.name in minus]
    values = [
        s.dur_ns - sum(e.dur_ns for e in inner
                       if s.start_ns <= e.start_ns and e.end_ns <= s.end_ns)
        for s in obs.trace.host if s.name == params["span"]]
    value = percentile(values, params.get("q", 50))
    return None if value is None else value * params.get("scale", 1.0)
