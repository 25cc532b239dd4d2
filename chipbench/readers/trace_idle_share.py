"""1 - union of the device-op intervals over the traced window, from the
profiler's trace (never from host time). params: {"scale": 100}"""

from chipbench.trace import reduce


def read(obs, params):
    if obs.trace is None or not obs.trace.device_ops:
        return None
    s = reduce.summary(obs.trace, int(obs.counters.get("chips", 1)))
    if s["window_s"] <= 0:
        return None
    return (1.0 - s["busy_s"] / s["window_s"]) * params.get("scale", 1.0)
