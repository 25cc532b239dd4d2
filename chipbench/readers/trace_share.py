"""Self time of the device ops whose label matches any of ``patterns``
(the map from the names the trace prints today to a kernel: data, in the
metric's file), over the device's busy time or over the traced window.
params: {"patterns": [regex], "of": "busy" | "window", "scale": 100}"""

from chipbench.trace import reduce


def read(obs, params):
    if obs.trace is None or not obs.trace.device_ops:
        return None
    chips = int(obs.counters.get("chips", 1))
    seconds, events = reduce.matching_seconds(obs.trace, params["patterns"],
                                              chips)
    s = reduce.summary(obs.trace, chips)
    base = s["busy_s"] if params.get("of", "busy") == "busy" else s["window_s"]
    if base <= 0:
        return None
    return seconds / base * params.get("scale", 1.0)
