"""A percentile of one of the run's samples.
params: {"samples": name, "q": 0..100, "scale": 1.0}"""

from chipbench.stats import percentile


def read(obs, params):
    value = percentile(obs.samples.get(params["samples"], []), params["q"])
    return None if value is None else value * params.get("scale", 1.0)
