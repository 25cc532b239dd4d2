"""The routed-expert layer's share of its roofline in a decode step: the
least time for one call (``trace/kernel_costs_mistral4.moe_experts`` with
the touched experts and held pairs a layer that the step program counted,
for each decode step that ended inside the traced span), over the
measured time of one call: the self time of the ops that match
``patterns`` over the number of ops that match ``calls`` (the op that
runs once a call). Which bound holds is the cost function's answer
(bandwidth while an expert sees few tokens).
params: {"patterns": [regex], "calls": [regex], "scale": 100}"""

from chipbench.trace import kernel_costs, kernel_costs_mistral4 as costs, reduce


def read(obs, params):
    lm_steps = getattr(obs, "lm_steps", None)
    if (obs.trace is None or not obs.trace.device_ops or obs.peaks is None
            or obs.trace_span is None or not lm_steps
            or not obs.model.get("layers")):
        return None
    t0, t1 = obs.trace_span
    layers = obs.model["layers"]
    steps = [(touched / layers, pairs / layers)
             for t, touched, pairs in lm_steps if t0 <= t < t1]
    seconds, _ = reduce.matching_seconds(obs.trace, params["patterns"], 1)
    _, calls = reduce.matching_seconds(obs.trace, params["calls"], 1)
    if not steps or not calls or not seconds:
        return None
    least = sum(kernel_costs.min_seconds(
        costs.moe_experts(touched, pairs, obs.model), obs.peaks)["seconds"]
        for touched, pairs in steps) / len(steps)
    return least / (seconds / calls) * params.get("scale", 1.0)
