"""The latent decode attention's share of its roofline: the least time
the chip could take for one call (``trace/kernel_costs_mistral4.
mla_decode`` over the resident rows the driver counted at each decode step
that ended inside the traced span; one call a layer, every layer the
same), over the measured time of one call: the self time of the ops that
match ``patterns`` over the number of ops that match ``calls`` (the op, or
the one op of a composed form, that runs once a call).
params: {"patterns": [regex], "calls": [regex], "scale": 100}"""

from chipbench.trace import kernel_costs, kernel_costs_mistral4 as costs, reduce


def read(obs, params):
    if (obs.trace is None or not obs.trace.device_ops or obs.peaks is None
            or obs.trace_span is None or not obs.model.get("layers")):
        return None
    t0, t1 = obs.trace_span
    steps = [(rows, tokens) for t, rows, tokens in obs.steps if t0 <= t < t1]
    seconds, _ = reduce.matching_seconds(obs.trace, params["patterns"], 1)
    _, calls = reduce.matching_seconds(obs.trace, params["calls"], 1)
    if not steps or not calls or not seconds:
        return None
    least = sum(kernel_costs.min_seconds(
        costs.mla_decode(tokens, rows, obs.model), obs.peaks)["seconds"]
        for rows, tokens in steps) / len(steps)
    return least / (seconds / calls) * params.get("scale", 1.0)
