"""A kernel's share of its roofline in a model with recurrent-state layers
beside latent-attention layers: the least time the chip could take for one
call (``trace/kernel_costs_kimi``'s function named by ``cost``:
``kda_decode`` over the active rows, or ``latent_decode`` over the
resident rows and the active rows, as the driver counted them at each
decode step that ended inside the traced span; or ``moe_experts`` over the
held experts touched and the pairs they computed, a sparse layer, as the
step program counted them; every call of a step the same), over the
measured time of one call: the self time of the ops that match
``patterns`` over the number of ops that match ``calls`` (the op that runs
once a call; ``patterns`` itself where a call is one op).
params: {"patterns": [regex], "calls": [regex] (optional), "cost":
"kda_decode" | "latent_decode" | "moe_experts", "scale": 100}"""

from chipbench.trace import kernel_costs, kernel_costs_kimi as costs, reduce


def read(obs, params):
    if (obs.trace is None or not obs.trace.device_ops or obs.peaks is None
            or obs.trace_span is None or not obs.model.get("kda_layers")):
        return None
    t0, t1 = obs.trace_span
    s = obs.model
    if params["cost"] == "moe_experts":
        # (time, experts touched, held pairs), both summed over the layers
        steps = [(touched / s["sparse_layers"], pairs / s["sparse_layers"])
                 for t, touched, pairs in getattr(obs, "lm_steps", ())
                 if t0 <= t < t1]
        cost = lambda touched, pairs: costs.moe_experts(touched, pairs, s)
    else:
        steps = [(rows, tokens) for t, rows, tokens in obs.steps
                 if t0 <= t < t1]
        cost = {"kda_decode": lambda rows, tokens: costs.kda_decode(rows, s),
                "latent_decode": lambda rows, tokens: costs.latent_decode(
                    tokens, rows, s)}[params["cost"]]
    seconds, calls = reduce.matching_seconds(obs.trace, params["patterns"], 1)
    if "calls" in params:
        _, calls = reduce.matching_seconds(obs.trace, params["calls"], 1)
    if not steps or not calls or not seconds:
        return None
    least = sum(kernel_costs.min_seconds(cost(*step), obs.peaks)["seconds"]
                for step in steps) / len(steps)
    return least / (seconds / calls) * params.get("scale", 1.0)
