"""The grouped-query decode attention's share of its roofline, over a
decode step's calls together: the least time the chip could take for one
step's calls (``trace/kernel_costs_exaone.gqa_decode``: one call a global
layer over the resident rows, one a window layer over the rows inside the
window, as the driver counted them at each decode step that ended inside
the traced span), over the measured time of one step's calls: the self
time of the ops that match ``patterns`` over the number of steps, which is
the number of ops that match ``calls`` (the global layers' call) over the
global layers. params: {"patterns": [regex], "calls": [regex],
"scale": 100}"""

from chipbench.trace import kernel_costs, kernel_costs_exaone as costs, reduce


def read(obs, params):
    steps = getattr(obs, "hybrid_steps", None)
    if (obs.trace is None or not obs.trace.device_ops or obs.peaks is None
            or obs.trace_span is None or not steps
            or not obs.model.get("window_layers")):
        return None
    t0, t1 = obs.trace_span
    s = obs.model
    inside = [(rows, tokens, windowed) for t, rows, tokens, windowed in steps
              if t0 <= t < t1]
    seconds, _ = reduce.matching_seconds(obs.trace, params["patterns"], 1)
    _, calls = reduce.matching_seconds(obs.trace, params["calls"], 1)
    if not inside or not calls or not seconds:
        return None

    def least(tokens, rows):
        return kernel_costs.min_seconds(costs.gqa_decode(tokens, rows, s),
                                        obs.peaks)["seconds"]

    per_step = sum(s["global_layers"] * least(tokens, rows)
                   + s["window_layers"] * least(windowed, rows)
                   for rows, tokens, windowed in inside) / len(inside)
    measured = seconds / (calls / s["global_layers"])
    return per_step / measured * params.get("scale", 1.0)
