"""A kernel's share of its roofline, as ``cost_kernel_roofline`` reads it,
over a step list the driver names: ``obs.<steps>``'s (time, rows, x) of
each decode step, where ``x`` is the quantity the cost function takes
(``trace/<costs>``'s ``cost``, ``f(rows, x, sizes)``; the blocks a sparse
layer's queries read, say). The least time of the steps that ended inside
the traced span, averaged, over the measured time of one call (the self
time of the ops that match ``patterns`` over their number). Nothing where
the run's sizes lack ``needs``, the driver kept no such list, or the
trace holds no such op.
params: {"patterns": [regex], "steps": attribute of obs, "costs": module
of chipbench/trace, "cost": function name, "needs": a key of the sizes,
"scale": 100}"""

import importlib

from chipbench.trace import kernel_costs, reduce


def read(obs, params):
    listed = getattr(obs, params["steps"], None)
    if (obs.trace is None or not obs.trace.device_ops or obs.peaks is None
            or obs.trace_span is None or params["needs"] not in obs.model
            or not listed):
        return None
    costs = importlib.import_module(f"chipbench.trace.{params['costs']}")
    cost = getattr(costs, params["cost"])
    t0, t1 = obs.trace_span
    steps = [(rows, x) for t, rows, x in listed if t0 <= t < t1]
    seconds, calls = reduce.matching_seconds(obs.trace, params["patterns"], 1)
    if not steps or not calls or not seconds:
        return None
    least = sum(kernel_costs.min_seconds(cost(rows, x, obs.model),
                                         obs.peaks)["seconds"]
                for rows, x in steps) / len(steps)
    return least / (seconds / calls) * params.get("scale", 1.0)
