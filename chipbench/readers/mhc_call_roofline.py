"""A named kernel's share of its roofline in the cell of the four-stream
model, over the traced window: the least time the chip could take for every
call that ran, summed, over the calls' measured self time, summed (as
``mhc_kernel_roofline`` reads the two hyper-connection kernels; costs from
``trace/kernel_costs_xing4``, sizes from the configuration file).

``cost: moe_experts``: a call's token-expert pairs are the rows of its
result, read from the op's own HLO line. A decode step's call (``slots x
top_k`` pairs) is charged the held experts the step program COUNTED as
touched, a sparse layer, the mean over the decode steps that ended inside
the traced span; a prefill chunk's call (any other size) is charged every
held expert, or one an expert a pair if it has fewer pairs than experts (at
512 pairs or more over 64 experts all are touched but for a draw of 1 in
3,000; the prefill programs return no count).
``cost: latent_decode``: every call is a decode step's, one a layer, charged
the latent rows resident at the traced span's decode steps, read once, and
each active row's absorbed query and latent output.
params: {"patterns": [regex], "cost": "moe_experts" | "latent_decode",
"scale": 100}"""

import re

from chipbench.trace import kernel_costs, kernel_costs_xing4 as costs, reduce

_ROWS = re.compile(r" = \(?\w+\[(\d+),")


def read(obs, params):
    if (obs.trace is None or not obs.trace.device_ops or obs.peaks is None
            or obs.trace_span is None or not obs.model.get("hc_streams")):
        return None
    t0, t1 = obs.trace_span
    s = obs.model
    mean = lambda xs: sum(xs) / len(xs) if xs else None     # noqa: E731
    touched = mean([n / s["sparse_layers"]
                    for t, n, _ in getattr(obs, "lm_steps", ())
                    if t0 <= t < t1])
    steps = [(rows, tokens) for t, rows, tokens in obs.steps if t0 <= t < t1]
    step_pairs = int(obs.counters.get("slots", 0)) * s["top_k"]
    rx = [re.compile(p) for p in params["patterns"]]
    chip = min(k for k, ev in obs.trace.device_ops.items() if ev)
    least = measured = 0.0
    for e, t in reduce.self_times(obs.trace.device_ops[chip]):
        if not any(r.search(e.name) for r in rx):
            continue
        if params["cost"] == "latent_decode":
            if not steps:
                continue
            cost = costs.latent_decode(mean([n for _, n in steps]),
                                       mean([r for r, _ in steps]), s)
        else:
            rows = _ROWS.search(e.name)
            if rows is None:
                continue
            pairs = int(rows.group(1))
            if pairs == step_pairs:
                if touched is None:
                    continue
                cost = costs.moe_experts(touched, pairs, s)
            else:
                cost = costs.moe_experts(min(s["experts_held"], pairs),
                                         pairs, s)
        least += kernel_costs.min_seconds(cost, obs.peaks)["seconds"]
        measured += t / 1e9
    if not measured:
        return None
    return least / measured * params.get("scale", 1.0)
