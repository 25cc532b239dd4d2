"""The decode-attention kernel's share of its roofline: the least time the
chip could take for one call, over the measured time of one call, both
averaged over the traced span. Needed bytes come from
``trace/kernel_costs.decode_attention`` over the resident tokens the
benchmark counted at each decode step that ended inside the span (one call
a layer, every layer the same); the bound is bandwidth (1 op per byte
against 240 at the ridge). params: {"patterns": [regex], "scale": 100}"""

from chipbench.trace import kernel_costs, reduce


def read(obs, params):
    if (obs.trace is None or not obs.trace.device_ops or obs.peaks is None
            or obs.trace_span is None or not obs.model):
        return None
    t0, t1 = obs.trace_span
    steps = [(rows, tokens) for t, rows, tokens in obs.steps if t0 <= t < t1]
    seconds, events = reduce.matching_seconds(obs.trace, params["patterns"], 1)
    if not steps or not events:
        return None
    m = obs.model
    least = sum(kernel_costs.min_seconds(
        kernel_costs.decode_attention(tokens, rows, m["num_heads"],
                                      m["head_dim"], m["kv_bytes"]),
        obs.peaks)["seconds"] for rows, tokens in steps) / len(steps)
    return least / (seconds / events) * params.get("scale", 1.0)
