"""Serving FLOP/s utilization of a model with recurrent-state layers
beside latent-attention layers: output tokens per second over the window
times the operations one output token needs through this chip's share
(``trace/kernel_costs_kimi.serve_flops_per_token``: the held pairs a token
and the mean context are the window's own, from the driver's counters),
over the chip's published bf16 peak. An end-to-end share of the whole
step's peak, not a kernel's roofline share. params: {"scale": 100}"""

from chipbench.trace import kernel_costs_kimi as costs


def read(obs, params):
    c = obs.counters
    need = ("tokens_in_span", "token_span_s", "moe_rows", "moe_held_pairs",
            "lm_resident_tokens")
    if obs.peaks is None or not obs.model.get("kda_layers") \
            or any(not c.get(n) for n in need):
        return None
    flops = costs.serve_flops_per_token(
        c["moe_held_pairs"] / c["moe_rows"],
        c["lm_resident_tokens"] / c["moe_rows"], obs.model)
    rate = c["tokens_in_span"] / c["token_span_s"]
    return rate * flops / obs.peaks["bf16_flops"] * params.get("scale", 1.0)
