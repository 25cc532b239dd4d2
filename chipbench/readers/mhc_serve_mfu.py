"""Serving FLOP/s utilization of a model with a multi-stream residual path,
PREFILL COUNTED: the operations of the window's output tokens
(``trace/kernel_costs_xing4.decode_flops_per_token`` at the window's own mean
context and held pairs a token, times ``out_tok_s``) plus the operations of
the prompt tokens prefilled in the sampled part of the window, uncached ones
only, attention at each one's own context (``prefill_flops``, over that
part's seconds), over the chip's published bf16 peak. An end-to-end share of
the whole pass's peak, not a kernel's roofline share. params: {"scale": 100}"""

from chipbench.trace import kernel_costs_xing4 as costs


def read(obs, params):
    c = obs.counters
    need = ("tokens_in_span", "token_span_s", "moe_rows", "moe_held_pairs",
            "lm_resident_tokens", "sample_window_s")
    if obs.peaks is None or not obs.model.get("hc_streams") \
            or any(not c.get(n) for n in need):
        return None
    s = obs.model
    decode = (c["tokens_in_span"] / c["token_span_s"]
              * costs.decode_flops_per_token(
                  c["moe_held_pairs"] / c["moe_rows"],
                  c["lm_resident_tokens"] / c["moe_rows"], s))
    # every expert is held or the share of the pairs held is the steps'
    pairs = c["moe_held_pairs"] / c["moe_rows"]
    prefill = costs.prefill_flops(
        c.get("prompt_tokens_uncached", 0.0),
        c.get("mhc_prompt_context_sum", 0.0), pairs, s) / c["sample_window_s"]
    return ((decode + prefill) / obs.peaks["bf16_flops"]
            * params.get("scale", 1.0))
