"""A decode step's share of its memory roofline, for a model with a
multi-stream residual path: the bytes one step must move
(``trace/kernel_costs_xing4.decode_step_bytes`` with the window's mean
touched experts, resident latent rows and active rows a step) over the chip's
published HBM bandwidth, over the median wall time of ``Engine.step`` (the
``step_ms`` samples). params: {"scale": 100}"""

from chipbench.stats import percentile
from chipbench.trace import kernel_costs_xing4 as costs


def read(obs, params):
    c = obs.counters
    step_ms = percentile(obs.samples.get("step_ms", []), 50)
    if obs.peaks is None or not obs.model.get("hc_streams") \
            or not step_ms or not c.get("moe_steps"):
        return None
    steps = c["moe_steps"]
    need = costs.decode_step_bytes(
        c["moe_touched"] / steps, c["lm_resident_tokens"] / steps,
        c["moe_rows"] / steps, obs.model)
    least_ms = need / obs.peaks["hbm_bytes_per_s"] * 1e3
    return least_ms / step_ms * params.get("scale", 1.0)
