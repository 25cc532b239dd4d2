"""A decode step's share of its memory roofline, for a model with
block-sparse layers beside lightning layers: the bytes one step must move
(``trace/kernel_costs_minicpm_sala.decode_step_bytes`` at the window's mean
active rows, resident tokens and blocks read a step) over the chip's
published HBM bandwidth, over the median wall time of ``Engine.step`` (the
``step_ms`` samples). params: {"scale": 100}"""

from chipbench.stats import percentile
from chipbench.trace import kernel_costs_minicpm_sala as costs


def read(obs, params):
    c = obs.counters
    step_ms = percentile(obs.samples.get("step_ms", []), 50)
    if obs.peaks is None or not obs.model.get("lightning_heads") \
            or not step_ms or not c.get("decode_steps"):
        return None
    steps = c["decode_steps"]
    need = costs.decode_step_bytes(
        c["decode_rows"] / steps, c.get("sala_resident_tokens", 0) / steps,
        c.get("sparse_blocks_read", 0) / steps, obs.model)
    least_ms = need / obs.peaks["hbm_bytes_per_s"] * 1e3
    return least_ms / step_ms * params.get("scale", 1.0)
