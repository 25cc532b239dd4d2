"""Model FLOP/s utilization: tokens per chip-second times the operations a
token needs forward + backward (6N + 6*L*d*S, computed by
trace/kernel_costs.py from the configuration), over the chip's published
bf16 peak. An end-to-end utilization, not a kernel's roofline share.
params: {"scale": 100}"""


def read(obs, params):
    c = obs.counters
    if obs.peaks is None or not c.get("train_chip_seconds"):
        return None
    rate = c["train_tokens"] / c["train_chip_seconds"]
    return (rate * c["flops_per_token"] / obs.peaks["bf16_flops"]
            * params.get("scale", 1.0))
