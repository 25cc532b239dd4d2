"""The two hyper-connection kernels' share of their roofline over the traced
window: the least time the chip could take for every call that ran (the
tokens of a call are the first dimension of its result, read from the op's
own HLO line; ``trace/kernel_costs_xing4.mhc_call`` gives a call's bytes and
operations), summed, over the calls' measured self time, summed. Decode
steps' calls and prefill chunks' alike, so the share is weighted by where the
time went. ``patterns`` maps a kernel's name to its kind.
params: {"patterns": {"pre": regex, "post": regex}, "scale": 100}"""

import re

from chipbench.trace import kernel_costs, kernel_costs_xing4 as costs, reduce

_TOKENS = re.compile(r" = \(?f32\[(\d+),")


def read(obs, params):
    if (obs.trace is None or not obs.trace.device_ops or obs.peaks is None
            or not obs.model.get("hc_streams")):
        return None
    kinds = {k: re.compile(p) for k, p in params["patterns"].items()}
    chip = min(k for k, ev in obs.trace.device_ops.items() if ev)
    least = measured = 0.0
    for e, t in reduce.self_times(obs.trace.device_ops[chip]):
        kind = next((k for k, rx in kinds.items() if rx.search(e.name)), None)
        tokens = _TOKENS.search(e.name)
        if kind is None or tokens is None:
            continue
        least += kernel_costs.min_seconds(
            costs.mhc_call(kind, int(tokens.group(1)), obs.model),
            obs.peaks)["seconds"]
        measured += t / 1e9
    if not measured:
        return None
    return least / measured * params.get("scale", 1.0)
