"""The device's idle time of the traced window for each decode pass of the
program: idle nanoseconds (every gap of the chip inside the window, or
only those that ``reduce.gaps_by_annotation`` gives to one of ``owners``)
over the number of ``per`` host events that enclose a ``holding`` event.
With ``per`` the scheduler's pass and ``holding`` the engine's dispatch
that counts the passes that decoded: an idle poll holds no dispatch, and
``reduce.load`` keeps no attrs of a host event, so ``live`` cannot be
asked. Unlike an idle SHARE this does not move when a kernel gets faster:
it is the host's turnaround, which a shorter step leaves as it was. A run
that was not traced, a driver that keeps neither span, or a window
without such a pass reads as nothing (None).
params: {"per": name, "holding": name, "owners": [names] | null,
         "scale": 1e-6}"""

from chipbench.trace import reduce


def read(obs, params):
    trace = obs.trace
    if trace is None or not trace.device_ops:
        return None
    held = [e for e in trace.host if e.name == params["holding"]]
    passes = sum(
        1 for p in trace.host if p.name == params["per"]
        and any(p.start_ns <= h.start_ns and h.end_ns <= p.end_ns
                for h in held))
    if not passes:
        return None
    owners = params.get("owners")
    if owners is None:
        idle_ns = sum(b - a for a, b in reduce.idle_gaps(trace))
    else:
        idle_ns = 1e9 * sum(s for name, s in reduce.gaps_by_annotation(trace)
                            if name in owners)
    return idle_ns / passes * params.get("scale", 1.0)
