"""What one run observed: samples, counters and (in a traced run) the
reduced trace. Drivers fill it; metric readers only read it."""

from __future__ import annotations

from typing import Dict, List, Optional


class Obs:
    def __init__(self):
        self.samples: Dict[str, List[float]] = {}
        self.counters: Dict[str, float] = {}
        # serve: (time, active rows, resident tokens) of each decode step
        # in the window, for the decode kernel's bytes
        self.steps: List[tuple] = []
        self.trace = None                   # chipbench.trace.reduce.Trace
        self.trace_span: Optional[tuple] = None   # (t0, t1), driver clock
        self.peaks: Optional[dict] = None   # the chip's published peaks
        self.model: dict = {}               # sizes the cost functions need

    def sample(self, name: str, value: float) -> None:
        self.samples.setdefault(name, []).append(value)

    def count(self, name: str, by: float = 1.0) -> None:
        self.counters[name] = self.counters.get(name, 0.0) + by

    def set(self, name: str, value: float) -> None:
        self.counters[name] = float(value)
