"""Metrics recording: JSONL sink + async-dispatch-aware step timing.

Absorbed from ``utils/metrics.py`` into the telemetry subsystem (the
public names stay importable from ``nezha_tpu.utils`` as thin
re-exports). JAX dispatch is asynchronous — ``step()`` returns before the
device finishes — so naive per-step wall timing measures Python overhead,
not the step. ``StepTimer`` measures over windows and closes each window
with a host fetch of a device scalar (the barrier; see bench.py's
note), giving true steps/sec.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, IO, Optional


class MetricsLogger:
    """Append-only JSONL metrics: one object per line with ``step`` and a
    wall-clock ``ts``. Cheap enough to call every logged step; safe to use
    as the Trainer's ``metric_logger``."""

    def __init__(self, path: str, flush_every: int = 1, mode: str = "a"):
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        self._f: Optional[IO[str]] = open(path, mode)
        self._flush_every = max(flush_every, 1)
        self._since_flush = 0
        self.path = path

    def __call__(self, step: int, metrics: Dict[str, Any]) -> None:
        self.log(step, metrics)

    def log(self, step: int, metrics: Dict[str, Any]) -> None:
        if self._f is None:
            raise ValueError("logger is closed")
        rec = {"step": int(step), "ts": time.time()}
        for k, v in metrics.items():
            # Ints stay ints (a metrics-dict "step" must not demote the
            # canonical int field to float); device/numpy scalars coerce.
            if isinstance(v, bool) or isinstance(v, int):
                rec[k] = v
            else:
                rec[k] = float(v) if hasattr(v, "__float__") else v
        self._f.write(json.dumps(rec) + "\n")
        self._since_flush += 1
        if self._since_flush >= self._flush_every:
            self._f.flush()
            self._since_flush = 0

    def close(self) -> None:
        if self._f is not None:
            self._f.close()
            self._f = None

    def __enter__(self) -> "MetricsLogger":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def read_metrics(path: str) -> list:
    """Read a JSONL metrics file back as a list of dicts."""
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out


class StepTimer:
    """Windowed steps/sec with a true device barrier per window.

    Usage::

        timer = StepTimer(window=10)
        for batch in batches:
            state, metrics = step(state, batch)
            rate = timer.tick(metrics["loss"])   # None inside a window
            if rate is not None: ...             # steps/sec for the window

    ``tick`` fetches the scalar to host only at window edges, so the
    dispatch pipeline stays full in between. For loops that pick their own
    window boundaries (the Trainer logs on global-step multiples, which a
    mid-window resume can desynchronize from a fixed tick count), use the
    explicit form: ``start()`` once, then ``lap(scalar, n)`` at each
    boundary to close a window of exactly ``n`` steps.
    """

    def __init__(self, window: int = 10):
        self.window = max(window, 1)
        self._count = 0
        self._t0: Optional[float] = None

    def tick(self, device_scalar) -> Optional[float]:
        if self._t0 is None:  # first call: sync, then open the window
            float(device_scalar)
            self._t0 = time.perf_counter()
            self._count = 0
            return None
        self._count += 1
        if self._count < self.window:
            return None
        float(device_scalar)  # barrier: all window steps actually finished
        now = time.perf_counter()
        rate = self._count / max(now - self._t0, 1e-9)
        self._t0 = now
        self._count = 0
        return rate

    # -- explicit-window form ----------------------------------------------
    def start(self) -> None:
        """Open a window now (no barrier: pair with a ``lap`` whose scalar
        sync defines the closing edge)."""
        self._t0 = time.perf_counter()
        self._count = 0

    def lap(self, device_scalar, steps: int) -> Optional[float]:
        """Close an explicit window of ``steps`` steps: barrier on the
        scalar, return steps/sec since ``start()``/the previous lap.
        Returns None when no window is open or it covered zero steps."""
        float(device_scalar)  # barrier: the window's steps actually finished
        now = time.perf_counter()
        if self._t0 is None or steps <= 0:
            self._t0 = now
            return None
        rate = steps / max(now - self._t0, 1e-9)
        self._t0 = now
        return rate

    def reset(self) -> None:
        """Forget the open window (e.g. after an elastic-recovery stall —
        the heal wait must not count against the next window's rate)."""
        self._t0 = None
        self._count = 0
