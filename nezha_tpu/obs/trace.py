"""Tracing and profiling on top of jax.profiler — plus the distributed
request-trace context re-exports.

Two kinds of tracing meet here:

- **device tracing** (this module's own code): absorbed from
  ``utils/profiling.py`` (the public names stay importable from
  ``nezha_tpu.utils``). The reference had no attested profiler subsystem
  (SURVEY.md §5); on TPU the platform tool is the XLA profiler —
  ``jax.profiler`` captures device traces (MXU occupancy, HBM traffic,
  per-op timing) viewable in TensorBoard/XProf. The context managers are
  no-ops when disabled, so call sites can stay annotated permanently.
- **distributed request tracing** (re-exported from ``obs.registry``,
  where the Span machinery lives): ``trace_context(trace_id)`` sets the
  ambient trace a request carries across the serving fleet,
  ``mint_trace_id()`` mints one at the admission edge (sampled by
  ``set_trace_sample``), ``traced_span`` / ``emit_span`` record
  per-request lifecycle fragments that ``nezha-telemetry RUN_DIR
  --trace`` stitches back into per-request timelines (obs/report.py).
"""

from __future__ import annotations

import contextlib
import os
from typing import Dict, Iterator, Optional

import jax

from nezha_tpu.obs.registry import (  # noqa: F401 — re-exported API
    NULL_SPAN,
    REGISTRY,
    Span,
    current_trace,
    emit_span,
    enabled,
    mint_trace_id,
    new_span_id,
    set_trace_sample,
    trace_context,
    trace_sample,
    traced_span,
)

# THE list of layer spans: every name :func:`annotate` /
# :func:`annotate_step` is called with, and the span each one opens
# inside (None: a root). A span's SELF time is its duration minus its
# children's by this map, which is what an idle gap of the device is
# charged to (chipbench/pass_spans.py). Read from here by
# analysis/telemetry_schema.py (the pinned names; from the source, as a
# literal: keep it one) and by the benchmark's tools and tests.
LAYER_SPANS: Dict[str, Optional[str]] = {
    "serve.sched.pass": None,
    "serve.sched.admit": "serve.sched.pass",
    "serve.sched.emit": "serve.sched.pass",
    "serve.engine.prefill": "serve.sched.admit",
    "serve.engine.dispatch": "serve.sched.pass",
    "serve.engine.wait": "serve.sched.pass",
    "train.step": None,
    "train.data": "train.step",
    "train.dispatch": "train.step",
    "train.fetch": "train.step",
    "serve.engine.bind": "serve.engine.dispatch",
    "serve.engine.tables": "serve.engine.dispatch",
    "serve.engine.launch": "serve.engine.dispatch",
    "serve.engine.fetch": "serve.engine.wait",
    "serve.engine.prefill.bind": "serve.engine.prefill",
    "serve.engine.prefill.launch": "serve.engine.prefill",
}


@contextlib.contextmanager
def profile_trace(log_dir: str,
                  create_perfetto_link: bool = False) -> Iterator[None]:
    """Capture a device trace for the enclosed block into ``log_dir``.

    Wrap a handful of steady-state steps (skip step 0 — it contains the
    compile). View with TensorBoard's profile plugin or Perfetto.
    """
    os.makedirs(log_dir, exist_ok=True)
    jax.profiler.start_trace(log_dir,
                             create_perfetto_link=create_perfetto_link)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class _Annotation:
    """One layer boundary, open on two clocks: a profiler annotation
    (always) and, while a run dir has the registry enabled, the registry
    ``Span`` of the same name and attrs (``NULL_SPAN`` otherwise).
    ``set(**attrs)`` adds what is only known inside the block (a count
    of rows admitted, tokens emitted) to both."""

    __slots__ = ("_trace", "_span")

    def __init__(self, trace, span):
        self._trace, self._span = trace, span

    def __enter__(self) -> "_Annotation":
        self._trace.__enter__()
        self._span.__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        self._span.__exit__(*exc)
        self._trace.__exit__(*exc)
        return False

    def set(self, **attrs) -> "_Annotation":
        self._trace.set_metadata(**attrs)
        self._span.set(**attrs)
        return self


def _layer_span(name: str, attrs: dict, record: bool = True):
    """The registry half of an annotation. A layer boundary belongs to a
    pass, not to a request: it never joins (or re-parents) the ambient
    request trace, so per-request timelines stitch as before."""
    return Span(name, REGISTRY, attrs) if record and enabled() \
        else NULL_SPAN


def annotate(name: str, *, record: bool = True, **attrs) -> _Annotation:
    """THE primitive for a layer boundary (``serve.sched.pass``,
    ``train.dispatch``, ...): a host span on the profiler's own
    timeline, so what the program knows shares a clock with the device
    ops of an ``.xplane.pb``. Outside a profiler session the
    ``TraceAnnotation`` is one atomic check in C++; the registry span
    exists only under ``--run-dir``, so ``spans.jsonl`` and the trace
    carry one vocabulary. ``attrs`` are cheap ints: they arrive as the
    host event's stats. ``record=False`` leaves the registry half out:
    a loop that polls while idle passes whether this turn has work, so
    an idle server writes no span record (each one is a line of
    ``spans.jsonl`` and a slot of the registry's bounded span list).
    Usable inside jit too (an XLA op annotation).
    """
    return _Annotation(jax.profiler.TraceAnnotation(name, **attrs),
                       _layer_span(name, attrs, record))


def annotate_step(name: str, step_num: int, **attrs) -> _Annotation:
    """:func:`annotate` for one iteration of a training loop: a
    ``StepTraceAnnotation``, which XProf's step view groups device ops
    by."""
    return _Annotation(
        jax.profiler.StepTraceAnnotation(name, step_num=step_num, **attrs),
        _layer_span(name, {"step": step_num, **attrs}))


class Tracer:
    """Start/stop trace control for long-running loops.

    A Trainer holds one and calls ``maybe_trace(step)`` between two steps,
    before it dispatches ``step``: the trace turns on before ``start_step``
    and off ``num_steps`` later — the standard "profile steps 10..12"
    workflow without restructuring the loop.
    """

    def __init__(self, log_dir: Optional[str] = None, start_step: int = 10,
                 num_steps: int = 3):
        self.log_dir = log_dir
        self.start_step = start_step
        self.num_steps = num_steps
        self.stop_step = start_step + num_steps
        self._active = False
        self._done = False

    @property
    def enabled(self) -> bool:
        return self.log_dir is not None

    def maybe_trace(self, step: int, sync=None) -> None:
        """``sync`` (optional) runs right before the window opens and
        right before it closes: a loop passes its device barrier, so the
        window holds whole steps only — nothing in flight at either end,
        where the profiler would catch device ops with no host span."""
        if not self.enabled:
            return
        # A resumed run's counter may start anywhere past start_step (e.g.
        # restored global_step=5000 with start_step=10): rebase the window
        # onto the first step actually observed at/after start_step, so a
        # full num_steps window is always captured exactly once.
        if not self._active and not self._done and step >= self.start_step:
            self.stop_step = step + self.num_steps
            os.makedirs(self.log_dir, exist_ok=True)
            if sync is not None:
                sync()
            jax.profiler.start_trace(self.log_dir)
            self._active = True
        elif self._active and step >= self.stop_step:
            if sync is not None:
                sync()
            self.stop()

    def stop(self) -> None:
        if self._active:
            jax.profiler.stop_trace()
            self._active = False
            self._done = True  # one window per Tracer

    def __del__(self):
        try:
            self.stop()
        except Exception:
            pass
