"""The device a run is on, and the compile cache it keeps."""

from __future__ import annotations

import json
import os
import time

from chipbench.manifest import REPO, ROOT

# The one CPU path: an explicit rehearsal pin used by the tests. A
# rehearsal prints ``platform: cpu`` and no device metric.
REHEARSAL_ENV = "CHIPBENCH_REHEARSAL"


def rehearsal() -> bool:
    return os.environ.get(REHEARSAL_ENV) == "cpu"


def compile_cache_dir() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache`` at the
    root of the checkout: a fixed path (it is part of the cache's key) and
    the one the program's own ``enable_persistent_compile_cache`` takes,
    so the CLI entry points the benchmark calls write to the same cache."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(REPO, ".jax_cache"))


def start(chips: int):
    """Devices for a cell that needs ``chips``, or exit non-zero: no
    accelerator, or fewer chips than asked for, prints no result."""
    import jax

    if rehearsal():
        jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    platform = devices[0].platform
    if not rehearsal() and platform != "tpu":
        raise SystemExit(f"chipbench: needs a TPU; jax reports {platform!r} "
                         f"(set {REHEARSAL_ENV}=cpu to rehearse)")
    if len(devices) < chips:
        raise SystemExit(f"chipbench: cell needs {chips} chip(s); jax "
                         f"reports {len(devices)}")
    return devices


def info(devices, chips: int) -> dict:
    """``device`` of the result line, as jax reports it. ``count`` is what
    jax sees; ``memory_peak_bytes`` is the peak on the fullest chip used:
    ``peak_bytes_in_use`` (arrays) plus ``peak_bytes_reserved`` (what the
    TPU runtime sets aside for the loaded programs' temporaries, which the
    first figure leaves out: a train step that needs 9 GB reads 1.6 GB
    there)."""
    peak = 0
    for d in devices[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def peaks(kind: str) -> dict:
    """The chip's published peaks; an unknown kind is an error."""
    with open(os.path.join(ROOT, "trace", "peaks.json")) as f:
        table = json.load(f)["device_kinds"]
    if kind not in table:
        raise SystemExit(f"chipbench: no peaks on record for device kind "
                         f"{kind!r}; add it to trace/peaks.json with its "
                         f"source")
    return table[kind]


class CompileCounter:
    """Counts XLA backend compilations (not cache reads) and persistent
    cache hits/misses, as jax itself reports them."""

    def __init__(self):
        import jax.monitoring

        self.compile_times = []     # perf_counter stamp of each one
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def _duration(self, event: str, _secs: float, **__) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_times.append(time.perf_counter())

    def compiles_between(self, t0: float, t1: float) -> int:
        """Programs built (compiled, or read from the persistent cache)
        in [t0, t1): inside a measured window there must be none."""
        return sum(1 for t in self.compile_times if t0 <= t < t1)

    def snapshot(self) -> dict:
        return {"compiles": len(self.compile_times), "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}
