"""Test rig: force an 8-device virtual CPU platform BEFORE jax initializes,
so collectives/sharding tests run the real multi-chip code paths on any host
(SURVEY.md §4 test strategy)."""

import os

# Force CPU regardless of the ambient platform (tests need 8 virtual
# devices for the multi-chip paths). Plugins (jaxtyping) import jax before
# this conftest runs, so the env default is already baked — override via
# jax.config, which works any time before backend initialization.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

# Persistent XLA compilation cache: the suite is compile-bound on the
# 1-core build box (~40 CLI tests each jitting multi-second programs), and
# identical programs recur both across runs and across the worker processes
# the multi-process tests spawn (workers inherit the env var set here).
import sys as _sys

_sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 ".."))
from nezha_tpu.utils.compile_cache import (  # noqa: E402
    enable_persistent_compile_cache,
)

os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                 ".jax_cache"))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
enable_persistent_compile_cache()

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


def worker_env():
    """Environment for worker OS processes (one-device hosts): repo root on
    PYTHONPATH (extended, never replaced), the suite's forced 8-device flag
    scrubbed so each worker sees its own single CPU device. Workers inherit
    JAX_COMPILATION_CACHE_DIR (set above), so repeated launches of the same
    tiny-preset programs deserialize instead of recompiling."""
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo_root + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if not f.startswith("--xla_force_host_platform_device_count"))
    return env


class TwoRankElastic:
    """Scaffolding for the elastic-recovery CLI tests: a 2-rank mlp_mnist
    control-plane world (`--on-failure rejoin`, shared --ckpt-dir,
    coordinator on rank 0), per-rank stderr files, metrics-line polling,
    and guaranteed process reaping. Tests drive kills/relaunches."""

    def __init__(self, tmp_path, rejoin_timeout="120"):
        import socket
        import sys

        self.tmp_path = tmp_path
        self.env = worker_env()
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        self.ck = str(tmp_path / "ck")
        self.base = [sys.executable, "-m", "nezha_tpu.cli.train",
                     "--config", "mlp_mnist", "--batch-size", "64",
                     "--platform", "cpu", "--log-every", "25",
                     "--failure-check-every", "5", "--ckpt-dir", self.ck,
                     "--coordinator", f"127.0.0.1:{self.port}",
                     "--no-jax-distributed", "--on-failure", "rejoin",
                     "--rejoin-timeout", str(rejoin_timeout)]
        self.procs = []
        self.errfiles = []

    def launch(self, tag, extra):
        import subprocess

        errf = open(self.tmp_path / f"{tag}.err", "w+")
        self.errfiles.append(errf)
        p = subprocess.Popen(self.base + extra, stdout=subprocess.DEVNULL,
                             stderr=errf, text=True, env=self.env)
        self.procs.append(p)
        return p

    def err(self, tag) -> str:
        return (self.tmp_path / f"{tag}.err").read_text()

    def wait_for(self, tag, needle, proc, timeout=120):
        """Poll a rank's stderr for ``needle`` while it stays alive."""
        import time

        deadline = time.monotonic() + timeout
        while needle not in self.err(tag):
            assert proc.poll() is None, self.err(tag)
            assert time.monotonic() < deadline, self.err(tag)
            time.sleep(0.25)

    def cleanup(self):
        for p in self.procs:
            if p.poll() is None:
                p.kill()
                p.wait()
        for f in self.errfiles:
            f.close()


def run_worker_processes(argv_per_rank, timeout=300):
    """Launch one OS process per argv list (modelling one-device hosts) and
    return [(returncode, stdout, stderr)]. Shared harness for the
    multi-process launch tests; workers always reaped on timeout."""
    import subprocess

    env = worker_env()
    procs = [subprocess.Popen(argv, stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True, env=env)
             for argv in argv_per_rank]
    try:
        outs = [p.communicate(timeout=timeout) for p in procs]
    finally:  # never leak a wedged worker (hung initialize, etc.)
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    return [(p.returncode, out, err) for p, (out, err) in zip(procs, outs)]
