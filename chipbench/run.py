#!/usr/bin/env python3
"""One run of one cell: load, warm up, measure, print one JSON line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A fresh process every time. Earlier stdout lines are JSON facts about the
run (set-up phases, the reference comparison, generator lateness); the LAST
line is the result: ``correct``, ``attempted``, ``failed``, ``metrics``
(``--trace 0``: the cell's end-to-end metrics; ``--trace 1``: its per-layer
metrics), ``device`` and, when traced, ``breakdown``. No TPU, or fewer
chips than the cell needs: non-zero exit and no result. The one CPU path is
the rehearsal pin ``CHIPBENCH_REHEARSAL=cpu`` the tests use; its result
says ``platform: cpu`` and carries counts only.
"""

from __future__ import annotations

import time

T_PROCESS0 = time.perf_counter()

import argparse
import importlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from chipbench import device, manifest  # noqa: E402


def say(**facts) -> None:
    print(json.dumps(facts, default=str), flush=True)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--root", default=manifest.ROOT,
                   help="directory holding cells/, configs/, traffic/, "
                        "metrics/, readers/ (a test's temporary copy)")
    p.add_argument("--keep-trace", default=None,
                   help="copy the .xplane.pb here (by hand, for reading)")
    args = p.parse_args(argv)

    cell = manifest.load_cell(args.workload, args.root)
    chips = cell["chips"]
    devices = device.start(chips)
    work_dir = os.path.join(manifest.REPO, ".chipbench_work", cell["name"])
    os.makedirs(work_dir, exist_ok=True)

    driver = importlib.import_module(
        f"chipbench.drivers.{cell['traffic']['driver']}")
    job, head = driver.run(cell, args, work_dir, T_PROCESS0)
    obs = job.obs
    rehearsal = device.rehearsal()
    dev = device.info(devices, chips)
    job.facts["memory_stats_chip0"] = devices[0].memory_stats()
    if dev["memory_peak_bytes"]:
        obs.set("memory_peak_bytes", dev["memory_peak_bytes"])
    if not rehearsal:
        obs.peaks = device.peaks(dev["kind"])

    breakdown = None
    if args.trace and job.trace_dir:
        from chipbench.trace import reduce
        xplane = reduce.find_xplane(job.trace_dir)
        if xplane is None:
            raise SystemExit(f"chipbench: no trace under {job.trace_dir}")
        obs.trace = reduce.load(xplane, driver.ANNOTATIONS)
        obs.trace_span = job.trace_span
        if args.keep_trace:
            import shutil
            os.makedirs(args.keep_trace, exist_ok=True)
            shutil.copy(xplane, args.keep_trace)
        if obs.trace.device_ops:
            dev.update(reduce.summary(obs.trace, chips))
            breakdown = reduce.breakdown(obs.trace)
        job.facts["trace_structure"] = obs.trace.structure

    files = cell["metrics"]["per_layer" if args.trace else "end_to_end"]
    if rehearsal:
        # A CPU rehearsal proves paths and counts. No time, rate or share
        # of a device is printed under a metric's name.
        files = [m for m in files if m["source"] == "program_counter"]
    metrics = manifest.read_metrics(files, obs, args.root)
    say(facts=job.facts)
    result = {**head, "metrics": metrics, "device": dev}
    if breakdown is not None:
        result["breakdown"] = breakdown
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
