#!/usr/bin/env python3
"""The knee sweep: the highest rate an open-loop cell's system sustains.

    python3 chipbench/sweep.py --workload gpt2-124m.chat-prefix \\
        --rates 8,16,24,32,48,64,96 --seconds 8 [--seed 0]

Not a cell: run once, by hand, on the chip, when a mix is defined; the
rate then goes into the traffic file as a number (a cell offers a fixed
rate and never searches for one). One process: the stack is built once and
each rate gets warm-up traffic and one window, with a drain in between.
Prints one JSON line a rate and, last, the table and the knee: the highest
rate whose window ended with no backlog (at most ``--backlog-share`` of the
requests offered still without a first token, and the last third's median
TTFT at most ``--growth`` times the first third's).
"""

from __future__ import annotations

import time

T_PROCESS0 = time.perf_counter()

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from chipbench import device, manifest  # noqa: E402
from chipbench.stats import percentile  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=8.0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--root", default=manifest.ROOT)
    p.add_argument("--backlog-share", type=float, default=0.02)
    p.add_argument("--growth", type=float, default=2.0)
    args = p.parse_args(argv)

    from chipbench.drivers import serve

    cell = manifest.load_cell(args.workload, args.root)
    if cell["traffic"]["generator"] != "open_loop":
        raise SystemExit("sweep: only an open-loop mix has a knee")
    devices = device.start(cell["chips"])
    work_dir = os.path.join(manifest.REPO, ".chipbench_work", "sweep")
    os.makedirs(work_dir, exist_ok=True)
    job = serve.ServeRun(cell, args.seed, args.seconds, False, work_dir)
    job.setup(T_PROCESS0)
    print(json.dumps({"facts": job.facts}, default=str), flush=True)
    rows = []
    for rate in [float(r) for r in args.rates.split(",")]:
        head = job.measure(args.seconds, rate)
        obs = job.obs
        ttft = obs.samples.get("ttft_ms", [])
        third = max(len(ttft) // 3, 1)
        first, last = percentile(ttft[:third], 50), percentile(ttft[-third:], 50)
        attempted = max(head["attempted"], 1)
        unstarted = job.facts["unstarted_at_end"]
        sustained = (bool(ttft) and head["failed"] == 0
                     and unstarted <= max(2, args.backlog_share * attempted)
                     and last <= args.growth * first)
        row = {"rate_per_s": rate, "attempted": head["attempted"],
               "failed": head["failed"], "first_tokens": len(ttft),
               "unstarted_at_end": unstarted,
               "queue_depth_at_end": job.facts["queue_depth_at_end"],
               "ttft_p50_ms": percentile(ttft, 50),
               "ttft_p95_ms": percentile(ttft, 95),
               "ttft_p50_first_third_ms": first,
               "ttft_p50_last_third_ms": last,
               "itl_p99_ms": percentile(obs.samples.get("itl_ms", []), 99),
               "out_tok_s": obs.counters.get("tokens_in_span", 0)
                   / max(obs.counters.get("token_span_s", 0), 1e-9),
               "step_ms_p50": percentile(obs.samples.get("step_ms", []), 50),
               "lateness_p99_ms":
                   job.facts["generator_lateness_ms"]["p99"],
               "sustained": sustained}
        rows.append(row)
        print(json.dumps(row), flush=True)
        job.drain()
    knee = max((r["rate_per_s"] for r in rows if r["sustained"]),
               default=None)
    print(json.dumps({"memory_stats": devices[0].memory_stats()}), flush=True)
    print(json.dumps({"device": device.info(devices, cell["chips"]),
                      "seconds": args.seconds, "rows": rows,
                      "knee_per_s": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
