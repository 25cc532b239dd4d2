#!/usr/bin/env python3
"""The program's layer spans, read by hand from a kept trace.

    python3 chipbench/run.py --workload <cell> --seed <n> --trace 1 \\
        --keep-trace chiprun_out/<dir>
    python3 chipbench/layer_spans.py chiprun_out/<dir>/*.xplane.pb

The program opens ten spans on the profiler's clock
(``nezha_tpu.obs.annotate``, PR 24). A run of a cell does not read them
yet: the drivers' ``ANNOTATIONS`` do not name them and no cell's file
lists the three metrics made for them, and a PR that changes the program
may edit neither. Until a ``benchmark`` issue appends ``SPANS`` to both
``ANNOTATIONS`` and ``METRICS`` to the cells' ``per_layer`` lists
(PERF.md, open questions), this prints what the result line will then
carry: the metrics in waiting (``metrics/<name>.json``, reader
``host_span``) and, for each chip, its idle seconds under the innermost
span open at each gap's middle.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import manifest  # noqa: E402
from chipbench.obs import Obs  # noqa: E402
from chipbench.trace import reduce  # noqa: E402

SPANS = ("serve.sched.pass", "serve.sched.admit", "serve.sched.emit",
         "serve.engine.prefill", "serve.engine.dispatch", "serve.engine.wait",
         "train.step", "train.data", "train.dispatch", "train.fetch")
METRICS = ("sched.host_ms_per_pass", "engine.dispatch_ms_p50",
           "trainer.host_ms_per_step")


def read(xplane: str) -> dict:
    obs = Obs()
    obs.trace = reduce.load(xplane, SPANS)
    files = []
    for name in METRICS:
        with open(os.path.join(manifest.ROOT, "metrics", f"{name}.json")) as f:
            files.append(json.load(f))
    events: dict = {}
    for e in obs.trace.host:
        events[e.name] = events.get(e.name, 0) + 1
    return {"metrics": manifest.read_metrics(files, obs),
            "host_events": events,
            "idle_gaps": {str(chip): reduce.gaps_by_annotation(obs.trace, chip)
                          for chip in sorted(obs.trace.device_ops)},
            **reduce.summary(obs.trace)}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("xplane")
    args = p.parse_args(argv)
    print(json.dumps(read(args.xplane)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
