"""One run of a benchmark cell with the program's registry and a run dir's
sink ON, as under ``nezha-serve --run-dir`` (run on the chip).

``chipbench/run.py`` drives the stack through ``cli/serve.py::_build_stack``,
which opens no run: the benchmark measures with the registry off. This opens
one around it (``obs.start_run``: registry, rolling windows and ``RunSink``,
the default trace sample, so every request is traced), runs the cell as the
arguments after ``--`` say, closes the run and prints, after the cell's own
result line, one JSON line with what only the registry counts
(``serve.sampling.full_sort_steps_total``, the ``serve.moe.*`` counters) and
what the run dir holds. PERF.md's "registry on" readings are pairs of this
beside the same command without it (PR 34).

Usage: chiprun --chips 1 -- python3 experiments/registry_on.py \
           chiprun_out/registry_on -- --workload gpt2-124m.batch-gen \
           --seed 5 --seconds 40 --trace 0
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COUNTERS = ("serve.tokens_total", "serve.admitted_total",
            "serve.sampling.full_sort_steps_total", "serve.moe.pairs_total",
            "serve.moe.held_pairs_total", "serve.moe.expert_visits_total",
            "serve.moe.experts_touched_total")


def main(argv) -> int:
    if len(argv) < 3 or argv[1] != "--":
        print(__doc__, file=sys.stderr)
        return 2
    run_dir = argv[0]
    sys.path.insert(0, ROOT)
    from chipbench import run            # its clock starts at this import
    from nezha_tpu import obs

    obs.start_run(run_dir, meta={"kind": "serve", "mode": "chipbench"})
    try:
        rc = run.main(argv[2:])
        counters = {k: obs.counter(k).value for k in COUNTERS}
        tpot = obs.histogram("serve.tpot_s").summary()
        recorded = len(obs.REGISTRY.spans)
    finally:
        obs.end_run()
    spans = os.path.join(run_dir, obs.SPANS_FILE)
    with open(spans) as f:
        lines = sum(1 for _ in f)
    print(json.dumps({"registry_on": {
        "run_dir": run_dir, "counters": counters,
        "tpot_s": {k: tpot[k] for k in ("count", "mean", "p50", "p99")},
        "span_records_kept": recorded, "spans_jsonl_lines": lines,
        "spans_jsonl_bytes": os.path.getsize(spans)}}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
