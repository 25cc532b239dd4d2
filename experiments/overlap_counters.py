"""One run of a serving cell through ``chipbench/run.py``, with the
engine's own ledgers of the block in flight printed beside it (run on the
chip; PR 37).

The benchmark measures with the registry off, so
``serve.engine.blocks_overlapped_total`` / ``settles_total`` /
``stale_rows_total`` count nothing there; the ``Engine`` keeps the same
three as plain integers beside ``step_calls``. This runs the cell as the
arguments say and prints, before the cell's own fact and result lines, one
JSON line with those integers at the end of the measured window, the share
of launches made with a block in flight, and the period between the ends of
two ``Engine.step`` calls in the window (what a decode pass costs end to
end: ``engine.step_ms_p50`` times the call alone). A tree without the
mechanism (the parent) prints nulls for the integers and runs the same.

It takes the tree it runs from off the working directory, so the parent is
measured from its own export with this one file:

    chiprun --chips 1 -- bash -c 'cd export_check && python3 \
        ../experiments/overlap_counters.py --workload gpt2-124m.batch-gen \
        --seed 5 --seconds 40 --trace 0'
"""

import json
import os
import sys


def main(argv) -> int:
    sys.path.insert(0, os.getcwd())
    from chipbench import run            # its clock starts at this import
    from chipbench.drivers import serve
    import numpy as np

    measure = serve.ServeRun.measure

    def measured(self, *args, **kw):
        head = measure(self, *args, **kw)
        eng = self.engine
        ends = np.asarray([t for t, _, _ in self.obs.steps
                           if self.win[0] <= t < self.win[1]])
        calls = getattr(eng, "step_calls", None)
        over = getattr(eng, "blocks_overlapped", None)
        print(json.dumps({"overlap": {
            "step_calls": calls, "blocks_overlapped": over,
            "overlap_share": over / calls if over is not None else None,
            "settles": getattr(eng, "settles", None),
            "stale_rows": getattr(eng, "stale_rows", None),
            "calls_in_window": int(ends.size),
            "pass_period_ms_p50": float(np.median(np.diff(ends)) * 1e3)
            if ends.size > 1 else None}}), flush=True)
        return head

    serve.ServeRun.measure = measured
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
