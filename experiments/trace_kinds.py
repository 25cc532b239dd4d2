"""By hand: a kept trace's device ops by kind with their COUNT, self time and
time a call, for the ops whose name matches a regex (the benchmark's
``breakdown`` gives the ten heaviest kinds and no counts):

    python3 experiments/trace_kinds.py /tmp/tr/*.xplane.pb 'nezha_|fusion'
"""

from __future__ import annotations

import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench.trace import reduce  # noqa: E402


def main(argv) -> int:
    trace = reduce.load(argv[0])
    rx = re.compile(argv[1] if len(argv) > 1 else ".")
    chip = min(k for k, ev in trace.device_ops.items() if ev)
    kinds: dict = {}
    for e, t in reduce.self_times(trace.device_ops[chip]):
        if rx.search(e.name):
            k = kinds.setdefault(reduce.op_kind(e.name), [0, 0.0])
            k[0] += 1
            k[1] += t / 1e9
    print(json.dumps({"summary": reduce.summary(trace, 1)}))
    for kind, (n, s) in sorted(kinds.items(), key=lambda kv: -kv[1][1])[:40]:
        print(json.dumps({"kind": kind, "calls": n, "seconds": round(s, 5),
                          "us_a_call": round(s / n * 1e6, 1)}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
