"""Rule ``bench-records``: committed perf records are real measurements.

The lint-rule face of :mod:`nezha_tpu.analysis.bench_records`: every
committed ``BENCH_*.json`` at the repo root must be valid JSON, a
genuine measurement, and platform-labeled. Running it through
``nezha-lint`` means one invocation covers source contracts and
committed artifacts alike."""

from __future__ import annotations

import re
from typing import List

from nezha_tpu.analysis.bench_records import check_dir
from nezha_tpu.analysis.core import Finding, rule
from nezha_tpu.analysis.index import SourceIndex

_FILE_RE = re.compile(r"(BENCH_\w+\.json)")


@rule("bench-records",
      "every committed BENCH_*.json is valid JSON, a real measurement "
      "(rc==0 + parsed metric, or by_platform slots), and platform-"
      "labeled")
def check(index: SourceIndex) -> List[Finding]:
    findings: List[Finding] = []
    for msg in check_dir(index.root):
        m = _FILE_RE.search(msg)
        fname = m.group(1) if m else "BENCH_*.json"
        findings.append(Finding(
            file=fname, line=0, rule="bench-records",
            symbol="record", detail=msg.split(":", 1)[-1].strip()[:60],
            message=msg))
    return findings
