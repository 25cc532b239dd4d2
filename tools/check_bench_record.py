#!/usr/bin/env python3
"""Sanity-check committed BENCH_*.json perf records — shim over
``nezha_tpu.analysis``.

The validation core lives in ``nezha_tpu/analysis/bench_records.py``,
shared between this standalone checker and the ``bench-records`` lint
rule: every committed record must be valid JSON, a real measurement,
and platform-labeled.

This file keeps the standalone entry point and the API tier-1 tests
import (``check_dir`` / ``check_record``)::

    python tools/check_bench_record.py            # repo root
    python tools/check_bench_record.py /some/dir
"""

from __future__ import annotations

import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)
try:
    import nezha_tpu  # noqa: F401 — the full package, when jax exists
except Exception:
    # Stdlib-only fallback (see check_fault_points.py): load the
    # analysis subpackage under a namespace stub so this checker keeps
    # working on boxes without jax.
    import types
    _pkg = types.ModuleType("nezha_tpu")
    _pkg.__path__ = [os.path.join(_ROOT, "nezha_tpu")]
    sys.modules["nezha_tpu"] = _pkg

from nezha_tpu.analysis.bench_records import (  # noqa: E402,F401
    check_dir, check_record)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    root = argv[0] if argv else _ROOT
    errors = check_dir(root)
    for e in errors:
        print(e, file=sys.stderr)
    if errors:
        print(f"FAIL: {len(errors)} bench-record violation(s)",
              file=sys.stderr)
        return 1
    print("OK: committed bench records are platform-labeled and "
          "schema-valid")
    return 0


if __name__ == "__main__":
    sys.exit(main())
