"""Committed BENCH_*.json hygiene — the validation core.

A bench run that DIED must never be committed as if it were a
measurement. Every committed record must be

- **valid JSON**, and
- a **real measurement** — either a driver round record (``rc == 0``
  with a non-null parsed metric) or a ``nezha-bench`` baseline
  (non-empty ``by_platform`` slots), and
- **platform-labeled** — a top-level ``platform``/``backend`` field, a
  platform inside ``parsed``, or ``by_platform`` keys — so a CPU number
  can never masquerade as (or overwrite) a TPU anchor.

There is no exemption: a crash record fails the build the moment it
lands, and the way to keep the history is the notes, not the file.

Consumed by the ``bench-records`` lint rule and by the
``tools/check_bench_record.py`` shim (tier-1 via
tests/test_bench_record.py)."""

from __future__ import annotations

import glob
import json
import os
from typing import List


def _platform_label(rec: dict) -> str:
    """The record's platform label, '' when unlabeled."""
    for key in ("platform", "backend"):
        v = rec.get(key)
        if isinstance(v, str) and v:
            return v
    parsed = rec.get("parsed")
    if isinstance(parsed, dict):
        for key in ("platform", "backend"):
            v = parsed.get(key)
            if isinstance(v, str) and v:
                return v
    by = rec.get("by_platform")
    if isinstance(by, dict) and by:
        return ",".join(sorted(str(k) for k in by))
    return ""


def check_record(path: str) -> List[str]:
    """-> violations for one committed record file (empty = valid)."""
    name = os.path.basename(path)
    try:
        with open(path) as f:
            rec = json.load(f)
    except OSError as e:
        return [f"{name}: unreadable ({e})"]
    except ValueError:
        return [f"{name}: not valid JSON"]
    if not isinstance(rec, dict):
        return [f"{name}: record must be a JSON object"]
    errors: List[str] = []
    if "rc" in rec:
        # Driver round record: {n, cmd, rc, tail, parsed}.
        if rec.get("rc") != 0:
            errors.append(
                f"{name}: CRASH RECORD (rc={rec.get('rc')!r}) — not a "
                f"measurement; drop it")
        elif not isinstance(rec.get("parsed"), dict) \
                or "value" not in rec["parsed"]:
            errors.append(
                f"{name}: rc=0 but no parsed metric — the run printed "
                f"nothing measurable")
    elif "by_platform" in rec:
        by = rec.get("by_platform")
        if not isinstance(by, dict) or not by:
            errors.append(f"{name}: 'by_platform' must be a non-empty "
                          f"object of per-platform slots")
    else:
        errors.append(
            f"{name}: unrecognized record shape (neither a driver "
            f"round record with 'rc' nor a nezha-bench 'by_platform' "
            f"baseline)")
    if not errors and not _platform_label(rec):
        errors.append(
            f"{name}: no platform label (top-level 'platform'/"
            f"'backend', parsed.platform, or by_platform keys) — "
            f"unlabeled numbers cannot be gated per-platform")
    return errors


def check_dir(root: str) -> List[str]:
    """Validate every committed BENCH_*.json under ``root``.
    -> violations."""
    paths = sorted(glob.glob(os.path.join(root, "BENCH_*.json")))
    if not paths:
        return [f"no BENCH_*.json records found under {root}"]
    errors: List[str] = []
    for path in paths:
        errors.extend(check_record(path))
    return errors
