"""Rule ``telemetry-schema``: source instrument names match the pins.

``tools/check_telemetry_schema.py`` validates run-dir CAPTURES; this
rule closes the other half of the loop at the SOURCE: every literal
``obs.counter("...")`` / ``obs.gauge`` / ``obs.histogram`` /
``obs.span`` whose name falls in a pinned namespace (``serve.`` /
``router.`` / ``dist.`` / ``checkpoint.``) must be a member of the
pinned set for its instrument kind — and of the RIGHT kind (a
``serve.ttft_s`` counter would be a schema violation even though the
name exists as a histogram). A typo'd instrument therefore fails the
lint when the call site lands, instead of surfacing as a blank
dashboard panel after the capture ships.

Typed events (PR 16) face the same contract: a literal
``obs.record_event("...")`` kind under the ``watchdog.`` / ``slo.``
namespaces must be a member of the pinned ``EVENT_KINDS`` registry —
alert routing and ``nezha-telemetry --slo`` key on event kinds exactly
as dashboards key on instrument names.

Dynamic names (f-strings, variables) are skipped, never guessed — the
run-dir validator still catches those at capture time."""

from __future__ import annotations

import ast
from typing import List

from nezha_tpu.analysis import telemetry_schema as ts
from nezha_tpu.analysis.core import Finding, rule
from nezha_tpu.analysis.index import SourceIndex, call_name, str_arg

_KIND_SETS = {
    "counter": ("counter", ts.PINNED_COUNTERS),
    "gauge": ("gauge", ts.PINNED_GAUGES),
    "histogram": ("histogram", ts.PINNED_HISTOGRAMS),
}


@rule("telemetry-schema",
      "literal obs.counter/gauge/histogram/span names under the pinned "
      "namespaces are members of the pinned schema sets (right name AND "
      "right instrument kind); literal obs.record_event kinds under "
      "watchdog./slo. are members of the pinned event registry")
def check(index: SourceIndex) -> List[Finding]:
    findings: List[Finding] = []
    for mod in index:
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            cn = call_name(node) or ""
            if not cn.startswith("obs."):
                continue
            kind = cn[len("obs."):]
            # The retroactive (emit_span), trace-gated (traced_span)
            # and profiler-clock (annotate, annotate_step) forms
            # record into the same span stream — their literal
            # names face the identical pinned-registry contract.
            if kind in ("emit_span", "traced_span", "annotate",
                        "annotate_step"):
                kind = "span"
            name = str_arg(node)
            if name is None:
                continue
            # faults.injected_total rides in the serve set but is not
            # namespace-prefixed; only pinned namespaces are enforced.
            if kind == "record_event":
                if not name.startswith(ts.EVENT_KIND_PREFIXES):
                    continue
                if name not in ts.EVENT_KINDS:
                    findings.append(_finding(
                        index, mod, node, name,
                        f"event kind {name!r} is not in the pinned "
                        f"event registry (EVENT_KINDS) for its "
                        f"namespace — add it to "
                        f"analysis/telemetry_schema.py (and the "
                        f"RUNBOOK event taxonomy) deliberately"))
                continue
            if kind == "span":
                if not name.startswith(ts.PINNED_SPAN_PREFIXES):
                    continue
                if name not in ts.PINNED_SPANS:
                    findings.append(_finding(
                        index, mod, node, name,
                        f"span name {name!r} is not in the pinned span "
                        f"registry for its namespace — add it to "
                        f"analysis/telemetry_schema.py (and the docs) "
                        f"deliberately"))
                continue
            if kind not in _KIND_SETS:
                continue
            if not name.startswith(ts.PINNED_METRIC_PREFIXES):
                continue
            label, members = _KIND_SETS[kind]
            if name in members:
                continue
            other = [k for k, (_, s) in _KIND_SETS.items()
                     if k != kind and name in s]
            if other:
                msg = (f"{name!r} is pinned as a {other[0]} but used "
                       f"as a {label} — instrument kind mismatch")
            else:
                msg = (f"{label} name {name!r} is not in the pinned "
                       f"schema for its namespace — add it to "
                       f"analysis/telemetry_schema.py (and "
                       f"register_*_instruments) deliberately")
            findings.append(_finding(index, mod, node, name, msg))
    return findings


def _finding(index, mod, node, name, msg) -> Finding:
    return Finding(file=mod.rel, line=node.lineno,
                   rule="telemetry-schema",
                   symbol=index.qualname(mod, node), detail=name,
                   message=msg)
