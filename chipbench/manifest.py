"""Finds a cell's files by name.

Everything that belongs to one configuration, one traffic mix, one cell or
one metric is a file of its own under ``chipbench/``:

    cells/<cell>.json       configuration + traffic + chips + the metrics
    configs/<config>.json   sizes, source, deployment
    traffic/<traffic>.json  generator name and its parameters
    metrics/<metric>.json   unit, direction, source, reader and its
                            parameters (for a per-layer metric: layer, moves)
    readers/<reader>.py     ``read(obs, params) -> float | None``

A later PR adds files and entries to ``BENCHMARK.json``; it edits nothing
that is here. ``root`` is the directory that holds those directories, so a
test can load a cell from a temporary copy.
"""

from __future__ import annotations

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(ROOT)


def _load(root: str, kind: str, name: str) -> dict:
    path = os.path.join(root, kind, f"{name}.json")
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SystemExit(f"chipbench: no {kind} file {path}") from None


def load_cell(name: str, root: str = ROOT) -> dict:
    """The cell with its configuration, traffic and metric files loaded."""
    cell = _load(root, "cells", name)
    cell["config"] = _load(root, "configs", cell["config"])
    cell["traffic"] = _load(root, "traffic", cell["traffic"])
    cell["metrics"] = {kind: [_load(root, "metrics", m) for m in cell[kind]]
                       for kind in ("end_to_end", "per_layer")}
    return cell


def load_reader(name: str, root: str = ROOT):
    """``readers/<name>.py``'s ``read`` function."""
    path = os.path.join(root, "readers", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_reader_{name}", path)
    if spec is None or not os.path.exists(path):
        raise SystemExit(f"chipbench: no reader {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def read_metrics(metric_files: list, obs, root: str = ROOT) -> dict:
    """{name: {"value", "unit"}} for every metric whose reader finds
    something to read; a reader that returns None leaves its metric out."""
    out = {}
    for m in metric_files:
        value = load_reader(m["reader"], root)(obs, m.get("params", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
