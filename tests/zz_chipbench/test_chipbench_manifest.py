"""BENCHMARK.json and the files it names agree, and a new cell needs only
new files."""

import json
import os
import re

import pytest

from chipbench import manifest
import chipbench_tiny

REPO = manifest.REPO
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_manifest_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "chipbench/run.py"]
    assert bench["paths"] == ["chipbench", "tests/zz_chipbench"]
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert w["chips"] in (1, 4)
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])


def test_names_and_units_use_allowed_characters(bench):
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    names += [w[k] for w in bench["workloads"] for k in ("config", "traffic")]
    for n in names:
        assert NAME.match(n), n
    metric_names = [m["name"] for k in ("end_to_end", "per_layer")
                    for m in bench[k]]
    assert len(metric_names) == len(set(metric_names))
    for k in ("end_to_end", "per_layer"):
        for m in bench[k]:
            assert UNIT.match(m["unit"]), m
            assert m["better"] in ("lower", "higher")


def test_every_named_file_exists_and_agrees(bench):
    cells = {w["name"]: w for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for c in bench["configs"]:
        assert c["file"] == f"chipbench/configs/{c['name']}.json"
        with open(os.path.join(REPO, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        assert any(w["config"] == c["name"] for w in bench["workloads"])
    layers = set()
    for name, w in cells.items():
        cell = manifest.load_cell(name)
        assert cell["chips"] == w["chips"] and cell["why"] == w["why"]
        assert cell["config"]["name"] == w["config"]
        assert cell["traffic"]["name"] == w["traffic"]
        assert "setup_s" in [m["name"] for m in cell["metrics"]["end_to_end"]]
        assert len(cell["metrics"]["end_to_end"]) >= 2
        assert cell["metrics"]["per_layer"]
        for kind, table in (("end_to_end", e2e), ("per_layer", per_layer)):
            for m in cell["metrics"][kind]:
                entry = table[m["name"]]
                for key in ("unit", "better", "source"):
                    assert m[key] == entry[key], (m["name"], key)
                assert name in entry.get("workloads", cells)
                assert "workloads" not in m     # the manifest's to say
                manifest.load_reader(m["reader"])
                if kind == "per_layer":
                    assert m["layer"] == entry["layer"]
                    assert m["moves"] == entry["moves"]
                    # reported only where the metric it moves is
                    assert m["moves"] in [x["name"] for x in
                                          cell["metrics"]["end_to_end"]]
                    layers.add(m["layer"])
        # and nothing the manifest lists for this cell is missing from it
        for kind, table in (("end_to_end", e2e), ("per_layer", per_layer)):
            listed = {n for n, m in table.items()
                      if name in m.get("workloads", cells)}
            assert listed == {m["name"] for m in cell["metrics"][kind]}
    perf = open(os.path.join(REPO, "PERF.md")).read()
    for layer in layers:
        assert layer in perf, f"PERF.md's list of layers lacks {layer!r}"


def test_the_cell_in_waiting_loads(bench):
    """``gpt2-124m.chat-prefix`` is built and measured but not a manifest
    entry yet (PERF.md): its files must stay loadable, so that adding it is
    manifest entries and nothing else."""
    assert "gpt2-124m.chat-prefix" not in [w["name"] for w in bench["workloads"]]
    cell = manifest.load_cell("gpt2-124m.chat-prefix")
    assert cell["traffic"]["generator"] == "open_loop"
    assert cell["traffic"]["arrivals"]["rate_per_s"] == pytest.approx(
        cell["traffic"]["arrivals"]["share_of_knee"]
        * cell["traffic"]["arrivals"]["knee_per_s"])
    e2e = [m["name"] for m in cell["metrics"]["end_to_end"]]
    assert e2e == ["out_tok_s", "itl_p50_ms", "setup_s"]
    for m in cell["metrics"]["per_layer"]:
        manifest.load_reader(m["reader"])
        assert m["moves"] in e2e, m["name"]


def test_a_fifth_cell_needs_only_new_files(tmp_path):
    root = chipbench_tiny.make_root(str(tmp_path))   # asserts it edits none
    cell = manifest.load_cell("tiny.chat", root)
    assert cell["config"]["name"] == "gpt2-tiny"
    assert cell["traffic"]["generator"] == "open_loop"
    assert [m["name"] for m in cell["metrics"]["end_to_end"]] == [
        "out_tok_s", "itl_p50_ms", "setup_s"]
    # the files that were there are byte for byte what they were
    for d in chipbench_tiny.DATA_DIRS:
        for f in os.listdir(os.path.join(manifest.ROOT, d)):
            a = os.path.join(manifest.ROOT, d, f)
            if os.path.isfile(a):
                assert open(a, "rb").read() == open(
                    os.path.join(root, d, f), "rb").read()
