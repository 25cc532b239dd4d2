"""The MiniCPM-SALA cell: its files against the catalog's row, its cost
functions against the numbers its configuration file states, its new
readers on a recorded tiny observation and on an empty one, its traffic's
lengths, and a CPU rehearsal of a tiny cell through
``drivers/serve_sala.py`` with the window-only control."""

import dataclasses
import json
import os

import numpy as np
import pytest

import chipbench_tiny
from chipbench import device, manifest, traffic as traffic_lib
from chipbench.drivers import serve_sala
from chipbench.obs import Obs
from chipbench.trace import kernel_costs, kernel_costs_minicpm_sala as costs
from chipbench.trace.reduce import Event, Trace

CELL = "minicpm-sala.reason-gen-64k"
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
NEW_METRICS = ("kernel.sparse_decode_time_share",
               "kernel.sparse_decode_roofline", "kernel.sparse_score_roofline",
               "kernel.lightning_decode_roofline", "model.sparse_read_share",
               "model.sala_serve_mfu", "model.sala_decode_hbm_roofline")
REUSED_METRICS = ("sched.batch_occupancy", "engine.step_ms_p50",
                  "engine.kv_pool_fill_share", "device.idle_share",
                  "device.hbm_peak_gb", "kernel.ssm_decode_time_share")


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(CELL)


@pytest.fixture(scope="module")
def sizes(cell):
    return serve_sala.sizes_of(cell["config"])


# The published config.json's widths, as the configuration file must hold
# them (only the depth is cut).
PUBLISHED = {
    "hidden_size": 4096, "intermediate_size": 16384,
    "num_attention_heads": 32, "num_key_value_heads": 2, "head_dim": 128,
    "lightning_nh": 32, "lightning_nkv": 32, "lightning_head_dim": 128,
    "vocab_size": 73448, "max_position_embeddings": 524288,
    "rope_theta": 10000, "rms_norm_eps": 1e-06, "scale_emb": 12,
    "scale_depth": 1.4, "dim_model_base": 256, "mup_denominator": 32,
    "attn_use_rope": False, "qk_norm": True, "use_output_gate": True,
    "use_output_norm": True, "attn_use_output_gate": True,
    "tie_word_embeddings": False, "model_type": "minicpm_sala"}


def test_the_cell_loads_with_published_widths_and_its_cut(cell):
    cfg = cell["config"]
    assert cfg["source"] == (
        "https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json")
    for key, value in PUBLISHED.items():
        assert cfg[key] == value, key
    assert cfg["published"]["num_hidden_layers"] == 32
    assert len(cfg["mixer_types"]) == 32
    assert cfg["mixer_types"].count("minicpm4") == 8
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["mixer_types"][:4] == ["minicpm4"] + ["lightning-attn"] * 3
    assert set(cfg["assumed"]) >= {"block", "mup_denominator",
                                   "sparse_layer", "sparse_selection",
                                   "lightning_layer", "lightning_activation",
                                   "weights", "dtype"}
    assert cfg["assumed_sizes"]["sparse_config"] == {
        "kernel_size": 32, "kernel_stride": 16, "block_size": 64,
        "topk": 64, "init_blocks": 1, "window_size": 2048,
        "dense_len": 8192}
    assert "8 v5e chips" in cfg["deployment"]
    rehearsal = cfg["serve"]["rehearsal"]
    assert rehearsal["pool_or_state_shaped_copies_in_step_hlo"] == 0
    assert rehearsal["step_program_bytes"]["live"] < 0.9 * 16e9
    argv = cfg["serve"]["argv"]
    assert argv[argv.index("--model") + 1] == "minicpm_sala"
    assert argv[argv.index("--max-batch-size") + 1] == "192"
    assert argv[argv.index("--kv-block-size") + 1] == "64"
    tr = cell["traffic"]
    assert (tr["driver"], tr["generator"]) == ("serve_sala", "backlog")
    assert tr["max_total"] == int(argv[argv.index("--max-len") + 1])
    assert tr["prompt"]["unique"] == {"median": 3072, "sigma": 0.8,
                                      "min": 1024, "max": 16384}
    assert tr["output"] == {"median": 16384, "sigma": 0.5, "min": 6144,
                            "max": 49152}
    names = [m["name"] for m in cell["metrics"]["per_layer"]]
    assert set(names) == set(NEW_METRICS) | set(REUSED_METRICS)


def test_the_program_preset_is_the_configuration_files_cut(cell):
    from nezha_tpu.models.minicpm_sala import minicpm_sala
    cfg, c = cell["config"], minicpm_sala("full").cfg
    assert c.num_hidden_layers == cfg["num_hidden_layers"] == 4
    assert c.vocab_held == c.vocab_size == cfg["vocab_size"]
    for key in ("hidden_size", "intermediate_size", "num_attention_heads",
                "num_key_value_heads", "head_dim", "lightning_nh",
                "lightning_head_dim", "rope_theta", "rms_norm_eps",
                "scale_emb", "scale_depth", "dim_model_base",
                "max_position_embeddings"):
        assert getattr(c, key) == cfg[key], key
    assert list(c.mixer_types) == cfg["mixer_types"]
    z = cfg["assumed_sizes"]["sparse_config"]
    assert c.sparse_sizes == (z["kernel_size"], z["kernel_stride"],
                              z["block_size"], z["topk"], z["init_blocks"],
                              z["window_size"], z["dense_len"])


def test_cost_functions_give_the_configuration_files_bytes(cell, sizes):
    b = cell["config"]["bytes"]
    p = b["parameters"]
    assert p["sparse_layer"] == costs.sparse_params(sizes) + costs.mlp_params(
        sizes) + 2 * 4096
    assert 253e6 < p["sparse_layer"] < 255e6              # the published layer's ~254 M
    assert 284e6 < p["lightning_layer"] < 286e6           # ~285 M
    assert p["embedding_and_head"] == 2 * costs.head_params(sizes)
    assert 3.41e9 < p["total_bf16_bytes"] < 3.43e9        # 3.42 GB of bf16 weights
    assert costs.state_bytes(sizes) == b["state"]["per_layer_row"] == 2 ** 21
    assert b["pool"]["pool_bytes"] == 65536 * (64 * 1024 + 4096)
    # one lightning call over 192 rows: each state read and written once
    c = costs.lightning_decode(192, 0, sizes)
    assert c["bytes"] == 192 * (2 * 2 ** 21 + 4 * 4096 * 4)
    assert kernel_costs.min_seconds(c, PEAKS)["bound"] == "bandwidth"
    # a step at ~15k resident tokens a row, ~80 blocks a (row, head):
    # ~6.5 GB, the two new mechanisms over half of it
    step = costs.decode_step_bytes(192, 192 * 15_000, 192 * 2 * 80, sizes)
    new = 3 * 192 * 2 * 2 ** 21 + 192 * 2 * 80 * 64 * 2 * 128 * 2
    assert 6.0e9 < step < 7.2e9 and new / step > 0.5
    assert costs.selected_tokens(8192, sizes) == 8192
    assert costs.selected_tokens(40_000, sizes) == 64 + 2048 + 64 * 64


def _obs(sizes):
    """Two decode steps inside a traced span: a step is one score call,
    one sparse decode call, three lightning updates and a fusion."""
    obs = Obs()
    obs.model, obs.peaks, obs.trace_span = sizes, PEAKS, (10.0, 11.0)
    obs.steps = [(9.0, 192, 2_800_000), (10.2, 192, 2_900_000),
                 (10.7, 190, 2_900_000)]
    obs.sparse_steps = [(9.0, 192, 30_000), (10.2, 192, 31_000),
                        (10.7, 190, 30_500)]
    ops, t = [], 0.0
    for _ in range(2):
        ops.append(Event("%nezha_sparse_score.1 = (f32[192,1,8192]{2,1,0}) "
                         "custom-call()", t, 0.5e6))
        ops.append(Event("%nezha_sparse_decode_paged.1 = "
                         "bf16[384,16,128]{2,1,0} custom-call()",
                         t + 0.5e6, 2.0e6))
        for i in range(3):
            ops.append(Event(f"%nezha_ssm_decode.{i} = (f32[192,32,128]) "
                             "custom-call()", t + 2.5e6 + i * 1.5e6, 1.5e6))
        ops.append(Event("%fusion.9 = f32[192,73448]{1,0} fusion(%r)",
                         t + 7.0e6, 5.0e6))
        t += 12.0e6
    obs.trace = Trace({0: ops}, [], {})
    obs.samples["step_ms"] = [13.0, 12.0, 14.0]
    for name, v in (("chips", 1), ("tokens_in_span", 500_000),
                    ("token_span_s", 40.0), ("sample_window_s", 35.0),
                    ("decode_rows", 2600 * 192.0), ("decode_steps", 2600),
                    ("sala_resident_tokens", 2600 * 192.0 * 15_000),
                    ("sparse_blocks_read", 2600 * 30_000.0),
                    ("sparse_blocks_resident", 2600 * 90_000.0),
                    ("prompt_tokens_uncached", 120_000),
                    ("sala_prompt_context_sum", 200_000_000)):
        obs.set(name, v)
    return obs


def test_new_readers_on_a_recorded_observation(cell, sizes):
    obs = _obs(sizes)
    files = [m for m in cell["metrics"]["per_layer"]
             if m["name"] in NEW_METRICS + ("kernel.ssm_decode_time_share",)]
    got = {k: v["value"] for k, v in manifest.read_metrics(files, obs).items()}
    assert set(got) == set(NEW_METRICS) | {"kernel.ssm_decode_time_share"}
    assert got["kernel.ssm_decode_time_share"] == pytest.approx(9 / 24 * 100)
    assert got["kernel.sparse_decode_time_share"] == pytest.approx(
        4 / 24 * 100)
    least = sum(costs.sparse_decode(r, x, sizes)["bytes"]
                for r, x in ((192, 31_000), (190, 30_500))) / 2 / 819e9
    assert got["kernel.sparse_decode_roofline"] == pytest.approx(
        least / 2.0e-3 * 100)
    assert got["model.sparse_read_share"] == pytest.approx(100 / 3)
    need = costs.decode_step_bytes(192, 192 * 15_000, 30_000, sizes)
    assert got["model.sala_decode_hbm_roofline"] == pytest.approx(
        need / 819e9 * 1e3 / 13.0 * 100)
    assert all(0 < got[n] < 100 for n in NEW_METRICS)


def test_new_readers_read_nothing_where_nothing_is(cell):
    """An untraced run, another model's sizes and counters (the parent
    has no such model): every new metric is left out or reads 0."""
    files = [m for m in cell["metrics"]["per_layer"]
             if m["name"] in NEW_METRICS]
    assert manifest.read_metrics(files, Obs()) == {}
    obs = Obs()
    obs.peaks, obs.trace_span = PEAKS, (0.0, 1.0)
    obs.model = {"layers": 6, "heads": 32}
    obs.steps = [(0.5, 8, 100)]
    obs.trace = Trace({0: [Event("%fusion.1 = f32[8]{0} fusion()", 0.0, 1e6)]},
                      [], {})
    obs.set("tokens_in_span", 10)
    got = manifest.read_metrics(files, obs)
    assert set(got) <= {"kernel.sparse_decode_time_share"}
    assert all(v["value"] == 0.0 for v in got.values())


def test_the_traffic_draws_lengths_inside_its_bounds(cell):
    tr = cell["traffic"]
    stream = traffic_lib.request_stream(tr, 2150000011, 73448)
    reqs = [next(stream) for _ in range(400)]
    lens = np.asarray([len(r.prompt) for r in reqs])
    outs = np.asarray([r.max_new_tokens for r in reqs])
    assert 1024 <= lens.min() and lens.max() <= 16384
    assert 6144 <= outs.min() and outs.max() <= 49152
    assert (lens + outs <= tr["max_total"]).all()
    assert 2500 < np.median(lens) < 3700 and 14000 < np.median(outs) < 19000
    fill = traffic_lib.stationary_fill(tr, 2150000011, 73448, 192)
    resident = np.asarray([len(r.prompt) for r in fill])
    # the pool (65,535 usable blocks of 64) holds the fill with room
    assert np.ceil(resident / 64).sum() < 0.8 * 65535
    assert 0.6 < (resident > 8192).mean() < 0.9


def test_edge_prompts_of_the_cell(sizes):
    edges = serve_sala.edge_prompt_lengths(sizes, 1024)
    assert sorted(n for ns in edges.values() for n in ns) == [
        31, 32, 1025, 2049, 8191, 8193, 16384]
    # the long prompt's rows select 64 of more than 150 blocks outside
    # the window
    assert 16384 // 64 - 2048 // 64 - 1 > 150


def _tiny_config():
    from nezha_tpu.models.minicpm_sala import TINY_KW, MiniCPMSALAConfig
    c = MiniCPMSALAConfig(**TINY_KW)
    keys = {f.name: getattr(c, f.name) for f in dataclasses.fields(c)}
    keys = {k: list(v) if isinstance(v, tuple) else v for k, v in
            keys.items() if k not in ("vocab_held", "decode_impl")
            and not k.startswith("sparse_")}
    return {
        "name": "sala-tiny", "source": "tests only", "reduced": [], **keys,
        "assumed_sizes": {"sparse_config": {
            "kernel_size": 4, "kernel_stride": 2, "block_size": 8,
            "topk": 4, "init_blocks": 1, "window_size": 16,
            "dense_len": 32}},
        "serve": {"reference": "minicpm_sala", "argv": [
            "--model", "minicpm_sala", "--random-init", "--model-preset",
            "tiny", "--max-len", "128", "--max-batch-size", "4",
            "--max-prefill-len", "32", "--prefill-buckets", "16,32",
            "--kv-block-size", "8", "--cache-dtype", "f32",
            "--prefix-cache", "off", "--queue-capacity", "64"]}}


@pytest.fixture
def tiny_root(tmp_path, monkeypatch):
    """A benchmark root with the cell at tiny size (``tiny.sala``)."""
    root = chipbench_tiny.make_root(str(tmp_path))
    with open(os.path.join(root, "cells", f"{CELL}.json")) as f:
        tiny_cell = json.load(f)
    tiny_cell.update(name="tiny.sala", config="sala-tiny",
                     traffic="tiny-gen-sala")
    files = {"configs/sala-tiny.json": _tiny_config(),
             "traffic/tiny-gen-sala.json": {
                 **chipbench_tiny.TINY_GEN, "name": "tiny-gen-sala",
                 "driver": "serve_sala",
                 "prompt": {"unique": {"median": 30, "sigma": 0.5, "min": 8,
                                       "max": 60}}},
             "cells/tiny.sala.json": tiny_cell}
    for rel, obj in files.items():
        with open(os.path.join(root, rel), "w") as f:
            json.dump(obj, f)
    monkeypatch.setenv(device.REHEARSAL_ENV, "cpu")
    monkeypatch.setattr(serve_sala, "SHORT_PAD", 64)
    monkeypatch.setattr(serve_sala, "LONG_EDGE", 100)
    monkeypatch.setattr(serve_sala, "CHECK_REQUESTS", 3)
    return root


def test_tiny_cell_rehearses_through_serve_sala(tiny_root, capsys):
    from chipbench import run

    assert run.main(["--root", tiny_root, "--workload", "tiny.sala",
                     "--seed", "2150000007", "--seconds", "1", "--trace",
                     "1"]) == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.strip()]
    result, facts = lines[-1], lines[-2]["facts"]
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"sched.batch_occupancy",
                                      "engine.kv_pool_fill_share",
                                      "model.sparse_read_share"}
    assert 0 < result["metrics"]["model.sparse_read_share"]["value"] <= 100
    chk = facts["reference_check"]
    # 3 of the mix's own and the edge prompts (kernel 4, dense_len 32,
    # chunks of 32, the long one cut to 100)
    assert chk["ok"] and chk["tripped"] == [] and chk["requests"] == 3 + 7
    assert chk["prompt_lengths"][3:] == [3, 4, 31, 33, 33, 65, 100]
    # float32 at tiny size: a hundredth of an ulp on every row; every
    # request's state and compressed keys read from the pool
    assert chk["largest_row_ulps"] < 0.05
    assert len(chk["state_diffs"]) == 10 and 0 < chk["state_diff"] < 1e-4
    assert len(chk["ck_diffs"]) == 10 and chk["ck_diff"] < 1e-5
    assert facts["compilations_in_window"] == 0
    assert facts["serve.sparse.blocks_read_total"] > 0


def test_the_window_only_control_reads_not_correct_at_tiny_size(
        tiny_root, capsys, monkeypatch):
    """The reference's sparse layer with the init block and the window
    alone past dense_len: at tiny size the sound program reads ~1e-6 of
    the first lightning layer's state, a wrong selection ~1e-3; limits
    between THOSE."""
    monkeypatch.setattr(serve_sala, "LOGIT_TOL_ULPS", 1.0)
    monkeypatch.setattr(serve_sala, "STATE_TOL", 1e-4)
    assert serve_sala.control([
        "--root", tiny_root, "--workload", "tiny.sala", "--seed",
        "2150000007", "--control", "window-only"]) == 0
    line = [json.loads(l) for l in capsys.readouterr().out.splitlines()
            if l.startswith("{")][-1]
    chk = line["reference_check"]
    assert line["control"] == "window-only" and chk["ok"] is False
    assert "state_diff" in chk["tripped"] and chk["ck_diff"] < 1e-5


def test_the_comparison_reads_every_row_state_and_key():
    rng = np.random.default_rng(3)
    base = rng.uniform(0.1, 0.9, 300) * serve_sala.LOGIT_TOL_ULPS
    states = [0.4 * serve_sala.STATE_TOL] * 10
    cks = [0.4 * serve_sala.CK_TOL] * 10

    def tripped(per_row, s=states, k=cks):
        return serve_sala.judge(per_row, s, k)["tripped"]

    assert tripped(base) == []
    wild = base.copy()
    wild[7] = 2.0 * serve_sala.LOGIT_TOL_ULPS
    assert tripped(wild) == ["largest_row_ulps"]
    assert tripped(base, states[:-1] + [2 * serve_sala.STATE_TOL]) == [
        "state_diff"]
    assert tripped(base, k=cks[:-1] + [2 * serve_sala.CK_TOL]) == ["ck_diff"]
    assert tripped(base, states[:-1] + [None]) == ["state_diff"]
