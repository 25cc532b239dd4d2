"""The Mistral-Small-4 cell: its files, its cost functions against numbers
reckoned by hand (ISSUE 26), its readers on a small synthetic ``Obs``, and
a CPU rehearsal of a tiny cell through ``drivers/serve_lm.py``."""

import json
import os

import pytest

import chipbench_tiny
from chipbench import device, manifest
from chipbench.drivers import serve_lm
from chipbench.obs import Obs
from chipbench.trace import kernel_costs, kernel_costs_mistral4 as costs
from chipbench.trace.reduce import Event, Trace

CELL = "mistral-small-4.batch-gen-4k"
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
NEW_METRICS = ("model.serve_mfu", "model.decode_hbm_roofline",
               "model.moe_load_max_over_mean", "kernel.mla_decode_time_share",
               "kernel.mla_decode_roofline", "kernel.moe_time_share",
               "kernel.moe_roofline")


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(CELL)


@pytest.fixture(scope="module")
def sizes(cell):
    return serve_lm.sizes_of(cell["config"])


def test_the_cell_loads_with_published_widths_and_its_cut(cell):
    cfg = cell["config"]
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Mistral-Small-4-119B-2603")
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value       # stated beside the cut
        else:
            assert cfg[key] == value, key               # verbatim, no width cut
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"], cfg["experts_held"]) == (6, 32, 32768, [0, 32])
    assert set(cfg["assumed"]) >= {"router_score", "softmax_scale", "weights"}
    assert "24 v5e chips" in cfg["deployment"]
    assert set(cfg["serve"]["rehearsal"]["step_program_bytes"]) == {"6", "5", "4"}
    assert cfg["serve"]["rehearsal"]["pool_shaped_copies_in_step_hlo"] == 0
    argv = cfg["serve"]["argv"]
    assert argv[argv.index("--model") + 1] == "mistral_small4"
    assert cell["traffic"]["driver"] == "serve_lm"
    assert cell["traffic"]["generator"] == "backlog"
    names = [m["name"] for m in cell["metrics"]["per_layer"]]
    assert set(NEW_METRICS) <= set(names)
    # the three span metrics in waiting stay out (test_chipbench_host_span)
    assert not {"sched.host_ms_per_pass", "engine.dispatch_ms_p50"} & set(names)


def test_the_program_preset_is_the_configuration_files_cut(cell):
    from nezha_tpu.models.mistral4 import mistral_small4
    cfg, c = cell["config"], mistral_small4("full").cfg
    assert (c.num_hidden_layers, c.experts_held, c.vocab_held) == (
        cfg["num_hidden_layers"], tuple(cfg["experts_held"]), cfg["vocab_size"])
    assert c.n_routed_experts == cfg["published"]["n_routed_experts"]
    for key in ("hidden_size", "q_lora_rank", "kv_lora_rank", "v_head_dim",
                "qk_nope_head_dim", "qk_rope_head_dim", "num_attention_heads",
                "moe_intermediate_size", "num_experts_per_tok",
                "n_shared_experts", "rms_norm_eps", "norm_topk_prob"):
        assert getattr(c, key) == cfg[key], key
    rope = cfg["rope_parameters"]
    assert (c.rope_theta, c.rope_factor, c.rope_original_max, c.rope_beta_fast,
            c.rope_beta_slow, c.llama_4_scaling_beta) == (
        rope["rope_theta"], rope["factor"],
        rope["original_max_position_embeddings"], rope["beta_fast"],
        rope["beta_slow"], rope["llama_4_scaling_beta"])


def test_cost_functions_against_hand_reckoned_numbers(sizes):
    # a layer outside its routed experts: 28.05M + 25.17M + 0.52M
    assert costs.attention_params(sizes) == (4096 * 1024 + 1024 * 4096
                                             + 4096 * 320 + 256 * 6144
                                             + 4096 * 4096) == 28_049_408
    assert costs.expert_params(sizes) == 25_165_824
    assert costs.dense_layer_params(sizes) == 28_049_408 + 25_165_824 + 524_288
    assert costs.head_params(sizes) == 134_217_728
    # mla_decode: T x 640 B + q and o rows; 2 x T x 32 x 576 operations
    c = costs.mla_decode(256_000, 128, sizes)
    assert c["bytes"] == 256_000 * 640 + 128 * 32 * (320 + 256) * 2
    assert c["flops"] == 2 * 256_000 * 32 * 576
    assert c["flops"] / (256_000 * 640) == pytest.approx(57.6)
    assert kernel_costs.min_seconds(c, PEAKS)["bound"] == "bandwidth"
    # moe_experts: touched x 50.3 MB + pair rows in and out; 6 x 4096 x 2048 a pair
    c = costs.moe_experts(31.4, 128, sizes)
    assert c["bytes"] == pytest.approx(31.4 * 50_331_648 + 128 * 2 * 4096 * 2)
    assert c["flops"] == 128 * 6 * 4096 * 2048
    assert kernel_costs.min_seconds(c, PEAKS)["bound"] == "bandwidth"
    # one decode step: 6 x (0.107 GB dense + ~1.58 GB experts + 0.16 GB cache) + 0.27 GB head
    touched = 6 * 32 * (1 - 2.718281828 ** -4)
    step = costs.decode_step_bytes(touched, 128 * 2000, sizes)
    assert step == pytest.approx(2 * (6 * 53_739_520 + touched * 25_165_824
                                      + 6 * 256_000 * 320 + 134_217_728))
    assert 13.0e-3 < step / 819e9 < 14.5e-3             # the issue's ~14 ms floor
    # one output token: 2 x (53.7M + pairs x 25.17M) a layer + head + attention
    f = costs.serve_flops_per_token(1.0, 2000.0, sizes)
    assert f == pytest.approx(6 * (2 * (53_739_520 + 25_165_824)
                                   + 2 * 32 * 576 * 2000.0)
                              + 2 * 134_217_728)


def _obs(sizes):
    """Two decode steps inside a traced span; ops named as a v5e trace
    names them (a whole HLO line), 12 calls of each kernel (2 steps x 6)."""
    obs = Obs()
    obs.model, obs.peaks, obs.trace_span = sizes, PEAKS, (10.0, 11.0)
    obs.steps = [(9.0, 128, 250_000), (10.2, 128, 256_000),
                 (10.7, 128, 256_128)]
    obs.lm_steps = [(9.0, 180, 760), (10.2, 188, 770), (10.7, 186, 766)]
    ops, t = [], 0.0
    for _ in range(12):
        ops.append(Event("%nezha_mla_decode_paged.1 = bf16[128,32,256]{2,1,0} "
                         "custom-call(%p)", t, 0.5e6))
        ops.append(Event("%nezha_moe_experts.3 = bf16[512,4096]{1,0} "
                         "custom-call(%q)", t + 0.5e6, 4.0e6))
        ops.append(Event("%fusion.9 = f32[128,32768]{1,0} fusion(%r)",
                         t + 4.5e6, 0.5e6))
        t += 5.0e6
    obs.trace = Trace({0: ops}, [], {})
    obs.samples["step_ms"] = [31.0, 30.0, 29.0]
    for name, v in (("chips", 1), ("tokens_in_span", 160_000),
                    ("token_span_s", 40.0), ("moe_steps", 1250),
                    ("moe_rows", 160_000), ("moe_held_pairs", 960_000),
                    ("moe_touched", 1250 * 187.0),
                    ("lm_resident_tokens", 160_000 * 2000.0),
                    ("moe_load_max_over_mean_sum", 1250 * 2.5)):
        obs.set(name, v)
    return obs


def test_new_readers_on_a_synthetic_obs(cell, sizes):
    obs = _obs(sizes)
    files = [m for m in cell["metrics"]["per_layer"] if m["name"] in NEW_METRICS]
    assert len(files) == len(NEW_METRICS)
    for m in files:     # a kernel's name here: patterns are data, as a later PR sets them
        if "patterns" in m["params"]:
            pat = ["^%?nezha_mla_decode" if "mla" in m["name"]
                   else "^%?nezha_moe_experts"]
            m["params"] = {**m["params"], "patterns": pat,
                           **({"calls": pat} if "calls" in m["params"] else {})}
    got = {k: v["value"] for k, v in manifest.read_metrics(files, obs).items()}
    assert set(got) == set(NEW_METRICS)
    assert got["model.moe_load_max_over_mean"] == pytest.approx(2.5)
    assert got["kernel.mla_decode_time_share"] == pytest.approx(10.0)
    assert got["kernel.moe_time_share"] == pytest.approx(80.0)
    # one pair a token a layer, context 2000: 4,000 tok/s x flops / 197e12
    flops = costs.serve_flops_per_token(1.0, 2000.0, sizes)
    assert got["model.serve_mfu"] == pytest.approx(4000 * flops / 197e12 * 100)
    step = costs.decode_step_bytes(187.0, 256_000.0, sizes)
    assert got["model.decode_hbm_roofline"] == pytest.approx(
        step / 819e9 * 1e3 / 30.0 * 100)
    # the two steps inside the span, one call a layer
    least = sum(costs.mla_decode(t, 128, sizes)["bytes"]
                for t in (256_000, 256_128)) / 2 / 819e9
    assert got["kernel.mla_decode_roofline"] == pytest.approx(
        least / 0.5e-3 * 100)
    least = sum(costs.moe_experts(a / 6, b / 6, sizes)["bytes"]
                for a, b in ((188, 770), (186, 766))) / 2 / 819e9
    assert got["kernel.moe_roofline"] == pytest.approx(least / 4.0e-3 * 100)
    assert all(0 < got[n] < 100 for n in NEW_METRICS if "roofline" in n)


def test_new_readers_read_nothing_where_nothing_is(cell):
    """An untraced run, and a program without the counter (the parent):
    every new metric is left out and nothing raises."""
    files = [m for m in cell["metrics"]["per_layer"] if m["name"] in NEW_METRICS]
    assert manifest.read_metrics(files, Obs()) == {}
    obs = Obs()
    obs.peaks, obs.trace_span = PEAKS, (0.0, 1.0)
    obs.trace = Trace({0: [Event("%fusion.1 = f32[8]{0} fusion()", 0.0, 1e6)]},
                      [], {})
    assert set(manifest.read_metrics(files, obs)) <= {
        "kernel.mla_decode_time_share", "kernel.moe_time_share"}


def _tiny_config():
    from nezha_tpu.models.mistral4 import TINY_KW, Mistral4Config
    c = Mistral4Config(**TINY_KW)
    return {
        "name": "mistral-tiny", "source": "tests only", "reduced": [],
        "hidden_size": c.hidden_size, "num_attention_heads": c.num_attention_heads,
        "q_lora_rank": c.q_lora_rank, "kv_lora_rank": c.kv_lora_rank,
        "qk_nope_head_dim": c.qk_nope_head_dim,
        "qk_rope_head_dim": c.qk_rope_head_dim, "v_head_dim": c.v_head_dim,
        "moe_intermediate_size": c.moe_intermediate_size,
        "num_experts_per_tok": c.num_experts_per_tok,
        "num_hidden_layers": c.num_hidden_layers, "vocab_size": c.vocab_held,
        "n_routed_experts": c.experts_held[1],
        "published": {"n_routed_experts": c.n_routed_experts},
        "experts_held": list(c.experts_held), "rms_norm_eps": c.rms_norm_eps,
        "norm_topk_prob": c.norm_topk_prob,
        "routed_scaling_factor": c.routed_scaling_factor,
        "rope_parameters": {
            "rope_theta": c.rope_theta, "factor": c.rope_factor,
            "original_max_position_embeddings": c.rope_original_max,
            "beta_fast": c.rope_beta_fast, "beta_slow": c.rope_beta_slow,
            "mscale_all_dim": c.rope_mscale_all_dim,
            "llama_4_scaling_beta": c.llama_4_scaling_beta},
        "serve": {"reference": "mistral4", "argv": [
            "--model", "mistral_small4", "--random-init", "--model-preset",
            "tiny", "--max-len", "96", "--max-batch-size", "4",
            "--max-prefill-len", "32", "--prefill-buckets", "16,32",
            "--kv-block-size", "8", "--cache-dtype", "f32",
            "--queue-capacity", "64"]}}


def test_tiny_cell_rehearses_through_serve_lm(tmp_path, capsys, monkeypatch):
    from chipbench import run

    root = chipbench_tiny.make_root(str(tmp_path))
    with open(os.path.join(root, "cells", f"{CELL}.json")) as f:
        tiny_cell = json.load(f)
    tiny_cell.update(name="tiny.lm", config="mistral-tiny", traffic="tiny-gen-lm")
    files = {"configs/mistral-tiny.json": _tiny_config(),
             "traffic/tiny-gen-lm.json": {**chipbench_tiny.TINY_GEN,
                                          "name": "tiny-gen-lm",
                                          "driver": "serve_lm"},
             "cells/tiny.lm.json": tiny_cell}
    for rel, obj in files.items():
        with open(os.path.join(root, rel), "w") as f:
            json.dump(obj, f)
    monkeypatch.setenv(device.REHEARSAL_ENV, "cpu")
    assert run.main(["--root", root, "--workload", "tiny.lm", "--seed", "3",
                     "--seconds", "1", "--trace", "1"]) == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.strip()]
    result, facts = lines[-1], lines[-2]["facts"]
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    # counts only: of the new metrics the program counter alone
    assert "model.moe_load_max_over_mean" in result["metrics"]
    assert not {n for n in NEW_METRICS if n in result["metrics"]} - {
        "model.moe_load_max_over_mean"}
    assert result["metrics"]["sched.batch_occupancy"]["value"] > 90.0
    chk = facts["reference_check"]
    assert chk["ok"] and chk["requests"] == 32 and chk["rows_in_all"] >= 64
    assert chk["rows_in_all"] - chk["rows_set_aside"] == chk["rows"]
    assert chk["max_logit_diff"] < 1e-4     # float32 at tiny size
    c = facts["counters"]
    assert c["moe_steps"] > 0 and 0 < c["moe_held_pairs"] <= c["moe_rows"] * 4 * 2
    assert facts["compilations_in_window"] == 0
