"""The K-EXAONE cell: its files, its cost functions against numbers
reckoned by hand (ISSUE 30), its readers on a small synthetic ``Obs``, and
a CPU rehearsal of a tiny cell through ``drivers/serve_hybrid.py``."""

import json
import os

import pytest

import chipbench_tiny
from chipbench import device, manifest
from chipbench.drivers import serve_hybrid
from chipbench.obs import Obs
from chipbench.trace import kernel_costs, kernel_costs_exaone as costs
from chipbench.trace.reduce import Event, Trace

CELL = "k-exaone-236b.reason-gen-8k"
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
NEW_METRICS = ("model.hybrid_serve_mfu", "model.hybrid_decode_hbm_roofline",
               "kernel.gqa_decode_roofline", "kernel.window_decode_time_share")
# (``kernel.decode_time_share``: GPT-2's accepted metric takes any Pallas
# call whose result is ``bf16[rows, heads, 1, head_dim]``, so it reads both
# of this cell's decode calls and the cell brings no double of it)
REUSED_METRICS = ("sched.batch_occupancy", "engine.step_ms_p50",
                  "engine.kv_pool_fill_share", "device.idle_share",
                  "device.hbm_peak_gb", "model.moe_load_max_over_mean",
                  "kernel.moe_time_share", "kernel.decode_time_share")


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(CELL)


@pytest.fixture(scope="module")
def sizes(cell):
    return serve_hybrid.sizes_of(cell["config"])


def test_the_cell_loads_with_published_widths_and_its_cut(cell):
    cfg = cell["config"]
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "K-EXAONE-236B-A23B")
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value       # stated beside the cut
        else:
            assert cfg[key] == value, key               # verbatim, no width cut
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"],
            cfg["num_nextn_predict_layers"], cfg["experts_held"]) == (
        5, 16, 19200, 0, [0, 16])
    # the floors: the dense layer + a whole period, >= 8 experts, >= 1/8
    kinds = cfg["layer_types"][:5]
    assert kinds.count("full_attention") == 1 and len(kinds) == 5
    assert cfg["mlp_layer_types"][:5] == ["dense"] + ["sparse"] * 4
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    assert set(cfg["assumed"]) >= {"block", "qk_norm", "rotary", "router",
                                   "weights", "dtype"}
    assert "64 v5e chips" in cfg["deployment"]
    rehearsal = cfg["serve"]["rehearsal"]
    assert rehearsal["pool_shaped_copies_in_step_hlo"] == 0
    assert max(rehearsal["prefill_live_bytes"].values()) < 0.9 * 16e9
    argv = cfg["serve"]["argv"]
    assert argv[argv.index("--model") + 1] == "k_exaone"
    assert argv[argv.index("--prefix-cache") + 1] == "off"
    assert cell["traffic"]["driver"] == "serve_hybrid"
    assert cell["traffic"]["generator"] == "backlog"
    assert cell["traffic"]["max_total"] == int(argv[argv.index("--max-len") + 1])
    names = [m["name"] for m in cell["metrics"]["per_layer"]]
    assert set(names) == set(NEW_METRICS) | set(REUSED_METRICS)
    assert [m["name"] for m in cell["metrics"]["end_to_end"]] == [
        "out_tok_s", "setup_s"]


def test_the_program_preset_is_the_configuration_files_cut(cell):
    from nezha_tpu.models.exaone_moe import k_exaone
    cfg, c = cell["config"], k_exaone("full").cfg
    assert (c.num_hidden_layers, c.experts_held, c.vocab_held) == (
        cfg["num_hidden_layers"], tuple(cfg["experts_held"]), cfg["vocab_size"])
    assert c.num_experts == cfg["published"]["num_experts"]
    assert list(c.layer_types) == cfg["layer_types"]
    for key in ("hidden_size", "intermediate_size", "num_attention_heads",
                "num_key_value_heads", "head_dim", "sliding_window",
                "first_k_dense_replace", "moe_intermediate_size",
                "num_experts_per_tok", "num_shared_experts", "rms_norm_eps",
                "norm_topk_prob", "routed_scaling_factor", "scoring_func",
                "max_position_embeddings"):
        assert getattr(c, key) == cfg[key], key
    assert c.rope_theta == cfg["rope_parameters"]["rope_theta"]


def test_cost_functions_against_hand_reckoned_numbers(sizes):
    assert sizes["global_layers"] == 1 and sizes["window_layers"] == 4
    assert sizes["dense_layers"] == 1 and sizes["sparse_layers"] == 4
    # the issue's arithmetic: 113.25M, 339.74M, 37.75M, 0.79M
    assert costs.attention_params(sizes) == (6144 * 8192 * 2
                                             + 6144 * 1024 * 2) == 113_246_208
    assert costs.dense_mlp_params(sizes) == 3 * 6144 * 18432 == 339_738_624
    assert costs.expert_params(sizes) == 3 * 6144 * 2048 == 37_748_736
    assert costs.head_params(sizes) == 19200 * 6144 == 117_964_800
    always = (5 * 113_246_208 + 339_738_624
              + 4 * (37_748_736 + 6144 * 128) + 117_964_800)
    assert costs.always_params(sizes) == always
    assert costs.kv_row_bytes(sizes) == 4096
    # one call: T x 4,096 B + q and o rows; 4 x T x 64 x 128 operations
    c = costs.gqa_decode(486_400, 128, sizes)
    assert c["bytes"] == 486_400 * 4096 + 128 * 2 * 64 * 128 * 2
    assert c["flops"] == 4 * 486_400 * 64 * 128
    assert c["flops"] / (486_400 * 4096) == pytest.approx(8.0)
    assert kernel_costs.min_seconds(c, PEAKS)["bound"] == "bandwidth"
    # one decode step at ~3,800 resident tokens a row: the issue's 9.6 GB
    # (7.19 GB of weights with every held expert touched + 1.99 GB of the
    # global layer's K/V + 0.27 GB of the four rings' visible rows)
    step = costs.decode_step_bytes(64, 128 * 3800, 128 * 128, sizes)
    assert step == pytest.approx(2 * (always + 64 * 37_748_736)
                                 + 4096 * (128 * 3800 + 4 * 128 * 128))
    assert 9.3e9 < step < 9.7e9 and 11.3e-3 < step / 819e9 < 11.9e-3
    # one output token: two operations a parameter passed + attention
    f = costs.serve_flops_per_token(4.0, 3800.0, 128.0, sizes)
    assert f == pytest.approx(2 * (always + 4 * 37_748_736)
                              + 4 * 64 * 128 * (3800.0 + 4 * 128.0))


def _obs(sizes):
    """Two decode steps inside a traced span; ops named as a v5e trace
    names them (a whole HLO line): a step is 1 full-table call and 4 ring
    calls, then the experts and a fusion."""
    obs = Obs()
    obs.model, obs.peaks, obs.trace_span = sizes, PEAKS, (10.0, 11.0)
    obs.hybrid_steps = [(9.0, 128, 480_000, 16_000),
                        (10.2, 128, 486_400, 16_384),
                        (10.7, 128, 486_528, 16_384)]
    res = ('bf16[128,64,1,128]{3,2,1,0} custom-call(%p), '
           'custom_call_target="tpu_custom_call"')
    ops, t = [], 0.0
    for _ in range(2):
        ops.append(Event(f"%nezha_decode_attention_paged.1 = {res}", t, 3.0e6))
        for i in range(4):
            ops.append(Event(f"%nezha_decode_attention_window.{i} = {res}",
                             t + 3.0e6 + i * 0.25e6, 0.25e6))
        ops.append(Event("%ragged-dot.3 = f32[1024,2048]{1,0} custom-call(%q)",
                         t + 4.0e6, 5.0e6))
        ops.append(Event("%fusion.9 = f32[128,19200]{1,0} fusion(%r)",
                         t + 9.0e6, 1.0e6))
        t += 10.0e6
    obs.trace = Trace({0: ops}, [], {})
    obs.samples["step_ms"] = [31.0, 30.0, 29.0]
    for name, v in (("chips", 1), ("tokens_in_span", 160_000),
                    ("token_span_s", 40.0), ("moe_steps", 1250),
                    ("moe_rows", 160_000), ("moe_held_pairs", 640_000),
                    ("moe_touched", 1250 * 63.0),
                    ("lm_resident_tokens", 160_000 * 3800.0),
                    ("lm_window_tokens", 160_000 * 128.0),
                    ("moe_load_max_over_mean_sum", 1250 * 2.5)):
        obs.set(name, v)
    return obs


def test_new_readers_on_a_synthetic_obs(cell, sizes):
    obs = _obs(sizes)
    files = [m for m in cell["metrics"]["per_layer"]
             if m["name"] in NEW_METRICS + ("kernel.moe_time_share",
                                            "kernel.decode_time_share",
                                            "model.moe_load_max_over_mean")]
    got = {k: v["value"] for k, v in manifest.read_metrics(files, obs).items()}
    assert set(got) == set(NEW_METRICS) | {"kernel.moe_time_share",
                                           "kernel.decode_time_share",
                                           "model.moe_load_max_over_mean"}
    assert got["model.moe_load_max_over_mean"] == pytest.approx(2.5)
    # the accepted pattern reads the full-table call and the ring calls
    assert got["kernel.decode_time_share"] == pytest.approx(40.0)
    assert got["kernel.window_decode_time_share"] == pytest.approx(10.0)
    assert got["kernel.moe_time_share"] == pytest.approx(50.0)
    # four held pairs a token, context 3,800, 128 of it inside the window
    flops = costs.serve_flops_per_token(4.0, 3800.0, 128.0, sizes)
    assert got["model.hybrid_serve_mfu"] == pytest.approx(
        4000 * flops / 197e12 * 100)
    step = costs.decode_step_bytes(63.0, 128 * 3800.0, 128 * 128.0, sizes)
    assert got["model.hybrid_decode_hbm_roofline"] == pytest.approx(
        step / 819e9 * 1e3 / 30.0 * 100)
    # the two steps inside the span: one full-table and four ring calls
    least = sum(costs.gqa_decode(t, 128, sizes)["bytes"]
                + 4 * costs.gqa_decode(w, 128, sizes)["bytes"]
                for t, w in ((486_400, 16_384), (486_528, 16_384))) / 2 / 819e9
    assert got["kernel.gqa_decode_roofline"] == pytest.approx(
        least / 4.0e-3 * 100)
    assert all(0 < got[n] < 100 for n in NEW_METRICS)


def test_new_readers_read_nothing_where_nothing_is(cell):
    """An untraced run, a program without the kernels or the counters
    (the parent), another model's sizes: every new metric is left out
    and nothing raises."""
    files = [m for m in cell["metrics"]["per_layer"] if m["name"] in NEW_METRICS]
    assert len(files) == len(NEW_METRICS)
    assert manifest.read_metrics(files, Obs()) == {}
    obs = Obs()
    obs.peaks, obs.trace_span = PEAKS, (0.0, 1.0)
    obs.model = {"layers": 6, "heads": 32}              # serve_lm's sizes
    obs.trace = Trace({0: [Event("%fusion.1 = f32[8]{0} fusion()", 0.0, 1e6)]},
                      [], {})
    got = manifest.read_metrics(files, obs)
    assert set(got) <= {"kernel.window_decode_time_share"}
    assert all(v["value"] == 0.0 for v in got.values())


def _tiny_config():
    from nezha_tpu.models.exaone_moe import TINY_KW, ExaoneMoeConfig
    c = ExaoneMoeConfig(**TINY_KW)
    return {
        "name": "exaone-tiny", "source": "tests only", "reduced": [],
        "hidden_size": c.hidden_size, "intermediate_size": c.intermediate_size,
        "num_attention_heads": c.num_attention_heads,
        "num_key_value_heads": c.num_key_value_heads, "head_dim": c.head_dim,
        "layer_types": list(c.layer_types), "sliding_window": c.sliding_window,
        "first_k_dense_replace": c.first_k_dense_replace,
        "moe_intermediate_size": c.moe_intermediate_size,
        "num_experts_per_tok": c.num_experts_per_tok,
        "num_hidden_layers": c.num_hidden_layers, "vocab_size": c.vocab_held,
        "num_experts": c.experts_held[1],
        "published": {"num_experts": c.num_experts},
        "experts_held": list(c.experts_held), "rms_norm_eps": c.rms_norm_eps,
        "norm_topk_prob": c.norm_topk_prob,
        "routed_scaling_factor": c.routed_scaling_factor,
        "rope_parameters": {"rope_theta": c.rope_theta},
        "serve": {"reference": "exaone_moe", "argv": [
            "--model", "k_exaone", "--random-init", "--model-preset", "tiny",
            "--max-len", "96", "--max-batch-size", "4", "--max-prefill-len",
            "32", "--prefill-buckets", "16,32", "--kv-block-size", "4",
            "--cache-dtype", "f32", "--prefix-cache", "off",
            "--queue-capacity", "64"]}}


def test_tiny_cell_rehearses_through_serve_hybrid(tmp_path, capsys,
                                                  monkeypatch):
    from chipbench import run

    root = chipbench_tiny.make_root(str(tmp_path))
    with open(os.path.join(root, "cells", f"{CELL}.json")) as f:
        tiny_cell = json.load(f)
    tiny_cell.update(name="tiny.hybrid", config="exaone-tiny",
                     traffic="tiny-gen-hybrid")
    files = {"configs/exaone-tiny.json": _tiny_config(),
             "traffic/tiny-gen-hybrid.json": {**chipbench_tiny.TINY_GEN,
                                              "name": "tiny-gen-hybrid",
                                              "driver": "serve_hybrid"},
             "cells/tiny.hybrid.json": tiny_cell}
    for rel, obj in files.items():
        with open(os.path.join(root, rel), "w") as f:
            json.dump(obj, f)
    monkeypatch.setenv(device.REHEARSAL_ENV, "cpu")
    assert run.main(["--root", root, "--workload", "tiny.hybrid", "--seed",
                     "2150000003", "--seconds", "1", "--trace", "1"]) == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.strip()]
    result, facts = lines[-1], lines[-2]["facts"]
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    # counts only: of this cell's metrics the program counters alone
    assert set(result["metrics"]) == {
        "sched.batch_occupancy", "engine.kv_pool_fill_share",
        "model.moe_load_max_over_mean"}
    assert result["metrics"]["sched.batch_occupancy"]["value"] > 90.0
    chk = facts["reference_check"]
    # 32 of the mix's own and the edge prompts: block 4, a ring of 3, the
    # mix's prompts 4-30, 96 positions
    assert serve_hybrid.edge_prompt_lengths(4, 3, 4, 30, 96) == {
        "ring_wrap": [11, 23], "block_boundary": [7, 15, 19, 27],
        "long_context": [83, 91]}
    assert chk["ok"] and chk["requests"] == 40 and chk["rows_in_all"] >= 80
    assert chk["prompt_lengths"][32:] == [11, 23, 7, 15, 19, 27, 83, 91]
    # every case has rows; which of them the margin filter keeps is the
    # seed's (here 1 of 29, 4 of 68, 1 of 8)
    assert all(n >= 4 for n in chk["rows_by_case"].values())
    compared = chk["rows_compared_by_case"]
    assert set(compared) == set(chk["rows_by_case"])
    assert all(0 <= compared[c] <= chk["rows_by_case"][c] for c in compared)
    assert sum(compared.values()) >= 1
    assert chk["rows_in_all"] - chk["rows_set_aside"] == chk["rows"]
    assert chk["max_logit_diff"] < 1e-4     # float32 at tiny size
    c = facts["counters"]
    assert c["moe_steps"] > 0 and 0 < c["moe_held_pairs"] <= c["moe_rows"] * 4 * 4
    # a window of 8: every row's context inside it is at most 8 tokens
    assert 0 < c["lm_window_tokens"] <= 8 * c["moe_rows"]
    assert c["lm_window_tokens"] < c["lm_resident_tokens"]
    assert facts["compilations_in_window"] == 0


def test_edge_prompts_of_the_cell_and_what_each_row_covers():
    """At the cell's deployment (block 64, a ring of 3, prompts 256-4,096,
    8,192 positions): five prompts a kind inside the mix's lengths, two
    near the slot's end; each is one short of a block, so the second
    decode step's key opens a block."""
    edges = serve_hybrid.edge_prompt_lengths(64, 3, 256, 4096, 8192)
    assert edges == {"ring_wrap": [383, 1343, 2303, 3071, 4031],
                     "block_boundary": [319, 1279, 2239, 3135, 4095],
                     "long_context": [8063, 8127]}
    for kind, ns in edges.items():
        for n in ns:
            assert (n + 1) % 64 == 0 and n + 4 <= 8192
            assert ((n + 1) // 64 % 3 == 0) == (kind != "block_boundary"
                                                and n != 8127)
    # row 0 follows the prefill, row r the decode step at position n - 1 + r
    far = 4096 + 3 - 1
    assert serve_hybrid.row_cases(383, 4, 64, 3, far) == [
        set(), set(), {"block_boundary", "ring_wrap"},
        {"block_boundary", "ring_wrap"}]
    assert serve_hybrid.row_cases(319, 4, 64, 3, far) == [
        set(), set(), {"block_boundary"}, {"block_boundary"}]
    assert serve_hybrid.row_cases(8127, 4, 64, 3, far) == [
        {"long_context"}, {"long_context"},
        {"long_context", "block_boundary"},
        {"long_context", "block_boundary"}]
    # a prompt of the mix that ends mid-block covers nothing
    assert serve_hybrid.row_cases(1000, 4, 64, 3, far) == [set()] * 4
