"""The Kimi-Linear cell: its files, its cost functions against numbers
reckoned by hand (ISSUE 32), its readers on a small synthetic ``Obs``, and
a CPU rehearsal of a tiny cell through ``drivers/serve_state.py``."""

import json
import os

import numpy as np
import pytest

import chipbench_tiny
from chipbench import device, manifest
from chipbench.drivers import serve_state
from chipbench.obs import Obs
from chipbench.trace import kernel_costs, kernel_costs_kimi as costs
from chipbench.trace.reduce import Event, Trace

CELL = "kimi-linear-48b.reason-gen-16k"
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
NEW_METRICS = ("model.state_serve_mfu", "model.state_decode_hbm_roofline",
               "kernel.kda_decode_time_share", "kernel.kda_decode_roofline",
               "kernel.latent_decode_roofline", "kernel.state_moe_roofline")
# (``kernel.decode_time_share``: the accepted metric takes any Pallas call
# whose result is ``bf16[rows, heads, 1, d]``: here the latent call, and not
# ``nezha_kda_decode``, whose result is a tuple)
REUSED_METRICS = ("sched.batch_occupancy", "engine.step_ms_p50",
                  "engine.kv_pool_fill_share", "device.idle_share",
                  "device.hbm_peak_gb", "model.moe_load_max_over_mean",
                  "kernel.moe_time_share", "kernel.decode_time_share")


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(CELL)


@pytest.fixture(scope="module")
def sizes(cell):
    return serve_state.sizes_of(cell["config"])


def test_the_cell_loads_with_published_widths_and_its_cut(cell):
    cfg = cell["config"]
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value       # stated beside the cut
        else:
            assert cfg[key] == value, key               # verbatim, no width cut
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"],
            cfg["experts_held"]) == (5, 64, 40960, [0, 64])
    # the floors: the dense layer + a whole period of 4, >= 8 experts, >= 1/8
    lin = cfg["linear_attn_config"]
    kinds = ["kda" if l in lin["kda_layers"] else "mla" for l in range(1, 6)]
    assert kinds == ["kda", "kda", "kda", "mla", "kda"]
    assert cfg["first_k_dense_replace"] == 1
    assert cfg["num_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    assert set(cfg["assumed"]) >= {"block", "kda", "kda_init", "nope",
                                   "router", "weights", "dtype"}
    assert "16 v5e chips" in cfg["deployment"]
    rehearsal = cfg["serve"]["rehearsal"]
    assert rehearsal["pool_or_state_shaped_copies_in_step_hlo"] == 0
    assert max(rehearsal["prefill_live_bytes"].values()) < 0.9 * 16e9
    assert rehearsal["step_program_bytes"]["live"] < 0.9 * 16e9
    argv = cfg["serve"]["argv"]
    assert argv[argv.index("--model") + 1] == "kimi_linear"
    assert argv[argv.index("--prefix-cache") + 1] == "off"
    assert cell["traffic"]["driver"] == "serve_state"
    assert cell["traffic"]["generator"] == "backlog"
    assert cell["traffic"]["max_total"] == int(argv[argv.index("--max-len") + 1])
    names = [m["name"] for m in cell["metrics"]["per_layer"]]
    assert set(names) == set(NEW_METRICS) | set(REUSED_METRICS)
    assert [m["name"] for m in cell["metrics"]["end_to_end"]] == [
        "out_tok_s", "setup_s"]


def test_the_program_preset_is_the_configuration_files_cut(cell):
    from nezha_tpu.models.kimi_linear import kimi_linear
    cfg, c = cell["config"], kimi_linear("full").cfg
    assert (c.num_hidden_layers, c.experts_held, c.vocab_held) == (
        cfg["num_hidden_layers"], tuple(cfg["experts_held"]), cfg["vocab_size"])
    assert c.num_experts == cfg["published"]["num_experts"]
    lin = cfg["linear_attn_config"]
    assert list(c.kda_layers) == lin["kda_layers"]
    assert list(c.full_attn_layers) == lin["full_attn_layers"]
    assert (c.kda_num_heads, c.kda_head_dim, c.short_conv_kernel_size) == (
        lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"])
    for key in ("hidden_size", "intermediate_size", "num_attention_heads",
                "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "mla_use_nope",
                "first_k_dense_replace", "moe_intermediate_size",
                "num_experts_per_token", "num_shared_experts", "rms_norm_eps",
                "moe_renormalize", "routed_scaling_factor",
                "moe_router_activation_func", "model_max_length"):
        assert getattr(c, key) == cfg[key], key
    assert (c.kda_gate_rank, c.kda_chunk) == (
        cfg["assumed_sizes"]["kda_gate_rank"],
        cfg["assumed_sizes"]["kda_chunk"])


def test_cost_functions_against_hand_reckoned_numbers(sizes):
    assert sizes["kda_layers"] == 4 and sizes["mla_layers"] == 1
    assert sizes["dense_layers"] == 1 and sizes["sparse_layers"] == 4
    # the issue's arithmetic: 39.51M, 29.11M, 7.08M, 63.70M
    assert costs.kda_params(sizes) == (
        4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32
        + 3 * 4096 * 4) == 39_510_016
    assert costs.mla_params(sizes) == (
        2304 * 6144 + 2304 * 576 + 512 * 8192 + 4096 * 2304) == 29_114_368
    assert costs.expert_params(sizes) == 3 * 2304 * 1024 == 7_077_888
    assert costs.dense_mlp_params(sizes) == 3 * 2304 * 9216 == 63_700_992
    assert costs.head_params(sizes) == 40960 * 2304 == 94_371_840
    always = (4 * 39_510_016 + 29_114_368 + 63_700_992
              + 4 * (7_077_888 + 2304 * 256) + 94_371_840)
    assert costs.always_params(sizes) == always
    # a row's state a layer: 32 x 128 x 128 float32 = 2.1 MB; a slot's four
    # layers the issue's 8.39 MB; a token's latent row 1,152 B of work
    assert costs.state_bytes(sizes) == 32 * 128 * 128 * 4 == 2_097_152
    assert costs.tail_bytes(sizes) == 3 * 12288 * 2
    assert costs.latent_row_bytes(sizes) == 1152
    # one KDA call over 256 rows: each state read once and written once
    c = costs.kda_decode(256, sizes)
    assert c["bytes"] == 256 * (2 * 2_097_152 + (5 * 4096 + 32) * 4)
    assert c["flops"] == 256 * 7 * 32 * 128 * 128
    assert c["flops"] / c["bytes"] < 2.0
    assert kernel_costs.min_seconds(c, PEAKS)["bound"] == "bandwidth"
    assert 1.30e-3 < kernel_costs.min_seconds(c, PEAKS)["seconds"] < 1.34e-3
    # one latent call over 256 rows of ~4,400 tokens
    c = costs.latent_decode(256 * 4400, 256, sizes)
    assert c["bytes"] == 256 * 4400 * 1152 + 256 * 32 * (576 + 512) * 2
    assert c["flops"] == 2 * 256 * 4400 * 32 * (576 + 512)
    assert kernel_costs.min_seconds(c, PEAKS)["bound"] == "bandwidth"
    # one sparse layer's experts in a decode step: 60 of the 64 held touched
    # (14.16 MB each), 512 pairs' rows in and out: 0.85 GB, 1.04 ms of bytes
    c = costs.moe_experts(60, 512, sizes)
    assert c["bytes"] == (60 * 7_077_888 + 512 * 2 * 2304) * 2
    assert c["flops"] == 512 * 2 * 7_077_888
    assert kernel_costs.min_seconds(c, PEAKS)["bound"] == "bandwidth"
    assert 1.03e-3 < kernel_costs.min_seconds(c, PEAKS)["seconds"] < 1.05e-3
    # one decode step at ~4,400 resident tokens a row, every held expert
    # touched: the issue's 10.1 GB (4.38 weights + 4.29 state + 1.44 latent
    # at the stored 1,280 B a token; 1.30 at the 1,152 B of work) + tails
    step = costs.decode_step_bytes(256, 256 * 4400, 256, sizes)
    assert step == pytest.approx(
        2 * (always + 256 * 7_077_888) + 256 * 4400 * 1152
        + 4 * 256 * 2 * (2_097_152 + 73_728))
    assert 9.9e9 < step < 10.3e9 and 12.0e-3 < step / 819e9 < 12.6e-3
    state = 4 * 256 * 2 * 2_097_152
    assert 0.40 < state / step < 0.44           # the issue's 42%
    # one output token: two operations a parameter passed, the four
    # states' update and the attention over its context
    f = costs.serve_flops_per_token(8.0, 4400.0, sizes)
    assert f == pytest.approx(2 * (always + 8 * 7_077_888)
                              + 2 * 32 * (576 + 512) * 4400.0
                              + 4 * 7 * 32 * 128 * 128)


def _obs(sizes):
    """Two decode steps inside a traced span; ops named as a v5e trace
    names them (a whole HLO line): a step is 4 state updates and 1 latent
    call, then the experts and a fusion."""
    obs = Obs()
    obs.model, obs.peaks, obs.trace_span = sizes, PEAKS, (10.0, 11.0)
    obs.steps = [(9.0, 256, 1_100_000), (10.2, 256, 1_126_400),
                 (10.7, 250, 1_126_656)]
    # (time, held experts touched, held pairs), summed over 4 sparse layers
    obs.lm_steps = [(9.0, 256, 2048), (10.2, 240, 2048), (10.7, 232, 2000)]
    kda = ('(f32[256,32,128]{2,1,0}, f32[257,32,128,128]{3,2,1,0}) '
           'custom-call(%e, %c, %v, %s), '
           'custom_call_target="tpu_custom_call"')
    lat = ('bf16[256,32,1,512]{3,2,1,0} custom-call(%t, %l, %q, %p), '
           'custom_call_target="tpu_custom_call"')
    ops, t = [], 0.0
    for _ in range(2):
        for i in range(4):
            ops.append(Event(f"%nezha_kda_decode.{i} = {kda}",
                             t + i * 2.0e6, 2.0e6))
        ops.append(Event(f"%nezha_decode_attention_latent.1 = {lat}",
                         t + 8.0e6, 4.0e6))
        ops.append(Event("%ragged-dot.3 = f32[2048,1024]{1,0} custom-call(%q)",
                         t + 12.0e6, 4.0e6))
        ops.append(Event("%ragged-dot.5 = f32[2048,2304]{1,0} custom-call(%h)",
                         t + 16.0e6, 2.0e6))
        ops.append(Event("%fusion.9 = f32[256,40960]{1,0} fusion(%r)",
                         t + 18.0e6, 2.0e6))
        t += 20.0e6
    obs.trace = Trace({0: ops}, [], {})
    obs.samples["step_ms"] = [26.0, 25.0, 24.0]
    for name, v in (("chips", 1), ("tokens_in_span", 400_000),
                    ("token_span_s", 40.0), ("moe_steps", 1600),
                    ("moe_rows", 1600 * 250.0),
                    ("moe_held_pairs", 1600 * 250.0 * 8.0),
                    ("moe_touched", 1600 * 255.0),
                    ("lm_resident_tokens", 1600 * 250.0 * 4400.0),
                    ("moe_load_max_over_mean_sum", 1600 * 2.5)):
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
    # the accepted pattern reads the latent call and not the state update
    assert got["kernel.decode_time_share"] == pytest.approx(20.0)
    assert got["kernel.kda_decode_time_share"] == pytest.approx(40.0)
    assert got["kernel.moe_time_share"] == pytest.approx(30.0)
    # eight held pairs a token, context 4,400
    flops = costs.serve_flops_per_token(8.0, 4400.0, sizes)
    assert got["model.state_serve_mfu"] == pytest.approx(
        10_000 * flops / 197e12 * 100)
    step = costs.decode_step_bytes(255.0, 250 * 4400.0, 250.0, sizes)
    assert got["model.state_decode_hbm_roofline"] == pytest.approx(
        step / 819e9 * 1e3 / 25.0 * 100)
    # the two steps inside the span: 256 and 250 active rows
    least = sum(costs.kda_decode(rows, sizes)["bytes"]
                for rows in (256, 250)) / 2 / 819e9
    assert got["kernel.kda_decode_roofline"] == pytest.approx(
        least / 2.0e-3 * 100)
    least = sum(costs.latent_decode(tokens, rows, sizes)["bytes"]
                for rows, tokens in ((256, 1_126_400), (250, 1_126_656))
                ) / 2 / 819e9
    assert got["kernel.latent_decode_roofline"] == pytest.approx(
        least / 4.0e-3 * 100)
    # the experts' call of a sparse layer: the two steps inside the span
    # touched 60 and 58 held experts a layer; a call's ops took 6 ms
    least = sum(costs.moe_experts(touched / 4, pairs / 4, sizes)["bytes"]
                for touched, pairs in ((240, 2048), (232, 2000))) / 2 / 819e9
    assert got["kernel.state_moe_roofline"] == pytest.approx(
        least / 6.0e-3 * 100)
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
    assert set(got) <= {"kernel.kda_decode_time_share"}
    assert all(v["value"] == 0.0 for v in got.values())


def _tiny_config():
    from nezha_tpu.models.kimi_linear import TINY_KW, KimiLinearConfig
    c = KimiLinearConfig(**TINY_KW)
    return {
        "name": "kimi-tiny", "source": "tests only", "reduced": [],
        "hidden_size": c.hidden_size, "intermediate_size": c.intermediate_size,
        "num_attention_heads": c.num_attention_heads,
        "kv_lora_rank": c.kv_lora_rank,
        "qk_nope_head_dim": c.qk_nope_head_dim,
        "qk_rope_head_dim": c.qk_rope_head_dim, "v_head_dim": c.v_head_dim,
        "linear_attn_config": {
            "kda_layers": list(c.kda_layers),
            "full_attn_layers": list(c.full_attn_layers),
            "num_heads": c.kda_num_heads, "head_dim": c.kda_head_dim,
            "short_conv_kernel_size": c.short_conv_kernel_size},
        "assumed_sizes": {"kda_gate_rank": c.kda_gate_rank,
                          "kda_chunk": c.kda_chunk},
        "first_k_dense_replace": c.first_k_dense_replace,
        "moe_intermediate_size": c.moe_intermediate_size,
        "num_experts_per_token": c.num_experts_per_token,
        "num_hidden_layers": c.num_hidden_layers, "vocab_size": c.vocab_held,
        "num_experts": c.experts_held[1],
        "published": {"num_experts": c.num_experts},
        "experts_held": list(c.experts_held), "rms_norm_eps": c.rms_norm_eps,
        "moe_renormalize": c.moe_renormalize,
        "routed_scaling_factor": c.routed_scaling_factor,
        "serve": {"reference": "kimi_linear", "argv": [
            "--model", "kimi_linear", "--random-init", "--model-preset",
            "tiny", "--max-len", "96", "--max-batch-size", "4",
            "--max-prefill-len", "32", "--prefill-buckets", "16,32",
            "--kv-block-size", "4", "--cache-dtype", "f32",
            "--prefix-cache", "off", "--queue-capacity", "64"]}}


EDGE_CASES = ["chunk_edge.15", "chunk_edge.16", "chunk_edge.17",
              "bucket_edge.31", "bucket_edge.32", "bucket_edge.33",
              "bucket_edge.65", "block_bind.35", "longest.70"]


@pytest.fixture
def tiny_root(tmp_path, monkeypatch):
    """A benchmark root with the cell at tiny size (``tiny.state``)."""
    root = chipbench_tiny.make_root(str(tmp_path))
    with open(os.path.join(root, "cells", f"{CELL}.json")) as f:
        tiny_cell = json.load(f)
    tiny_cell.update(name="tiny.state", config="kimi-tiny",
                     traffic="tiny-gen-state")
    files = {"configs/kimi-tiny.json": _tiny_config(),
             "traffic/tiny-gen-state.json": {
                 **chipbench_tiny.TINY_GEN, "name": "tiny-gen-state",
                 "driver": "serve_state",
                 "prompt": {"unique": {"median": 12, "sigma": 0.5, "min": 4,
                                       "max": 70}}},
             "cells/tiny.state.json": tiny_cell}
    for rel, obj in files.items():
        with open(os.path.join(root, rel), "w") as f:
            json.dump(obj, f)
    monkeypatch.setenv(device.REHEARSAL_ENV, "cpu")
    monkeypatch.setattr(serve_state, "SHORT_PAD", 48)
    return root


def test_tiny_cell_rehearses_through_serve_state(tiny_root, capsys):
    from chipbench import run

    assert run.main(["--root", tiny_root, "--workload", "tiny.state",
                     "--seed", "2150000007", "--seconds", "1", "--trace",
                     "1"]) == 0
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
    # 32 of the mix's own and the edge prompts: KDA chunks of 8, blocks of
    # 4, the widest bucket 32, the mix's prompts 4-70
    assert serve_state.edge_prompt_lengths(8, 4, 32, 4, 70) == {
        "chunk_edge": [15, 16, 17], "bucket_edge": [31, 32, 33, 65],
        "block_bind": [35], "longest": [70]}
    assert chk["ok"] and chk["requests"] == 41 and chk["rows_in_all"] >= 82
    assert chk["tripped"] == []
    assert chk["prompt_lengths"][32:] == [15, 16, 17, 31, 32, 33, 65, 35, 70]
    # a case is the mix's requests together or ONE edge prompt
    assert list(chk["rows_by_case"]) == ["mix", *EDGE_CASES]
    assert all(n >= 4 for n in chk["rows_by_case"].values())
    compared = chk["rows_compared_by_case"]
    assert set(compared) == set(chk["rows_by_case"])
    assert all(0 <= compared[c] <= chk["rows_by_case"][c] for c in compared)
    assert chk["rows_in_all"] - chk["rows_set_aside"] == chk["rows"]
    assert sum(compared.values()) == chk["rows"] >= 4
    # a quartile for every case with four clear rows or more, the mix's
    # among them; float32 at tiny size: 1e-4 of a logit is 0.03 ulps
    quartile = chk["upper_quartile_ulps_by_case"]
    assert "mix" in quartile
    assert set(quartile) == {c for c, n in compared.items() if n >= 4}
    assert chk["upper_quartile_ulps"] == max(quartile.values())
    assert chk["upper_quartile_ulps"] <= chk["largest_row_ulps"] < 0.03
    assert len(chk["row_diffs_ulps"]) == chk["rows_in_all"]
    # the first layer's state of every request, read from the pool after
    # its last compared step, against the reference's after the same tokens
    assert len(chk["state_diffs"]) == 41
    assert max(chk["state_diffs"]) == pytest.approx(chk["state_diff"],
                                                    abs=1e-6)
    assert 0 < chk["state_diff"] < 1e-4
    c = facts["counters"]
    assert c["moe_steps"] > 0 and 0 < c["moe_held_pairs"] <= c["moe_rows"] * 4 * 4
    assert c["lm_resident_tokens"] > c["moe_rows"]
    assert facts["compilations_in_window"] == 0


@pytest.mark.parametrize("which, trips", [
    ("fp8", {"upper_quartile_ulps", "state_diff"}),
    ("bf16-state", {"state_diff"}),
    ("stale-state", {"state_diff"})])
def test_each_control_reads_not_correct_at_tiny_size(tiny_root, capsys,
                                                     monkeypatch, which,
                                                     trips):
    """The three controls through the driver's own comparison (float32 at
    tiny size, so the sound program reads ~0 and a control only what it
    brings): each exits 0, reads ``ok: false`` and names the limits it
    passed. A bf16 state moves the first layer's state and hardly a
    logit; two requests decoding from each other's state read a state
    that is another's on exactly those two."""
    if which == "bf16-state":
        # STATE_TOL stands between two readings at the published widths;
        # four heads of 16 x 16 over under 100 tokens read 3.4-5.1e-3 with
        # a bf16 state and 1e-6 without: a limit between THOSE
        monkeypatch.setattr(serve_state, "STATE_TOL", 1e-3)
    assert serve_state.control([
        "--root", tiny_root, "--workload", "tiny.state", "--seed",
        "2150000007", "--control", which]) == 0
    chk = json.loads(capsys.readouterr().out.splitlines()[-1])[
        "reference_check"]
    assert chk["ok"] is False and trips <= set(chk["tripped"])
    if which == "bf16-state":
        assert chk["tripped"] == ["state_diff"]
        assert chk["upper_quartile_ulps"] < serve_state.LOGIT_TOL_ULPS
    if which == "stale-state":
        assert len(chk["stale_state_planted_in"]) == 2
        off = [d > serve_state.STATE_TOL for d in chk["state_diffs"]]
        assert off == [True, True] + [False] * 39


def test_the_comparison_reads_a_quartile_that_a_few_rows_cannot_move():
    """The rule on synthetic rows: one row in ten far off (tokens after a
    routing split) leaves the reading where it was; a shift of every row
    (a loss of precision) or of ONE edge prompt's rows (a fault in one
    hand-over: a quarter of its kind's rows) moves it past the limit; one
    wild row trips the gross limit, and one request's state the state's."""
    rng = np.random.default_rng(3)
    cases = np.asarray(["mix"] * 400 + ["bucket_edge.1023"] * 40
                       + ["bucket_edge.1024"] * 40 + ["bucket_edge.1025"] * 40
                       + ["bucket_edge.2049"] * 40)
    margin = rng.uniform(0.0, 3.0, 560)
    states = [3.5e-3] * 14

    def tripped(per_row, state_diffs=states):
        return serve_state.judge(per_row, margin, cases, state_diffs)["tripped"]

    base = rng.uniform(2.3, 7.0, 560)
    assert tripped(base) == []
    spiked = base.copy()
    spiked[rng.choice(560, 56, replace=False)] = 20.0
    assert tripped(spiked) == []
    assert tripped(base + 25.0) == ["upper_quartile_ulps"]
    assert tripped(base + 25.0 * (cases == "bucket_edge.2049")) == [
        "upper_quartile_ulps"]
    wild = base.copy()
    wild[7] = 2.0 * serve_state.GROSS_TOL_ULPS
    assert tripped(wild) == ["largest_row_ulps"]
    assert tripped(base, states[:-1] + [1.1e-2]) == ["state_diff"]
    assert tripped(base, states[:-1] + [None]) == ["state_diff"]
    got = serve_state.judge(base, margin, cases, states)
    assert got["ok"] and set(got["upper_quartile_ulps_by_case"]) == set(cases)
    assert not serve_state.judge(base, margin * 0.0, cases, states)["ok"]
    # a run's largest row on the chip, a routing split, read 29.0-39.6
    # over fourteen runs (PERF.md section 6, PR 32): under the gross limit
    assert 39.6 < serve_state.GROSS_TOL_ULPS


def test_edge_prompts_of_the_cell():
    """At the cell's deployment (KDA chunks of 64, blocks of 64, the widest
    bucket 1,024, prompts 256-8,192): every kind inside the mix's own
    lengths."""
    edges = serve_state.edge_prompt_lengths(64, 64, 1024, 256, 8192)
    assert edges == {"chunk_edge": [319, 320, 321],
                     "bucket_edge": [1023, 1024, 1025, 2049],
                     "block_bind": [4223], "longest": [8192]}
    assert [n % 64 for n in edges["chunk_edge"]] == [63, 0, 1]
    # the second decode step's key (position n + 1) opens a latent block
    assert (edges["block_bind"][0] + 1) % 64 == 0
    assert edges["longest"][0] + serve_state.CHECK_STEPS + 1 <= 16384
