"""The Xing4.0 cell: its files against the catalog, its cost functions against
the configuration file's ``bytes`` and numbers reckoned by hand (ISSUE 36),
its readers on a small synthetic ``Obs`` and on an empty one, the traffic
file's draws, and a CPU rehearsal of a tiny cell through
``drivers/serve_mhc.py`` with its control."""

import json
import os
import re

import numpy as np
import pytest

import chipbench_tiny
from chipbench import device, manifest, traffic as traffic_lib
from chipbench.drivers import serve_mhc
from chipbench.obs import Obs
from chipbench.trace import kernel_costs, kernel_costs_xing4 as costs
from chipbench.trace.reduce import Event, Trace

CELL = "xing4.0-29b.long-prompt-16k"
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
NEW_METRICS = ("model.mhc_serve_mfu", "model.mhc_decode_hbm_roofline",
               "kernel.mhc_time_share", "kernel.mhc_roofline",
               "engine.prefill_busy_share", "kernel.mhc_moe_roofline",
               "kernel.mhc_mla_decode_roofline")
REUSED_METRICS = ("sched.batch_occupancy", "engine.step_ms_p50",
                  "engine.kv_pool_fill_share", "device.idle_share",
                  "device.hbm_peak_gb", "model.moe_load_max_over_mean",
                  "kernel.moe_time_share", "kernel.mla_decode_time_share")


@pytest.fixture(scope="module")
def cell():
    return manifest.load_cell(CELL)


@pytest.fixture(scope="module")
def sizes(cell):
    return serve_mhc.sizes_of(cell["config"])


def test_the_cell_loads_with_published_widths_and_its_cut(cell):
    cfg = cell["config"]
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Xing4.0-29B-A4B")
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value       # stated beside the cut
        else:
            assert cfg[key] == value, key               # verbatim, no width cut
    assert cfg["reduced"] == ["num_hidden_layers", "num_nextn_predict_layers"]
    assert (cfg["num_hidden_layers"], cfg["num_nextn_predict_layers"]) == (6, 0)
    # held whole: every expert, the vocabulary, both leading dense layers,
    # and four of the sparse layers that follow them (the floor)
    assert (cfg["n_routed_experts"], cfg["experts_held"],
            cfg["vocab_size"], cfg["first_k_dense_replace"]) == (
        64, [0, 64], 131072, 2)
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert (cfg["hc_mult"], cfg["hc_sinkhorn_iters"], cfg["hc_eps"],
            cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"]) == (
        4, 20, 1e-6, -30, 30)
    # every assumption names the alternative that was not run
    structural = {"start_and_read_out", "one_map_a_sublayer",
                  "clamp_before_exp", "rows_first", "eps_in_the_denominators",
                  "float32_maps_and_streams", "map_norm",
                  "interleaved_rotary", "selection_bias", "hc_init"}
    assert structural <= set(cfg["assumed"])
    assert all(re.search(r"the alternatives? not run", cfg["assumed"][k])
               for k in structural)
    assert "8 v5e chips" in cfg["deployment"]
    rehearsal = cfg["serve"]["rehearsal"]
    assert rehearsal["pool_shaped_copies_in_any_hlo"] == 0
    assert set(rehearsal["program_bytes"]) == {
        "step", "prefill1024", "prefill512", "prefill128"}
    assert max(p["live"] for p in rehearsal["program_bytes"].values()) \
        < 0.9 * 16e9
    argv = cfg["serve"]["argv"]
    assert argv == ("--model xing4 --random-init --model-preset full "
                    "--max-len 16384 --max-batch-size 32 --max-prefill-len "
                    "1024 --prefill-buckets 128,512,1024 --kv-block-size 64 "
                    "--kv-dtype bf16 --prefix-cache on --queue-capacity "
                    "64").split()
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    names = [m["name"] for m in cell["metrics"]["per_layer"]]
    assert set(names) == set(NEW_METRICS) | set(REUSED_METRICS)
    assert all(m["moves"] == "out_tok_s" for m in cell["metrics"]["per_layer"]
               if m["name"] in NEW_METRICS)
    assert [m["name"] for m in cell["metrics"]["end_to_end"]] == [
        "out_tok_s", "setup_s"]


def test_the_traffic_is_the_issues_letter_for_letter(cell):
    tr = cell["traffic"]
    assert (tr["driver"], tr["generator"], tr["backlog_depth"]) == (
        "serve_mhc", "backlog", 16)
    assert tr["prompt"] == {"unique": {"median": 8192, "sigma": 0.3,
                                       "min": 4096, "max": 14336}}
    assert tr["output"] == {"median": 512, "sigma": 0.4, "min": 128,
                            "max": 1536}
    assert tr["max_total"] == 16384
    assert tr["sampling"] == {"temperature": 0.8, "top_k": 40}
    assert tr["warmup"] == {"seconds": 3.0, "fill": "stationary"}
    assert tr["trace_seconds"] == 5.0
    # beside ISSUE 36's parameters: the window replays ONE draw of them
    assert tr["lengths_seed"] == 36


def test_a_window_replays_recorded_lengths_with_the_runs_own_ids(
        cell, sizes, monkeypatch):
    """``lengths_seed``: the fill's and the backlog's lengths are the
    general generator's draw from that seed whatever ``--seed`` is; token
    ids and sampling seeds are the run's; a check request is never
    touched; a mix without the key draws everything from the run."""
    import itertools

    from chipbench.drivers import serve, serve_lm

    def window(self, seconds, rate_per_s=None):
        # what the base's window does with a backlog mix, without a stack
        tr = self.traffic
        reqs = traffic_lib.stationary_fill(tr, self.seed, self.vocab, 4) \
            + list(itertools.islice(
                traffic_lib.request_stream(tr, self.seed, self.vocab), 6))
        self.sent = [self._submit(r, 0.0) for r in reqs]
        return {"correct": True}

    monkeypatch.setattr(serve_lm.ServeLMRun, "measure", window)
    monkeypatch.setattr(serve.ServeRun, "_submit",
                        lambda self, req, due, check=False: req)

    def run(seed, traffic):
        job = serve_mhc.ServeMhcRun({**cell, "traffic": traffic}, seed, 1.0,
                                    False, "")
        job.vocab, job.engine, job._replay_for = 131072, None, None
        job.obs.model = sizes
        assert job.measure(1.0) == {"correct": True}
        assert job.seed == seed and job._replay_for is None
        assert job.facts["lengths_seed"] == traffic.get("lengths_seed")
        return job.sent

    tr = cell["traffic"]
    a, b, again = run(11, tr), run(2150000007, tr), run(11, tr)
    shape = lambda reqs: [(r.index, len(r.prompt), r.max_new_tokens)  # noqa: E731
                          for r in reqs]
    recorded = traffic_lib.stationary_fill(tr, 36, 131072, 4) + list(
        itertools.islice(traffic_lib.request_stream(tr, 36, 131072), 6))
    assert shape(a) == shape(b) == shape(recorded)
    assert all(x.prompt != y.prompt and x.seed != y.seed
               for x, y in zip(a, b))
    assert [(r.prompt, r.seed) for r in a] == [(r.prompt, r.seed)
                                               for r in again]
    assert max(max(r.prompt) for r in a) < 131072
    free = {k: v for k, v in tr.items() if k != "lengths_seed"}
    assert shape(run(11, free)) != shape(run(12, free))
    # a check request goes through as it was made
    job = serve_mhc.ServeMhcRun(cell, 5, 1.0, False, "")
    job.vocab, job._replay_for = 131072, 5
    assert job._submit(recorded[0], 0.0, check=True) is recorded[0]


@pytest.mark.parametrize("seed", [11, 2150000007])
def test_the_traffic_draws_lengths_inside_its_bounds(cell, seed):
    tr = cell["traffic"]
    stream = traffic_lib.request_stream(tr, seed, 131072)
    reqs = [next(stream) for _ in range(64)]
    prompts = np.asarray([len(r.prompt) for r in reqs])
    outputs = np.asarray([r.max_new_tokens for r in reqs])
    assert 4096 <= prompts.min() and prompts.max() <= 14336
    assert 128 <= outputs.min() and outputs.max() <= 1536
    assert (prompts + outputs).max() <= tr["max_total"]
    assert 7000 < np.median(prompts) < 9500 and 380 < np.median(outputs) < 680
    assert max(max(r.prompt) for r in reqs[:4]) < 131072
    assert {r.temperature for r in reqs} == {0.8}
    fill = traffic_lib.stationary_fill(tr, seed, 131072, 32)
    assert len(fill) == 32 and max(
        len(r.prompt) + r.max_new_tokens for r in fill) <= tr["max_total"]


def test_the_program_preset_is_the_configuration_files_cut(cell):
    from nezha_tpu.models.xing4 import xing4
    cfg, c = cell["config"], xing4("full").cfg
    assert (c.num_hidden_layers, c.experts_held, c.vocab_held) == (
        cfg["num_hidden_layers"], tuple(cfg["experts_held"]), cfg["vocab_size"])
    for key in ("hidden_size", "intermediate_size", "num_attention_heads",
                "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "first_k_dense_replace",
                "n_routed_experts", "num_experts_per_tok", "n_shared_experts",
                "moe_intermediate_size", "norm_topk_prob",
                "routed_scaling_factor", "scoring_func", "rms_norm_eps",
                "max_position_embeddings", "rope_theta", "hc_mult",
                "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
                "mhc_h_res_clamp_max", "vocab_size"):
        assert getattr(c, key) == cfg[key], key
    rope = cfg["rope_scaling"]
    assert (c.rope_factor, c.rope_original_max, c.rope_beta_fast,
            c.rope_beta_slow, c.rope_mscale, c.rope_mscale_all_dim) == (
        rope["factor"], rope["original_max_position_embeddings"],
        rope["beta_fast"], rope["beta_slow"], rope["mscale"],
        rope["mscale_all_dim"])


def test_cost_functions_match_the_configuration_files_bytes(cell, sizes):
    """The issue's arithmetic, the file's ``bytes`` and the functions agree:
    a number the file states is the number the function gives."""
    assert (sizes["dense_layers"], sizes["sparse_layers"],
            sizes["hc_streams"], sizes["hc_maps"], sizes["sublayers"]) == (
        2, 4, 4, 24, 12)
    assert costs.attention_params(sizes) == (
        3584 * 768 + 768 * 32 * 192 + 3584 * 576 + 512 * 32 * 256
        + 4096 * 3584) == 28_409_856
    assert costs.expert_params(sizes) == 3 * 3584 * 1024 == 11_010_048
    assert costs.dense_mlp_params(sizes) == 3 * 3584 * 9216 == 99_090_432
    assert costs.head_params(sizes) == 131072 * 3584 == 469_762_048
    assert costs.hc_params(sizes) == 24 * 4 * 3584 == 344_064
    always = (6 * 28_409_856 + 2 * 99_090_432
              + 4 * (11_010_048 + 3584 * 64) + 469_762_048)
    assert costs.always_params(sizes) == always
    stated = cell["config"]["bytes"]
    text = stated["parameters"]
    for millions, value in ((28.41, costs.attention_params(sizes)),
                            (11.01, costs.expert_params(sizes)),
                            (99.09, costs.dense_mlp_params(sizes)),
                            (0.69, 2 * costs.hc_params(sizes))):
        assert f"{millions}M" in text
        assert value / 1e6 == pytest.approx(millions, abs=0.006)
    # every parameter held: what every token passes + the 64 experts of each
    # sparse layer + the embedding; the file's 4.172B and 8.36 GB
    held = always + 4 * 64 * costs.expert_params(sizes) \
        + costs.head_params(sizes)
    assert "4.172B" in text and held / 1e9 == pytest.approx(4.172, abs=5e-4)
    held_bytes = 2 * held + 4 * 12 * costs.hc_params(sizes)
    assert "8.36 GB" in text and held_bytes / 1e9 == pytest.approx(8.36,
                                                                   abs=5e-3)
    rehearsal = cell["config"]["serve"]["rehearsal"]["program_bytes"]
    pool = (32 * 256 + 1) * 64 * 6 * 1280
    assert "4.03 GB" in stated["pool"] and pool / 1e9 == pytest.approx(
        4.03, abs=5e-3)
    # what the compiler counted as arguments: weights + pool + last logits
    assert rehearsal["step"]["arguments"] == pytest.approx(
        held_bytes + pool + 32 * 131072 * 4, rel=2e-3)
    assert costs.latent_row_bytes(sizes) == 1152
    assert costs.stream_bytes(sizes) == 57_344
    assert "57,344 B" in stated["streams"]
    assert 58.7e6 == pytest.approx(1024 * costs.stream_bytes(sizes), rel=1e-3)
    # the two kernels: the issue's least bytes a sublayer (streams read
    # twice and written once, u and y once, phi once: 1.38 MB)
    pre, post = (costs.mhc_call(k, 1024, sizes) for k in ("pre", "post"))
    assert pre["bytes"] == 1024 * (57_344 + (3584 + 24) * 4) + 344_064 * 4
    assert post["bytes"] == 1024 * (2 * 57_344 + (3584 + 24) * 4)
    assert 344_064 * 4 == pytest.approx(1.38e6, rel=3e-3)
    both = pre["bytes"] + post["bytes"]
    assert both == pytest.approx(1024 * 3 * 57_344, rel=0.18)
    for c in (pre, post):
        assert kernel_costs.min_seconds(c, PEAKS)["bound"] == "bandwidth"
    # ~0.2 ms a sublayer at the chip's bandwidth (the issue's figure)
    assert 0.20e-3 < both / 819e9 < 0.26e-3
    with pytest.raises(ValueError):
        costs.mhc_call("mid", 1, sizes)
    # one decode step, 32 rows of ~8,900 tokens, every expert of the four
    # sparse layers touched: the issue's ~7.6 GB of weights + ~2.2 GB of
    # latent rows (at the stored 1,280 B a token; 1.97 at the 1,152 of work)
    step = costs.decode_step_bytes(4 * 64, 32 * 8900, 32, sizes)
    weights = 2 * (always + 256 * 11_010_048)
    latent = 6 * 32 * 8900 * 1152
    streams = 12 * (344_064 * 4 + 32 * 3 * 57_344)
    assert step == pytest.approx(weights + latent + streams)
    assert 7.3e9 < weights < 7.7e9 and 1.9e9 < latent < 2.3e9
    assert streams < 0.01 * step
    # an output token at context 8,900 with 16 held pairs (4 a sparse
    # layer), and a prompt of 8,192 uncached tokens
    f = costs.decode_flops_per_token(16.0, 8900.0, sizes)
    assert f == pytest.approx(
        2 * (always + 16 * 11_010_048) + 6 * 2 * 32 * (576 + 512) * 8900.0
        + costs.mhc_flops_per_token(sizes))
    n = 8192
    p = costs.prefill_flops(n, n * (n + 1) / 2, 16.0, sizes)
    assert p == pytest.approx(
        n * (2 * (always - 469_762_048 + 16 * 11_010_048)
             + costs.mhc_flops_per_token(sizes))
        + 6 * 2 * 32 * (192 + 128) * n * (n + 1) / 2)
    # a request's prefill needs ~5 times the operations of its 512 output
    # tokens (which are bound by bytes, not by operations)
    assert 0.15 < 512 * f / p < 0.3


def test_the_reference_in_blocks_is_the_reference_whole(monkeypatch):
    """On the chip the reference runs a block of heads, of query rows, of
    tokens, of the dense width and of the vocabulary at a time; at tiny size
    every block is the whole, so the blocks are made small here and the two
    passes compared (float32 round-off)."""
    import jax
    import jax.numpy as jnp

    from chipbench.reference import xing4 as ref
    from nezha_tpu.models.xing4 import xing4

    model = xing4("tiny")
    params = model.init(jax.random.PRNGKey(0))["params"]
    cfg = _tiny_config()
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 64), 0, 512)
    pos = jnp.arange(64)[None]
    whole, margin = ref.logits_at(params, toks, pos, cfg, with_margins=True)
    for name, size in (("QUERY_BLOCK", 16), ("HEAD_BLOCK", 2),
                       ("TOKEN_BLOCK", 16), ("WIDTH_BLOCK", 32),
                       ("VOCAB_BLOCK", 128)):
        monkeypatch.setattr(ref, name, size)
    monkeypatch.setattr(ref, "_LAYER_FNS", {})
    blocked, margin_b = ref.logits_at(params, toks, pos, cfg,
                                      with_margins=True)
    assert float(jnp.abs(whole).max()) > 0.3
    assert float(jnp.abs(whole - blocked).max()) < 1e-5
    assert float(jnp.abs(jnp.minimum(margin, 50) - jnp.minimum(margin_b, 50)
                         ).max()) < 1e-2


MHC_PRE = ('(f32[{t},3584]{{1,0}}, f32[{t},128]{{1,0}}) custom-call(%sc, %x, '
           '%phi), custom_call_target="tpu_custom_call"')
MHC_POST = ('f32[{t},14336]{{1,0}} custom-call(%x, %y, %m), '
            'custom_call_target="tpu_custom_call"')


def _obs(sizes):
    """A traced span of four programs in device order: a decode step (32
    rows), a 1,024-token chunk, a 128-token chunk, a decode step; ops named
    as a v5e trace names them (a whole HLO line). A program is 12 calls of
    each kernel (one shown a sublayer) with other work between."""
    obs = Obs()
    obs.model, obs.peaks, obs.trace_span = sizes, PEAKS, (10.0, 11.0)
    obs.steps = [(10.2, 32, 284_800), (10.7, 32, 284_832)]
    obs.lm_steps = [(10.2, 200, 512), (10.7, 190, 512)]
    ops, t = [], 0.0
    for tokens, pre_ns, post_ns, other_ns in (
            (32, 10e3, 8e3, 1.0e6), (1024, 150e3, 200e3, 4.0e6),
            (128, 20e3, 25e3, 0.5e6), (32, 10e3, 8e3, 1.0e6)):
        # a step's sampling and embedding run before its first marker
        ops.append(Event("%fusion.1 = s32[32]{0} fusion(%l)", t, 2e3))
        t += 2e3
        for i in range(12):
            ops.append(Event(f"%nezha_mhc_pre.{i} = "
                             + MHC_PRE.format(t=tokens), t, pre_ns))
            ops.append(Event(f"%nezha_mla_decode_paged.{i} = bf16[32,32,1,"
                             f"512]{{3,2,1,0}} custom-call(%q)"
                             if tokens == 32 else
                             f"%fusion.{i} = bf16[1,{tokens},4096]{{2,1,0}} "
                             f"fusion(%a)", t + pre_ns, other_ns / 24))
            ops.append(Event(f"%nezha_mhc_post.{i} = "
                             + MHC_POST.format(t=tokens),
                             t + pre_ns + other_ns / 24, post_ns))
            ops.append(Event(f"%nezha_moe_experts.{i} = (f32[{tokens * 4},"
                             f"3584]{{1,0}}, s32[2]{{0}}) custom-call(%w)",
                             t + pre_ns + other_ns / 24 + post_ns,
                             other_ns / 24))
            t += pre_ns + post_ns + other_ns / 12
        t += 50e3                       # the host's turnaround: idle
    obs.trace = Trace({0: ops}, [], {})
    obs.samples["step_ms"] = [16.0, 15.0, 14.0]
    for name, v in (("chips", 1), ("slots", 32), ("tokens_in_span", 60_000),
                    ("token_span_s", 40.0), ("sample_window_s", 35.0),
                    ("moe_steps", 2000), ("moe_rows", 2000 * 32.0),
                    ("moe_held_pairs", 2000 * 32.0 * 16.0),
                    ("moe_touched", 2000 * 200.0),
                    ("lm_resident_tokens", 2000 * 32.0 * 8900.0),
                    ("prompt_tokens_uncached", 70 * 8192.0),
                    ("mhc_prompt_context_sum", 70 * 8192 * 8193 / 2.0)):
        obs.set(name, v)
    return obs


def test_new_readers_on_a_synthetic_obs(cell, sizes):
    obs = _obs(sizes)
    files = [m for m in cell["metrics"]["per_layer"]
             if m["name"] in NEW_METRICS + ("kernel.moe_time_share",
                                            "kernel.mla_decode_time_share")]
    got = {k: v["value"] for k, v in manifest.read_metrics(files, obs).items()}
    assert set(got) == set(NEW_METRICS) | {"kernel.moe_time_share",
                                           "kernel.mla_decode_time_share"}
    busy = 4 * 2e3 + 12 * (2 * (10e3 + 8e3 + 1.0e6 / 12) + (150e3 + 200e3
                           + 4.0e6 / 12) + (20e3 + 25e3 + 0.5e6 / 12))
    mhc = 12 * (2 * 18e3 + 350e3 + 45e3)
    assert got["kernel.mhc_time_share"] == pytest.approx(mhc / busy * 100)
    # every call's least time by its own tokens, over every call's time
    least = sum(12 * (costs.mhc_call("pre", t, sizes)["bytes"]
                      + costs.mhc_call("post", t, sizes)["bytes"]) / 819e9
                for t in (32, 1024, 128, 32))
    assert got["kernel.mhc_roofline"] == pytest.approx(
        least / (mhc / 1e9) * 100)
    # the two chunks' programs over all four: a program runs from its first
    # marker to the next program's (the idle between them is not busy time)
    step = 12 * (18e3 + 1.0e6 / 12) + 2e3
    chunks = 12 * (350e3 + 4.0e6 / 12) + 2e3 + 12 * (45e3 + 0.5e6 / 12) + 2e3
    assert got["engine.prefill_busy_share"] == pytest.approx(
        chunks / (chunks + 2 * step - 2e3) * 100, rel=1e-3)
    assert got["kernel.moe_time_share"] == pytest.approx(
        (2 * 1.0e6 + 4.0e6 + 0.5e6) / 2 / busy * 100)
    # the accepted latent-decode patterns read the kernel by its name
    assert got["kernel.mla_decode_time_share"] == pytest.approx(
        2 * 1.0e6 / 2 / busy * 100)
    decode = 1500.0 * costs.decode_flops_per_token(16.0, 8900.0, sizes)
    prefill = costs.prefill_flops(70 * 8192.0, 70 * 8192 * 8193 / 2.0, 16.0,
                                  sizes) / 35.0
    assert got["model.mhc_serve_mfu"] == pytest.approx(
        (decode + prefill) / 197e12 * 100)
    need = costs.decode_step_bytes(200.0, 32 * 8900.0, 32.0, sizes)
    assert got["model.mhc_decode_hbm_roofline"] == pytest.approx(
        need / 819e9 * 1e3 / 15.0 * 100)
    assert all(0 < got[n] < 100 for n in NEW_METRICS[:5])
    # the experts' calls by their own pairs (the result's rows): a step's
    # 128 over the experts the program counted as touched (200 and 190 over
    # four sparse layers), a chunk's 4,096 and 512 over all 64 held
    sec = lambda cost: kernel_costs.min_seconds(cost, PEAKS)["seconds"]  # noqa: E731
    least = 12 * (2 * sec(costs.moe_experts(195 / 4, 128, sizes))
                  + sec(costs.moe_experts(64, 4096, sizes))
                  + sec(costs.moe_experts(64, 512, sizes)))
    assert got["kernel.mhc_moe_roofline"] == pytest.approx(
        least / ((2 * 1.0e6 + 4.0e6 + 0.5e6) / 2 / 1e9) * 100)
    # the latent decode call, a layer: the span's resident rows read once
    least = 24 * sec(costs.latent_decode(284_816, 32, sizes))
    assert got["kernel.mhc_mla_decode_roofline"] == pytest.approx(
        least / (2 * 1.0e6 / 2 / 1e9) * 100)
    by_hand = (55 * 3 * 3584 * 1024 + 128 * 2 * 3584) * 2
    assert costs.moe_experts(55, 128, sizes)["bytes"] == by_hand
    assert costs.latent_decode(1000, 32, sizes) == {
        "bytes": 1000 * 1152 + 32 * 32 * (576 + 512) * 2,
        "flops": 2 * 1000 * 32 * (576 + 512)}


def test_new_readers_read_nothing_where_nothing_is(cell):
    """An untraced run, a program without the kernels or the counters (the
    parent), another model's sizes: every new metric is left out and
    nothing raises."""
    files = [m for m in cell["metrics"]["per_layer"] if m["name"] in NEW_METRICS]
    assert len(files) == len(NEW_METRICS)
    assert manifest.read_metrics(files, Obs()) == {}
    obs = Obs()
    obs.peaks, obs.trace_span = PEAKS, (0.0, 1.0)
    obs.model = {"layers": 6, "heads": 32}              # serve_lm's sizes
    obs.set("slots", 32)
    obs.trace = Trace({0: [Event("%fusion.1 = f32[8]{0} fusion()", 0.0, 1e6)]},
                      [], {})
    got = manifest.read_metrics(files, obs)
    assert set(got) <= {"kernel.mhc_time_share"}
    assert all(v["value"] == 0.0 for v in got.values())


def _tiny_config():
    from nezha_tpu.models.xing4 import TINY_KW, Xing4Config
    c = Xing4Config(**TINY_KW)
    keys = ("hidden_size", "intermediate_size", "num_attention_heads",
            "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "first_k_dense_replace",
            "n_routed_experts", "num_experts_per_tok",
            "moe_intermediate_size", "norm_topk_prob",
            "routed_scaling_factor", "rms_norm_eps", "rope_theta", "hc_mult",
            "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
            "mhc_h_res_clamp_max", "num_hidden_layers")
    return {
        "name": "xing4-tiny", "source": "tests only", "reduced": [],
        **{k: getattr(c, k) for k in keys}, "vocab_size": c.vocab_held,
        "rope_scaling": {
            "type": "yarn", "factor": c.rope_factor,
            "original_max_position_embeddings": c.rope_original_max,
            "beta_fast": c.rope_beta_fast, "beta_slow": c.rope_beta_slow,
            "mscale": 1, "mscale_all_dim": 1},
        "published": {"n_routed_experts": c.n_routed_experts},
        "experts_held": list(c.experts_held),
        "serve": {"reference": "xing4", "argv": [
            "--model", "xing4", "--random-init", "--model-preset", "tiny",
            "--max-len", "128", "--max-batch-size", "4",
            "--max-prefill-len", "32", "--prefill-buckets", "16,32",
            "--kv-block-size", "4", "--cache-dtype", "f32",
            "--prefix-cache", "on", "--queue-capacity", "64"]}}


EDGE_CASES = ["bucket_edge.31", "bucket_edge.32", "bucket_edge.33",
              "block_bind.35", "shortest.4", "longest.70"]


@pytest.fixture
def tiny_root(tmp_path, monkeypatch):
    """A benchmark root with the cell at tiny size (``tiny.mhc``)."""
    root = chipbench_tiny.make_root(str(tmp_path))
    with open(os.path.join(root, "cells", f"{CELL}.json")) as f:
        tiny_cell = json.load(f)
    tiny_cell.update(name="tiny.mhc", config="xing4-tiny",
                     traffic="tiny-gen-mhc")
    files = {"configs/xing4-tiny.json": _tiny_config(),
             "traffic/tiny-gen-mhc.json": {
                 **chipbench_tiny.TINY_GEN, "name": "tiny-gen-mhc",
                 "driver": "serve_mhc", "max_total": 120,
                 "prompt": {"unique": {"median": 12, "sigma": 0.5, "min": 4,
                                       "max": 70}},
                 "output": {"median": 24, "sigma": 0.3, "min": 8, "max": 40}},
             "cells/tiny.mhc.json": tiny_cell}
    for rel, obj in files.items():
        with open(os.path.join(root, rel), "w") as f:
            json.dump(obj, f)
    monkeypatch.setenv(device.REHEARSAL_ENV, "cpu")
    monkeypatch.setattr(serve_mhc, "SHORT_PAD", 64)
    return root


def test_tiny_cell_rehearses_through_serve_mhc(tiny_root, capsys):
    from chipbench import run

    assert run.main(["--root", tiny_root, "--workload", "tiny.mhc",
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
    chk = facts["reference_check"]
    assert serve_mhc.edge_prompt_lengths(4, 32, 4, 70) == {
        "bucket_edge": [31, 32, 33], "block_bind": [35], "shortest": [4],
        "longest": [70]}
    assert chk["ok"] and chk["tripped"] == []
    assert chk["requests"] == serve_mhc.CHECK_REQUESTS + 6
    assert chk["prompt_lengths"][serve_mhc.CHECK_REQUESTS:] == [
        31, 32, 33, 35, 4, 70]
    assert list(chk["rows_compared_by_case"]) == ["mix", *EDGE_CASES]
    assert chk["rows_in_all"] - chk["rows_set_aside"] == chk["rows"] >= 4
    assert chk["rows_in_all"] >= 14 * 8
    # float32 at tiny size: 1e-4 of a logit is 0.03 ulps
    assert chk["upper_quartile_ulps"] <= chk["largest_clear_row_ulps"] \
        <= chk["largest_row_ulps"] < 0.03
    assert len(chk["row_diffs_ulps"]) == chk["rows_in_all"]
    assert 0.0 < chk["clear_rows_rel_rms"] < 1e-5
    assert 0.0 < chk["median_row_rel_rms"] < 1e-5 < chk["row_rel_rms_tol"]
    # the residual path's two readings, in the facts line
    assert 0.0 < facts["serve.mhc.sinkhorn_residual_max"] < 0.5
    c = facts["counters"]
    assert facts["serve.mhc.maps_total"] == 8 * (
        c["decode_rows"] + c["prefill_tokens_padded"])
    assert c["mhc_prompt_context_sum"] >= c["prompt_tokens_uncached"] > 0
    assert c["moe_steps"] > 0 and 0 < c["moe_held_pairs"] <= c["moe_rows"] * 2 * 2
    assert facts["compilations_in_window"] == 0


@pytest.mark.parametrize("control,tripped,passed", [
    ("fp8", "upper_quartile_ulps", None),
    ("bf16-streams", "median_row_rel_rms", "upper_quartile_ulps")])
def test_the_controls_at_tiny_size(tiny_root, capsys, monkeypatch, control,
                                   tripped, passed):
    """The reference with fp8 activations, or with bf16 streams and maps,
    through the driver's own comparison (float32 at tiny size, so the sound
    program reads ~0 and a control only what it brings): each exits 0 and
    reads ``ok: false``. The limits stand between two readings at the
    published widths; here four layers of 64-wide contractions read 4.5-5.7
    ulps with fp8 activations and 0.03 without, and a row's relative rms
    1e-6 sound and 3e-3 with bf16 streams: limits between THOSE. As at the
    published widths, bf16 streams pass the limit on a row's largest
    difference and trip the one on the rows' median relative rms."""
    monkeypatch.setattr(serve_mhc, "LOGIT_TOL_ULPS", 1.0)
    monkeypatch.setattr(serve_mhc, "ROW_REL_RMS_TOL", 1e-4)
    code = serve_mhc.control([
        "--root", tiny_root, "--workload", "tiny.mhc", "--seed",
        "2150000007", "--control", control])
    chk = json.loads(capsys.readouterr().out.splitlines()[-1])[
        "reference_check"]
    assert code == 0 and chk["ok"] is False
    assert tripped in chk["tripped"] and passed not in chk["tripped"]
    assert chk["median_row_rel_rms"] > 1e-3 and chk["clear_rows_rel_rms"] > 1e-3


def test_the_comparison_reads_a_quartile_that_a_few_rows_cannot_move():
    rng = np.random.default_rng(3)
    cases = np.asarray(["mix"] * 256 + ["bucket_edge.5119"] * 32
                       + ["bucket_edge.5120"] * 32 + ["longest.14336"] * 32)
    margin = rng.uniform(0.0, 3.0, 352)

    def tripped(per_row):
        return serve_mhc.judge(per_row, margin, cases)["tripped"]

    sound = rng.uniform(1.0, 0.6 * serve_mhc.LOGIT_TOL_ULPS, 352)
    assert tripped(sound) == []
    spiked = sound.copy()
    spiked[rng.choice(352, 35, replace=False)] = \
        0.9 * serve_mhc.GROSS_TOL_ULPS
    assert tripped(spiked) == []
    # a row whose own routing split is set aside, however far off it reads
    split = sound.copy()
    split[margin < serve_mhc.ROUTER_MARGIN_ULPS] = 200.0
    assert tripped(split) == []
    # a loss of precision moves every row, or every row of one edge prompt:
    # past the quartile's limit and still under the largest row's
    off = 0.6 * serve_mhc.LOGIT_TOL_ULPS
    assert 1.6 * serve_mhc.LOGIT_TOL_ULPS < serve_mhc.GROSS_TOL_ULPS
    assert tripped(sound + off) == ["upper_quartile_ulps"]
    assert tripped(sound + off * (cases == "longest.14336")) == [
        "upper_quartile_ulps"]
    wild = sound.copy()
    wild[int(np.argmax(margin))] = 2.0 * serve_mhc.GROSS_TOL_ULPS
    assert tripped(wild) == ["largest_clear_row_ulps"]
    assert not serve_mhc.judge(sound, margin * 0.0, cases)["ok"]
    assert not serve_mhc.judge(sound, margin, cases, finite=False)["ok"]
    # the rows' median relative rms: one row in eight far off (a split
    # routing) does not move it; every row a quarter up does (what bf16
    # streams add), with the largest differences still under their limits
    rel = rng.uniform(0.85, 0.95, 352) * 1e-2
    far = rel.copy()
    far[::8] = 0.5

    def judged(row_rel):
        return serve_mhc.judge(sound, margin, cases, row_rel=row_rel)

    assert judged(rel)["tripped"] == judged(far)["tripped"] == []
    assert judged(rel)["median_row_rel_rms"] == pytest.approx(0.9e-2, rel=0.03)
    assert judged(rel * 1.27)["tripped"] == ["median_row_rel_rms"]
    assert "median_row_rel_rms" in serve_mhc.judge(
        sound, margin, cases, finite=False, row_rel=rel)["tripped"]
    assert 9.47e-3 * 1.1 < serve_mhc.ROW_REL_RMS_TOL < 1.189e-2 / 1.1


def test_edge_prompts_of_the_cell(cell):
    """At the cell's deployment (blocks of 64, the widest bucket 1,024,
    prompts 4,096-14,336): every kind inside the mix's own lengths, and the
    reference's two padded lengths hold them."""
    unique = cell["traffic"]["prompt"]["unique"]
    edges = serve_mhc.edge_prompt_lengths(64, 1024, unique["min"],
                                          unique["max"])
    assert edges == {"bucket_edge": [5119, 5120, 5121], "block_bind": [9215],
                     "shortest": [4096], "longest": [14336]}
    assert (edges["block_bind"][0] + 1) % 64 == 0
    assert edges["longest"][0] + serve_mhc.CHECK_STEPS + 1 <= 16384
    assert serve_mhc.SHORT_PAD % 512 == 0 and serve_mhc.SHORT_PAD > 8192
    assert -(-(14336 + serve_mhc.CHECK_STEPS) // 512) * 512 == 14848
