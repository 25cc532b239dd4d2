"""CLI tests: the `nezha-train` entry point runs configs end-to-end
(SURVEY.md §1 `cmd/nezha-train`)."""

import json

import numpy as np

from nezha_tpu.cli.train import build_parser, run


def _run(argv):
    return run(build_parser().parse_args(argv))


def test_cli_mlp_mnist(tmp_path):
    metrics = _run(["--config", "mlp_mnist", "--steps", "30",
                    "--batch-size", "64", "--log-every", "10",
                    "--metrics-file", str(tmp_path / "m.jsonl")])
    assert np.isfinite(metrics["loss"])
    lines = (tmp_path / "m.jsonl").read_text().strip().splitlines()
    assert len(lines) == 3
    assert "examples_per_sec" in json.loads(lines[-1])


def test_cli_resume(tmp_path):
    ck = str(tmp_path / "ck")
    _run(["--config", "mlp_mnist", "--steps", "10", "--batch-size", "64",
          "--ckpt-dir", ck])
    m = _run(["--config", "mlp_mnist", "--steps", "5", "--batch-size", "64",
              "--ckpt-dir", ck, "--log-every", "5"])
    # Resumed from 10 -> logged step numbers continue past it.
    assert m["step"] == 15


def test_cli_dp_mesh(devices8, capsys):
    """ResNet (tiny preset) actually trains data-parallel over the 8-device
    mesh through the CLI — no degrade warning, finite loss."""
    metrics = _run(["--config", "resnet50_imagenet", "--model-preset", "tiny",
                    "--steps", "4", "--batch-size", "16", "--mesh", "dp=8",
                    "--log-every", "2"])
    assert np.isfinite(metrics["loss"])
    assert "only 1 device" not in capsys.readouterr().err  # DP really ran


def test_cli_dp_int8_allreduce(devices8, capsys):
    """--grad-allreduce int8 trains DP with the quantized wire collective;
    non-dp modes reject the flag instead of ignoring it."""
    import pytest
    metrics = _run(["--config", "resnet50_imagenet", "--model-preset", "tiny",
                    "--steps", "4", "--batch-size", "16", "--mesh", "dp=8",
                    "--grad-allreduce", "int8", "--log-every", "2"])
    assert np.isfinite(metrics["loss"])
    assert "only 1 device" not in capsys.readouterr().err
    # ZeRO-1 consumes it too (both wire phases quantized).
    metrics = _run(["--config", "bert_base_zero1", "--model-preset", "tiny",
                    "--steps", "2", "--batch-size", "16", "--mesh", "dp=8",
                    "--grad-allreduce", "int8", "--log-every", "2"])
    assert np.isfinite(metrics["loss"])
    with pytest.raises(SystemExit, match="grad-allreduce"):
        _run(["--config", "gpt2_124m", "--model-preset", "tiny",
              "--steps", "1", "--batch-size", "8", "--parallel", "sp",
              "--mesh", "dp=4,sp=2", "--grad-allreduce", "int8"])


def test_cli_label_smoothing():
    """--label-smoothing trains the CE configs; non-CE configs reject."""
    import pytest
    metrics = _run(["--config", "mlp_mnist", "--steps", "4",
                    "--batch-size", "64", "--label-smoothing", "0.1",
                    "--log-every", "2"])
    assert np.isfinite(metrics["loss"])
    with pytest.raises(SystemExit, match="label-smoothing"):
        _run(["--config", "gpt2_124m", "--model-preset", "tiny",
              "--steps", "1", "--batch-size", "4",
              "--label-smoothing", "0.1"])


def test_mesh_parsing():
    from nezha_tpu.cli.train import _parse_mesh
    assert _parse_mesh("dp=4,sp=2") == {"dp": 4, "sp": 2}
    assert _parse_mesh(None) is None


def test_cli_rejects_unusable_mesh_axes(devices8):
    """A mesh axis the chosen parallel mode cannot consume is an error, not
    silently ignored (VERDICT r2 missing #1)."""
    import pytest
    with pytest.raises(SystemExit, match="cannot use mesh axis"):
        _run(["--config", "resnet50_imagenet", "--model-preset", "tiny",
              "--steps", "1", "--batch-size", "8", "--parallel", "dp",
              "--mesh", "dp=4,tp=2"])
    with pytest.raises(SystemExit, match=r"needs mesh axis\(es\) \['tp'\]"):
        _run(["--config", "gpt2_124m", "--model-preset", "tiny",
              "--steps", "1", "--batch-size", "8", "--parallel", "gspmd",
              "--mesh", "dp=8"])
    with pytest.raises(SystemExit, match=r"needs mesh axis\(es\) \['dp'\]"):
        _run(["--config", "gpt2_124m", "--model-preset", "tiny",
              "--steps", "1", "--batch-size", "8", "--parallel", "sp",
              "--mesh", "sp=8"])
    with pytest.raises(SystemExit, match="no effect in single-device"):
        _run(["--config", "mlp_mnist", "--steps", "1", "--batch-size", "8",
              "--parallel", "single", "--mesh", "dp=8"])
    with pytest.raises(SystemExit, match="no tensor-parallel rule table"):
        _run(["--config", "resnet50_imagenet", "--model-preset", "tiny",
              "--steps", "1", "--batch-size", "8", "--parallel", "gspmd",
              "--mesh", "dp=2,tp=4"])


def _final_losses(config, steps, batch, extra):
    """Per-step losses from a metrics file for mode-vs-mode comparison."""
    import tempfile, pathlib, os
    with tempfile.TemporaryDirectory() as d:
        mf = os.path.join(d, "m.jsonl")
        _run(["--config", config, "--model-preset", "tiny",
              "--steps", str(steps), "--batch-size", str(batch),
              "--log-every", "1", "--metrics-file", mf] + extra)
        return [json.loads(l)["loss"]
                for l in pathlib.Path(mf).read_text().strip().splitlines()]


def test_cli_gspmd_matches_single(devices8):
    """--parallel gspmd (dp x tp, Megatron rules) launches from the CLI and
    matches single-device numerics step-for-step."""
    ref = _final_losses("gpt2_124m", 3, 8, ["--parallel", "single"])
    tp = _final_losses("gpt2_124m", 3, 8,
                       ["--parallel", "gspmd", "--mesh", "dp=2,tp=4"])
    np.testing.assert_allclose(tp, ref, rtol=1e-3)


def test_cli_pp_matches_single(devices8):
    """--parallel pp (dp x pp GPipe) launches from the CLI and matches
    single-device numerics step-for-step."""
    ref = _final_losses("gpt2_124m", 3, 8, ["--parallel", "single"])
    pp = _final_losses("gpt2_124m", 3, 8,
                       ["--parallel", "pp", "--mesh", "dp=2,pp=4",
                        "--microbatches", "2"])
    np.testing.assert_allclose(pp, ref, rtol=1e-3)


def test_cli_sp_matches_single(devices8):
    """--parallel sp (dp x sp ring attention) launches from the CLI and
    matches single-device numerics step-for-step."""
    ref = _final_losses("gpt2_124m", 3, 8, ["--parallel", "single"])
    sp = _final_losses("gpt2_124m", 3, 8,
                       ["--parallel", "sp", "--mesh", "dp=2,sp=4",
                        "--attn-impl", "ring"])
    np.testing.assert_allclose(sp, ref, rtol=1e-3)


def test_cli_moe_gpt2(devices8):
    """--moe-experts turns config 3 into a routed-MoE transformer and
    trains it data-parallel through the CLI."""
    m = _run(["--config", "gpt2_124m", "--model-preset", "tiny",
              "--moe-experts", "4", "--parallel", "dp", "--mesh", "dp=8",
              "--steps", "3", "--batch-size", "16", "--log-every", "1"])
    assert np.isfinite(m["loss"])


def test_cli_moe_ep_gspmd_matches_single(devices8):
    """--moe-experts with --parallel gspmd shards experts over an ep mesh
    axis (dp x tp x ep) from the CLI and matches single-device numerics."""
    ref = _final_losses("gpt2_124m", 3, 8,
                        ["--parallel", "single", "--moe-experts", "4"])
    ep = _final_losses("gpt2_124m", 3, 8,
                       ["--parallel", "gspmd", "--mesh", "dp=2,tp=2,ep=2",
                        "--moe-experts", "4"])
    np.testing.assert_allclose(ep, ref, rtol=1e-3)
    # An ep axis that does not divide the expert count is a friendly error,
    # not a raw device_put traceback (e.g. the dp=1,tp=1,ep=8 default mesh).
    import pytest
    with pytest.raises(SystemExit, match="not divisible by"):
        _run(["--config", "gpt2_124m", "--model-preset", "tiny",
              "--steps", "1", "--batch-size", "8", "--moe-experts", "4",
              "--parallel", "gspmd", "--mesh", "dp=1,tp=1,ep=8"])


def test_cli_sp_flash_matches_single(devices8):
    """--sp-flash on forces the flash-ring path from the CLI (interpret
    mode on CPU) and still matches single-device numerics; the flag is
    rejected where no sp kernels run."""
    import pytest
    ref = _final_losses("gpt2_124m", 3, 8, ["--parallel", "single"])
    spf = _final_losses("gpt2_124m", 3, 8,
                        ["--parallel", "sp", "--mesh", "dp=2,sp=4",
                         "--attn-impl", "ring", "--sp-flash", "on"])
    np.testing.assert_allclose(spf, ref, rtol=1e-3)
    with pytest.raises(SystemExit, match="does not consume it"):
        _run(["--config", "mlp_mnist", "--steps", "1", "--batch-size", "8",
              "--sp-flash", "off"])
    with pytest.raises(SystemExit, match="needs --parallel sp"):
        _run(["--config", "mlp_mnist", "--engine", "graph", "--steps", "1",
              "--batch-size", "8", "--sp-flash", "off"])


def test_cli_sp_one_chip_smoke(devices8):
    """The 1-chip sp smoke BENCH_NOTES prescribes: an EXPLICIT all-ones
    mesh (--mesh dp=1,sp=1) must RUN the sp mode on a single visible
    device (no degrade — it is the kernel/wiring smoke), with --sp-flash
    working in both positions."""
    import sys

    from conftest import run_worker_processes
    base = [sys.executable, "-m", "nezha_tpu.cli.train",
            "--config", "gpt2_124m", "--model-preset", "tiny",
            "--parallel", "sp", "--mesh", "dp=1,sp=1",
            "--platform", "cpu", "--steps", "2", "--batch-size", "4",
            "--log-every", "1"]
    results = run_worker_processes([base + ["--sp-flash", "on"],
                                    base + ["--sp-flash", "off"]])
    for rc, out, err in results:
        assert rc == 0, err[-3000:]
        assert "only 1 device" not in err  # ran sp, not the degrade
        assert json.loads(out.strip().splitlines()[-1])["final"]["loss"] > 0


def test_cli_sp_ulysses(devices8):
    """--attn-impl ulysses: the all-to-all sequence-parallel path from the
    CLI (heads 4 divisible by sp=4)."""
    m = _run(["--config", "gpt2_124m", "--model-preset", "tiny",
              "--parallel", "sp", "--mesh", "dp=2,sp=4",
              "--attn-impl", "ulysses", "--steps", "2", "--batch-size", "8",
              "--log-every", "1"])
    assert np.isfinite(m["loss"])


def test_cli_sp_long_context(devices8):
    """--seq-len stretches model + data together; with --parallel sp the
    sequence shards over sp, the long-context path of the brief — composed
    here with --remat (jax.checkpoint per block), the other long-context
    memory knob."""
    m = _run(["--config", "gpt2_124m", "--model-preset", "tiny",
              "--parallel", "sp", "--mesh", "dp=1,sp=8", "--seq-len", "256",
              "--attn-impl", "ring", "--remat", "--steps", "2",
              "--batch-size", "4", "--log-every", "1"])
    assert np.isfinite(m["loss"])


def test_cli_remat_matches_and_rejects(devices8):
    """--remat must not change training numerics, and configs/engines that
    cannot honor it reject instead of silently ignoring."""
    import pytest
    ref = _final_losses("gpt2_124m", 2, 8, ["--parallel", "single"])
    rm = _final_losses("gpt2_124m", 2, 8, ["--parallel", "single",
                                           "--remat"])
    np.testing.assert_allclose(rm, ref, rtol=1e-5)
    with pytest.raises(SystemExit, match="applies to gpt2_124m"):
        _run(["--config", "mlp_mnist", "--steps", "1", "--batch-size", "8",
              "--remat"])
    # pp honors --remat too (per-tick stage checkpointing): numerics match
    # the plain pp run exactly.
    pp_ref = _final_losses("gpt2_124m", 2, 8,
                           ["--parallel", "pp", "--mesh", "dp=2,pp=4",
                            "--microbatches", "2"])
    pp_rm = _final_losses("gpt2_124m", 2, 8,
                          ["--parallel", "pp", "--mesh", "dp=2,pp=4",
                           "--microbatches", "2", "--remat"])
    np.testing.assert_allclose(pp_rm, pp_ref, rtol=1e-5)


def test_cli_gspmd_sharded_checkpoint_resume(devices8, tmp_path):
    """GSPMD CLI checkpoints in the per-shard format and resumes from it."""
    ck = str(tmp_path / "ck")
    base = ["--config", "gpt2_124m", "--model-preset", "tiny",
            "--batch-size", "8", "--parallel", "gspmd",
            "--mesh", "dp=2,tp=4", "--ckpt-dir", ck, "--log-every", "1"]
    _run(base + ["--steps", "2"])
    import pathlib
    assert list(pathlib.Path(ck).glob("step_*.sharded"))
    m = _run(base + ["--steps", "1", "--eval", "--eval-batches", "2"])
    assert m["step"] == 3  # resumed at 2, trained 1 more
    assert any(k.startswith("eval_") for k in m)  # eval over sharded params


def test_cli_moe_ep_sharded_checkpoint_resume(devices8, tmp_path):
    """The ep-sharded expert layout round-trips the per-shard checkpoint
    format (reshard-on-restore must rebuild [E,.,.] leaves split over ep)."""
    ck = str(tmp_path / "ck")
    base = ["--config", "gpt2_124m", "--model-preset", "tiny",
            "--batch-size", "8", "--moe-experts", "4", "--parallel", "gspmd",
            "--mesh", "dp=2,tp=2,ep=2", "--ckpt-dir", ck, "--log-every", "1"]
    _run(base + ["--steps", "2"])
    import pathlib
    assert list(pathlib.Path(ck).glob("step_*.sharded"))
    m = _run(base + ["--steps", "1"])
    assert m["step"] == 3  # resumed at 2, trained 1 more
    assert np.isfinite(m["loss"])


def test_cli_pp_sharded_checkpoint_resume_and_eval(devices8, tmp_path):
    """Pipeline CLI checkpoints stacked stage slabs and resumes; eval runs
    off the merged (native-layout) params."""
    ck = str(tmp_path / "ck")
    base = ["--config", "gpt2_124m", "--model-preset", "tiny",
            "--batch-size", "8", "--parallel", "pp", "--mesh", "dp=2,pp=4",
            "--microbatches", "2", "--ckpt-dir", ck, "--log-every", "1"]
    _run(base + ["--steps", "2"])
    import pathlib
    assert list(pathlib.Path(ck).glob("step_*.sharded"))
    m = _run(base + ["--steps", "1", "--eval", "--eval-batches", "2"])
    assert m["step"] == 3
    assert any(k.startswith("eval_") for k in m)


def test_cli_graph_engine_trains_and_evals(tmp_path):
    """Config 1 through the Graph IR -> StableHLO -> Executor path; metrics
    improve and eval runs off the same params."""
    metrics = _run(["--config", "mlp_mnist", "--engine", "graph",
                    "--steps", "40", "--batch-size", "64",
                    "--log-every", "10", "--eval", "--eval-batches", "4",
                    "--metrics-file", str(tmp_path / "m.jsonl")])
    lines = [json.loads(l) for l in
             (tmp_path / "m.jsonl").read_text().strip().splitlines()]
    assert lines[-1]["loss"] < lines[0]["loss"]
    assert any(k.startswith("eval_") for k in metrics)


def test_cli_graph_engine_dp(devices8, tmp_path, capsys):
    """--engine graph --parallel dp: the IR's all_reduce path runs from the
    CLI over the 8-device mesh (no degrade warning, loss drops); invalid
    combos reject loudly."""
    import pytest
    metrics = _run(["--config", "mlp_mnist", "--engine", "graph",
                    "--parallel", "dp", "--steps", "30",
                    "--batch-size", "64", "--log-every", "10",
                    "--metrics-file", str(tmp_path / "m.jsonl")])
    assert np.isfinite(metrics["loss"])
    err = capsys.readouterr().err
    assert "running single-device" not in err  # the graph-dp degrade path
    assert "only 1 device" not in err
    lines = [json.loads(l) for l in
             (tmp_path / "m.jsonl").read_text().strip().splitlines()]
    assert lines[-1]["loss"] < lines[0]["loss"]
    with pytest.raises(SystemExit, match="not divisible by mesh axis"):
        _run(["--config", "mlp_mnist", "--engine", "graph", "--parallel",
              "dp", "--steps", "1", "--batch-size", "60"])
    # The conv path: graph-dp ResNet (tiny) trains over the mesh too.
    metrics = _run(["--config", "resnet50_imagenet", "--model-preset",
                    "tiny", "--engine", "graph", "--parallel", "dp",
                    "--steps", "4", "--batch-size", "16",
                    "--log-every", "2"])
    assert np.isfinite(metrics["loss"])
    # And the AdamW path (dp_adamw_update_graph): graph-dp GPT-2 + BERT
    # (BERT is the riskiest wiring: 5 feed arrays incl. a 4-d attn_mask
    # sharded over dp; per-shard masked-mean loss is the documented dp
    # semantics, so finite-and-runs is the contract here — exact dp math
    # is pinned by test_graph.py's GPT-2 parity).
    for config in ("gpt2_124m", "bert_base_zero1"):
        metrics = _run(["--config", config, "--model-preset", "tiny",
                        "--engine", "graph", "--parallel", "dp",
                        "--steps", "4", "--batch-size", "16",
                        "--log-every", "2"])
        assert np.isfinite(metrics["loss"]), config
    with pytest.raises(SystemExit, match="supports --parallel dp"):
        _run(["--config", "mlp_mnist", "--engine", "graph", "--parallel",
              "pp", "--steps", "1", "--batch-size", "8"])
    with pytest.raises(SystemExit, match="mesh axis 'dp'"):
        _run(["--config", "mlp_mnist", "--engine", "graph", "--parallel",
              "dp", "--mesh", "dp=4,tp=2", "--steps", "1",
              "--batch-size", "8"])


def test_cli_graph_engine_zero1(devices8, tmp_path, capsys):
    """--engine graph --parallel zero1: the IR's reduce_scatter/all_gather
    path trains over the 8-device mesh from the CLI (loss drops, no
    degrade), resumes from its flat-chunk checkpoint, and invalid combos
    reject loudly."""
    import pytest
    ck = str(tmp_path / "ck")
    metrics = _run(["--config", "mlp_mnist", "--engine", "graph",
                    "--parallel", "zero1", "--steps", "30",
                    "--batch-size", "64", "--log-every", "10",
                    "--ckpt-dir", ck, "--eval", "--eval-batches", "4",
                    "--metrics-file", str(tmp_path / "m.jsonl")])
    assert np.isfinite(metrics["loss"])
    # Eval runs off params materialized from the flat sharded state.
    assert any(k.startswith("eval_") for k in metrics)
    assert "running single-device" not in capsys.readouterr().err
    lines = [json.loads(l) for l in
             (tmp_path / "m.jsonl").read_text().strip().splitlines()]
    assert lines[-1]["loss"] < lines[0]["loss"]
    m = _run(["--config", "mlp_mnist", "--engine", "graph", "--parallel",
              "zero1", "--steps", "5", "--batch-size", "64",
              "--ckpt-dir", ck, "--log-every", "5"])
    assert m["step"] == 35  # resumed at 30, trained 5 more
    with pytest.raises(SystemExit, match="graph-engine zero1 is authored"):
        _run(["--config", "gpt2_124m", "--model-preset", "tiny", "--engine",
              "graph", "--parallel", "zero1", "--steps", "1",
              "--batch-size", "8"])


def test_cli_graph_engine_resnet(tmp_path):
    """Config 2 through the Graph IR engine (tiny preset): runs from the
    CLI with finite loss (descent is asserted on a fixed batch in
    test_graph.py); --eval is rejected (no running BN stats)."""
    import pytest
    _run(["--config", "resnet50_imagenet", "--model-preset",
                    "tiny", "--engine", "graph", "--steps", "6",
                    "--batch-size", "8", "--log-every", "2",
                    "--metrics-file", str(tmp_path / "m.jsonl")])
    # Rotating random-label batches at fixed lr don't descend this fast;
    # descent is asserted on a fixed batch in test_graph.py. Here: the IR
    # program runs through the CLI and stays finite.
    lines = [json.loads(l) for l in
             (tmp_path / "m.jsonl").read_text().strip().splitlines()]
    assert all(np.isfinite(l["loss"]) for l in lines)
    with pytest.raises(SystemExit, match="running BN stats"):
        _run(["--config", "resnet50_imagenet", "--model-preset", "tiny",
              "--engine", "graph", "--steps", "1", "--batch-size", "8",
              "--eval"])


def test_cli_graph_engine_bert(tmp_path):
    """Config 4's model through the Graph IR engine: the IR-authored BERT
    encoder + AdamW graphs train from the CLI and the loss drops."""
    metrics = _run(["--config", "bert_base_zero1", "--model-preset", "tiny",
                    "--engine", "graph", "--steps", "20",
                    "--batch-size", "8", "--log-every", "5",
                    "--metrics-file", str(tmp_path / "m.jsonl")])
    lines = [json.loads(l) for l in
             (tmp_path / "m.jsonl").read_text().strip().splitlines()]
    assert lines[-1]["loss"] < lines[0]["loss"]


def test_cli_graph_engine_gpt2(tmp_path):
    """Config 3 through the Graph IR engine: the IR-authored transformer +
    AdamW update graphs train from the CLI and the loss drops."""
    metrics = _run(["--config", "gpt2_124m", "--model-preset", "tiny",
                    "--engine", "graph", "--steps", "30",
                    "--batch-size", "8", "--log-every", "10",
                    "--metrics-file", str(tmp_path / "m.jsonl")])
    lines = [json.loads(l) for l in
             (tmp_path / "m.jsonl").read_text().strip().splitlines()]
    assert lines[-1]["loss"] < lines[0]["loss"]


def test_cli_degrade_warning_is_loud(monkeypatch, capsys):
    """A multi-device config on a 1-device host must warn, not silently
    shrink to 1/Nth scale (VERDICT round 1, weak #5)."""
    import jax
    one = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: one)
    _run(["--config", "resnet50_imagenet", "--steps", "0",
          "--batch-size", "8"])
    err = capsys.readouterr().err
    assert "WARNING" in err and "only 1 device" in err


def test_cli_explicit_multi_device_request_on_one_device_is_an_error(
        monkeypatch):
    """Only a config's DEFAULT mode degrades (with the loud warning
    above). An explicit ``--parallel``/``--mesh`` that one visible device
    cannot meet exits instead of quietly training at 1/Nth scale — in
    both engines."""
    import jax
    import pytest
    one = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: one)
    base = ["--config", "gpt2_124m", "--model-preset", "tiny",
            "--steps", "1", "--batch-size", "8"]
    for extra in (["--parallel", "dp", "--mesh", "dp=4"],
                  ["--parallel", "zero1"],
                  ["--mesh", "dp=4"],
                  ["--parallel", "dp", "--engine", "graph"]):
        with pytest.raises(SystemExit, match="1 visible device"):
            _run(base + extra)


def test_cli_reports_where_the_arrays_live(devices8):
    """The final metrics carry placement facts read off the arrays
    themselves (chip_smoke.py asserts on them): dp shards the batch and
    replicates the state; ZeRO-1 also splits the optimizer state."""
    base = ["--config", "gpt2_124m", "--model-preset", "tiny",
            "--steps", "1", "--batch-size", "8", "--log-every", "1"]
    dp = _run(base + ["--parallel", "dp", "--mesh", "dp=4"])
    assert (dp["batch_devices"], dp["state_devices"],
            dp["state_split_devices"]) == (4, 4, 0)
    zero1 = _run(base + ["--parallel", "zero1", "--mesh", "dp=4"])
    assert (zero1["batch_devices"], zero1["state_split_devices"]) == (4, 4)
    single = _run(base + ["--parallel", "single"])
    assert (single["batch_devices"], single["state_devices"],
            single["state_split_devices"]) == (1, 1, 0)


def test_cli_trains_rn50_from_image_records(devices8, tmp_path):
    """E2E: write NZR1 records, train ResNet-50 DP through the CLI from
    them (the real-data input path of benchmark config 2)."""
    from nezha_tpu.data.native import write_image_records
    from nezha_tpu.runtime.native import native_available
    if not native_available():
        import pytest
        pytest.skip("native runtime not available")
    rng = np.random.RandomState(0)
    write_image_records(
        tmp_path / "train.nzr",
        rng.randint(0, 256, (64, 40, 40, 3), dtype=np.uint8).astype(np.uint8),
        rng.randint(0, 100, 64))  # tiny preset has 100 classes
    # 20 val records with batch 8 forces the divisor adjustment (-> 5) and
    # full coverage; count pins the val.nzr path (synthetic fallback would
    # differ).
    write_image_records(
        tmp_path / "val.nzr",
        rng.randint(0, 256, (20, 40, 40, 3), dtype=np.uint8),
        rng.randint(0, 100, 20))
    # tiny preset: the test pins the records->loader->train->eval plumbing,
    # not model depth — the full 50-layer compile added ~45s of nothing.
    metrics = _run(["--config", "resnet50_imagenet", "--model-preset", "tiny",
                    "--steps", "2", "--batch-size", "8", "--log-every", "1",
                    "--data-dir", str(tmp_path), "--crop", "32",
                    "--eval"])
    assert np.isfinite(metrics["loss"])
    assert metrics["eval_count"] == 20  # every val record, exactly once


def test_cli_zero1_sharded_checkpoint_resume(devices8, tmp_path):
    """ZeRO-1 CLI runs checkpoint in the per-shard format and resume from it."""
    ck = str(tmp_path / "ck")
    _run(["--config", "bert_base_zero1", "--model-preset", "tiny",
          "--steps", "2", "--batch-size", "8",
          "--ckpt-dir", ck, "--log-every", "1"])
    import pathlib
    assert list(pathlib.Path(ck).glob("step_*.sharded"))
    m = _run(["--config", "bert_base_zero1", "--model-preset", "tiny",
              "--steps", "1",
              "--batch-size", "8", "--ckpt-dir", ck, "--log-every", "1"])
    assert m["step"] == 3  # resumed at 2, trained 1 more


def test_cli_ckpt_keep_retention(devices8, tmp_path):
    """--ckpt-keep N prunes old checkpoints in both formats (npz via the
    Trainer default path, per-shard via the wrapped async save_fn)."""
    import pathlib
    ck = str(tmp_path / "npz")
    _run(["--config", "mlp_mnist", "--steps", "6", "--batch-size", "16",
          "--ckpt-dir", ck, "--ckpt-every", "2", "--ckpt-keep", "1"])
    names = sorted(p.name for p in pathlib.Path(ck).glob("step_*.npz"))
    assert names == ["step_00000006.npz"]  # 2 and 4 pruned, final kept

    ck = str(tmp_path / "sharded")
    _run(["--config", "bert_base_zero1", "--model-preset", "tiny",
          "--steps", "4", "--batch-size", "16", "--mesh", "dp=8",
          "--ckpt-dir", ck, "--ckpt-every", "1", "--ckpt-keep", "2"])
    names = sorted(p.name for p in pathlib.Path(ck).glob("step_*.sharded"))
    assert names == ["step_00000003.sharded", "step_00000004.sharded"]


def test_cli_failure_detection_checkpoints_then_raises(tmp_path):
    """Kill a peer rank mid-run: the CLI loop (via Trainer) must detect the
    failure, checkpoint, and raise — the elastic machinery live from the
    CLI (VERDICT round 1, weak #6)."""
    import threading

    import pytest

    from nezha_tpu.runtime.native import native_available
    if not native_available():
        pytest.skip("native runtime not available")
    from nezha_tpu import dist
    from nezha_tpu.cli.train import build_parser, run

    with dist.Coordinator(world_size=2, heartbeat_timeout_s=1.0) as coord:
        g1 = dist.join("127.0.0.1", coord.port, rank_hint=1,
                       heartbeat_interval_s=0.1)
        killer = threading.Timer(1.0, g1.close)  # abrupt: no LEAVE
        killer.start()
        ck = str(tmp_path / "ck")
        args = build_parser().parse_args([
            "--config", "mlp_mnist", "--steps", "100000",
            "--batch-size", "16", "--log-every", "100000",
            "--failure-check-every", "5", "--ckpt-dir", ck,
            "--coordinator", f"127.0.0.1:{coord.port}",
            "--no-jax-distributed"])
        with pytest.raises(RuntimeError, match=r"peer rank\(s\) \[1\]"):
            run(args)
        import pathlib
        assert list(pathlib.Path(ck).glob("step_*.npz"))  # saved before raise


def test_cli_elastic_rejoin_continues(tmp_path):
    """The FULL elastic cycle (VERDICT r3 weak #7), automatically: rank 1
    is SIGKILLed mid-run; rank 0 detects it, commits a rescue checkpoint,
    and (--on-failure rejoin) WAITS; rank 1 is relaunched with --rank-hint
    1, resumes from the rescue checkpoint; rank 0 sees the world heal,
    reloads the same checkpoint, and training CONTINUES in-process to
    completion on both ranks."""
    import pathlib

    import pytest

    from nezha_tpu.runtime.native import native_available
    if not native_available():
        pytest.skip("native runtime not available")

    from conftest import TwoRankElastic
    cluster = TwoRankElastic(tmp_path)
    try:
        r0 = cluster.launch("r0", ["--steps", "2000", "--serve-coordinator",
                                   "--world-size", "2"])
        r1 = cluster.launch("r1", ["--steps", "2000", "--rank-hint", "1"])
        # Kill rank 1 only once it is demonstrably mid-training (has
        # logged a metrics line), so the failure lands between steps.
        cluster.wait_for("r1", '"step"', r1)
        r1.kill()
        r1.wait()

        # Rank 0 must detect, checkpoint, and announce the wait.
        cluster.wait_for("r0", "waiting for rejoin", r0)
        assert list(pathlib.Path(cluster.ck).glob("step_*.npz"))  # rescue

        # Relaunch the dead rank into its old slot; both must finish.
        r1b = cluster.launch("r1b", ["--steps", "200", "--rank-hint", "1"])
        assert r0.wait(timeout=240) == 0, cluster.err("r0")
        assert r1b.wait(timeout=240) == 0, cluster.err("r1b")
    finally:
        cluster.cleanup()

    e0 = cluster.err("r0")
    assert "world healed; resumed from step" in e0
    assert "resumed from step" in cluster.err("r1b")  # restored rescue ckpt
    # The loss stream continued: rank 0's logged steps are strictly
    # increasing through the failure and reach the full horizon.
    steps = [json.loads(l)["step"] for l in e0.splitlines()
             if l.startswith("{") and '"step"' in l]
    assert steps[-1] == 2000
    assert all(a < b for a, b in zip(steps, steps[1:]))  # no re-logged steps


def test_cli_rejoin_timeout_gives_up_loudly(tmp_path):
    """--on-failure rejoin with NO replacement: the survivor must not wait
    forever — after --rejoin-timeout it raises (checkpoint already
    committed), exiting nonzero with the timeout message."""
    import pathlib

    import pytest

    from nezha_tpu.runtime.native import native_available
    if not native_available():
        pytest.skip("native runtime not available")

    from conftest import TwoRankElastic
    cluster = TwoRankElastic(tmp_path, rejoin_timeout="3")
    try:
        r0 = cluster.launch("r0", ["--steps", "2000", "--serve-coordinator",
                                   "--world-size", "2"])
        r1 = cluster.launch("r1", ["--steps", "2000", "--rank-hint", "1"])
        cluster.wait_for("r1", '"step"', r1)
        r1.kill()
        r1.wait()
        assert r0.wait(timeout=180) != 0  # gave up, loudly
    finally:
        cluster.cleanup()
    assert "no replacement rejoined within 3s" in cluster.err("r0")
    assert list(pathlib.Path(cluster.ck).glob("step_*.npz"))  # rescue saved


def test_cli_on_failure_rejoin_validation():
    """--on-failure rejoin rejects combos its recovery path cannot honor."""
    import pytest
    with pytest.raises(SystemExit, match="needs --coordinator"):
        _run(["--config", "mlp_mnist", "--steps", "1", "--batch-size", "8",
              "--on-failure", "rejoin"])
    with pytest.raises(SystemExit, match="needs --ckpt-dir"):
        _run(["--config", "mlp_mnist", "--steps", "1", "--batch-size", "8",
              "--on-failure", "rejoin", "--coordinator", "127.0.0.1:1",
              "--no-jax-distributed"])
    with pytest.raises(SystemExit, match="no-jax-distributed"):
        _run(["--config", "mlp_mnist", "--steps", "1", "--batch-size", "8",
              "--on-failure", "rejoin", "--coordinator", "127.0.0.1:1",
              "--ckpt-dir", "/tmp/x"])


def test_cli_with_coordinator(tmp_path):
    """Single-process world through the real coordinator dial-in path."""
    from nezha_tpu.runtime.native import native_available
    if not native_available():
        import pytest
        pytest.skip("native runtime not available")
    from nezha_tpu import dist
    from nezha_tpu.cli.train import build_parser, run

    with dist.Coordinator(world_size=1) as coord:
        args = build_parser().parse_args([
            "--config", "mlp_mnist", "--steps", "4", "--batch-size", "16",
            "--platform", "cpu", "--log-every", "2",
            "--coordinator", f"127.0.0.1:{coord.port}",
        ])
        last = run(args)
    assert "loss" in last


def test_cli_two_process_dp_sharded_data(devices8, tmp_path):
    """The pod launch path end-to-end on one box: two OS processes
    rendezvous via --coordinator, enter jax.distributed, shard the record
    file by rank (disjoint halves of each epoch), assemble global batches
    from process-local rows, and train DP over the 2-device global mesh —
    replicated metrics must agree bit-for-bit across ranks."""
    import socket
    import sys

    from conftest import run_worker_processes
    from nezha_tpu.data.native import write_image_records
    from nezha_tpu.runtime.native import native_available
    if not native_available():
        import pytest
        pytest.skip("native runtime not available")

    rng = np.random.RandomState(0)
    write_image_records(
        tmp_path / "train.nzr",
        rng.randint(0, 256, (32, 36, 36, 3), dtype=np.uint8),
        rng.randint(0, 100, 32))
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    base = [sys.executable, "-m", "nezha_tpu.cli.train",
            "--config", "resnet50_imagenet", "--model-preset", "tiny",
            "--steps", "2", "--batch-size", "8", "--mesh", "dp=2",
            "--crop", "32", "--data-dir", str(tmp_path),
            "--platform", "cpu", "--log-every", "1",
            "--coordinator", f"127.0.0.1:{port}"]
    results = run_worker_processes([
        base + (["--serve-coordinator", "--world-size", "2"] if i == 0
                else [])
        for i in range(2)])
    for rc, _, err in results:
        assert rc == 0, err[-3000:]
    shards = {s for _, _, err in results
              for s in ("(shard 0/2)", "(shard 1/2)") if s in err}
    assert shards == {"(shard 0/2)", "(shard 1/2)"}, \
        [e[-500:] for _, _, e in results]
    finals = [json.loads(out.strip().splitlines()[-1])["final"]["loss"]
              for _, out, _ in results]
    assert np.isfinite(finals[0])
    assert finals[0] == finals[1]  # replicated metrics agree across ranks


def test_cli_two_process_graph_dp(devices8):
    """Graph-engine dp across two OS processes: the IR all_reduce path
    composes with the multi-process launch (process-local rows assembled
    into the global batch) — replicated metrics must agree across ranks."""
    import socket
    import sys

    from conftest import run_worker_processes
    from nezha_tpu.runtime.native import native_available
    if not native_available():
        import pytest
        pytest.skip("native runtime not available")

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    base = [sys.executable, "-m", "nezha_tpu.cli.train",
            "--config", "mlp_mnist", "--engine", "graph",
            "--parallel", "dp", "--steps", "3", "--batch-size", "16",
            "--platform", "cpu", "--log-every", "1",
            "--coordinator", f"127.0.0.1:{port}"]
    results = run_worker_processes([
        base + (["--serve-coordinator", "--world-size", "2"] if i == 0
                else [])
        for i in range(2)])
    for rc, _, err in results:
        assert rc == 0, err[-3000:]
        # jax.distributed forms the 2-device global world — the degrade
        # path must NOT fire, or the IR all_reduce never runs.
        assert "running single-device" not in err, err[-2000:]
    finals = [json.loads(out.strip().splitlines()[-1])["final"]["loss"]
              for _, out, _ in results]
    assert np.isfinite(finals[0])
    assert finals[0] == finals[1]  # replicated metrics agree across ranks


def test_cli_dropout_pipelines(devices8):
    """--dropout works in pp mode (per-layer/microbatch keys through the
    GPipe schedule) and is rejected where it cannot apply."""
    import pytest
    m = _run(["--config", "gpt2_124m", "--model-preset", "tiny",
              "--parallel", "pp", "--mesh", "dp=2,pp=4",
              "--microbatches", "2", "--dropout", "0.2", "--steps", "2",
              "--batch-size", "8", "--log-every", "1"])
    assert np.isfinite(m["loss"])
    with pytest.raises(SystemExit, match="applies to gpt2_124m"):
        _run(["--config", "mlp_mnist", "--steps", "1", "--batch-size", "8",
              "--dropout", "0.1"])
    with pytest.raises(SystemExit, match="no.*dropout path|dropout path"):
        _run(["--config", "gpt2_124m", "--model-preset", "tiny",
              "--engine", "graph", "--steps", "1", "--batch-size", "8",
              "--dropout", "0.1"])
    with pytest.raises(SystemExit, match=r"in \[0, 1\)"):
        _run(["--config", "gpt2_124m", "--model-preset", "tiny",
              "--steps", "1", "--batch-size", "8", "--dropout", "1.5"])


def test_cli_grad_accum(devices8):
    """--grad-accum N holds updates for N micro-steps: params change only
    every Nth step, and the graph engine rejects the wrapper."""
    import pytest
    losses = _final_losses("gpt2_124m", 4, 8,
                           ["--parallel", "single", "--grad-accum", "2"])
    # Steps 1 and 2 see the same params (update flushes at step 2's end):
    # identical batch stream per step is not guaranteed, so instead pin the
    # mechanism by comparing against no-accum: first-step losses match
    # (same init params), later steps diverge.
    plain = _final_losses("gpt2_124m", 4, 8, ["--parallel", "single"])
    np.testing.assert_allclose(losses[0], plain[0], rtol=1e-6)
    assert not np.allclose(losses[-1], plain[-1], rtol=1e-6)
    with pytest.raises(SystemExit, match="graph engine"):
        _run(["--config", "gpt2_124m", "--model-preset", "tiny",
              "--engine", "graph", "--steps", "1", "--batch-size", "8",
              "--grad-accum", "2"])
    with pytest.raises(SystemExit, match="grad-accum must be"):
        _run(["--config", "mlp_mnist", "--steps", "1", "--batch-size", "8",
              "--grad-accum", "0"])


def test_cli_clip_norm(devices8):
    """--clip-norm bounds the update: a near-zero clip freezes training
    (losses stay ~constant) where the unclipped run moves; invalid values
    reject."""
    import pytest
    # mlp_mnist trains with momentum SGD, whose update scales with the
    # gradient (AdamW's does not — it normalizes scale away), so a
    # near-zero clip visibly freezes it.
    clipped = _final_losses("mlp_mnist", 8, 64,
                            ["--parallel", "single", "--clip-norm", "1e-9"])
    plain = _final_losses("mlp_mnist", 8, 64, ["--parallel", "single"])
    # Frozen params still see per-batch loss noise (~0.05); the real run's
    # drop must dwarf the clipped run's drift.
    assert plain[0] - plain[-1] > 5 * abs(clipped[0] - clipped[-1]), \
        (plain, clipped)
    with pytest.raises(SystemExit, match="clip-norm must be"):
        _run(["--config", "mlp_mnist", "--steps", "1", "--batch-size", "8",
              "--clip-norm", "-1"])
    # The graph engine authors the clip in the IR (clip_scale_graph): a
    # near-zero clip freezes it exactly like the module engine.
    gclip = _final_losses("mlp_mnist", 8, 64,
                          ["--engine", "graph", "--clip-norm", "1e-9"])
    gplain = _final_losses("mlp_mnist", 8, 64, ["--engine", "graph"])
    assert gplain[0] - gplain[-1] > 5 * abs(gclip[0] - gclip[-1]), \
        (gplain, gclip)
    # Graph-dp cannot clip (the all_reduce lives inside the update graphs).
    with pytest.raises(SystemExit, match="REDUCED gradients"):
        _run(["--config", "mlp_mnist", "--steps", "1", "--batch-size", "8",
              "--engine", "graph", "--parallel", "dp", "--clip-norm", "1.0"])


def test_cli_ckpt_keep_rejects_nonpositive():
    import pytest
    with pytest.raises(SystemExit, match="ckpt-keep must be >= 1"):
        _run(["--config", "mlp_mnist", "--steps", "1", "--batch-size", "8",
              "--ckpt-keep", "0"])


def test_cli_optimizer_override(devices8):
    """--optimizer swaps the config's optimizer (with --lr + warmup/cosine);
    invalid combinations reject loudly."""
    import pytest
    m = _run(["--config", "resnet50_imagenet", "--model-preset", "tiny",
              "--steps", "3", "--batch-size", "16", "--mesh", "dp=8",
              "--optimizer", "lars", "--lr", "0.5", "--log-every", "1"])
    assert np.isfinite(m["loss"])
    m = _run(["--config", "gpt2_124m", "--model-preset", "tiny",
              "--steps", "2", "--batch-size", "8", "--parallel", "single",
              "--optimizer", "adafactor", "--lr", "1e-2"])
    assert np.isfinite(m["loss"])
    with pytest.raises(SystemExit, match="needs --lr"):
        _run(["--config", "mlp_mnist", "--steps", "1", "--batch-size", "8",
              "--optimizer", "adamw"])
    with pytest.raises(SystemExit, match="only applies with --optimizer"):
        _run(["--config", "mlp_mnist", "--steps", "1", "--batch-size", "8",
              "--lr", "0.1"])
    with pytest.raises(SystemExit, match="layerwise trust ratios"):
        _run(["--config", "bert_base_zero1", "--model-preset", "tiny",
              "--steps", "1", "--batch-size", "8", "--optimizer", "lamb",
              "--lr", "1e-3"])
    with pytest.raises(SystemExit, match="graph engine"):
        _run(["--config", "mlp_mnist", "--steps", "1", "--batch-size", "8",
              "--engine", "graph", "--optimizer", "adamw", "--lr", "1e-3"])


def test_cli_lr_rejects_nonpositive():
    import pytest
    with pytest.raises(SystemExit, match="lr must be"):
        _run(["--config", "mlp_mnist", "--steps", "1", "--batch-size", "8",
              "--optimizer", "sgd", "--lr", "nan"])


def test_cli_eval_every(devices8, tmp_path):
    """--eval-every N interleaves full eval passes with training: the
    metrics stream carries eval_* entries at each boundary plus the final
    pass, and eval accuracy reflects the current (training) params."""
    import pytest
    mf = tmp_path / "m.jsonl"
    m = _run(["--config", "gpt2_124m", "--model-preset", "tiny",
              "--parallel", "single", "--steps", "4", "--batch-size", "8",
              "--eval-every", "2", "--eval-batches", "2",
              "--log-every", "4", "--metrics-file", str(mf)])
    assert any(k.startswith("eval_") for k in m)  # final pass in result
    recs = [json.loads(l) for l in mf.read_text().strip().splitlines()]
    evals = [r for r in recs if any(k.startswith("eval_") for k in r)]
    assert len(evals) == 1 and evals[0]["step"] == 2  # midpoint pass logged
    with pytest.raises(SystemExit, match="eval-every must be"):
        _run(["--config", "mlp_mnist", "--steps", "1", "--batch-size", "8",
              "--eval-every", "0"])


def test_cli_knob_composition(devices8, tmp_path):
    """The whole knob stack composes in one run: gspmd (dp x tp) + remat +
    dropout + global clip + grad accumulation + periodic eval + retention,
    end to end with finite losses."""
    m = _run(["--config", "gpt2_124m", "--model-preset", "tiny",
              "--parallel", "gspmd", "--mesh", "dp=2,tp=4",
              "--remat", "--dropout", "0.1", "--clip-norm", "1.0",
              "--grad-accum", "2", "--eval-every", "2",
              "--eval-batches", "2", "--steps", "4", "--batch-size", "8",
              "--ckpt-dir", str(tmp_path), "--ckpt-every", "2",
              "--ckpt-keep", "1", "--log-every", "2"])
    assert np.isfinite(m["loss"])
    assert any(k.startswith("eval_") for k in m)
    kept = list(tmp_path.glob("step_*.sharded"))
    assert len(kept) == 1  # retention pruned to the newest


def test_cli_bert_real_token_data(devices8, tmp_path):
    """Config 4 on real data: packed tokens -> native TokenLoader ->
    dynamic MLM masking -> ZeRO-1 training (the same .tokens.u16 format
    GPT-2 consumes)."""
    import pytest
    try:
        from nezha_tpu.data.native import load_library
        load_library()
    except Exception:
        pytest.skip("native runtime not available")
    rng = np.random.RandomState(0)
    (tmp_path / "train.tokens.u16").write_bytes(
        rng.randint(0, 512, 8192).astype(np.uint16).tobytes())
    metrics = _run(["--config", "bert_base_zero1", "--model-preset", "tiny",
                    "--steps", "2", "--batch-size", "8", "--log-every", "1",
                    "--data-dir", str(tmp_path)])
    assert np.isfinite(metrics["loss"])


def test_cli_scan_layers(devices8):
    """--scan-layers trains the stacked trunk (single + dp), and the
    incompatible engines/modes reject loudly."""
    import pytest
    metrics = _run(["--config", "gpt2_124m", "--model-preset", "tiny",
                    "--steps", "2", "--batch-size", "2", "--scan-layers",
                    "--parallel", "single", "--log-every", "1"])
    assert np.isfinite(metrics["loss"])
    metrics = _run(["--config", "gpt2_124m", "--model-preset", "tiny",
                    "--steps", "2", "--batch-size", "8", "--scan-layers",
                    "--mesh", "dp=8", "--log-every", "1"])
    assert np.isfinite(metrics["loss"])
    with pytest.raises(SystemExit, match="scan-layers"):
        _run(["--config", "resnet50_imagenet", "--model-preset", "tiny",
              "--steps", "1", "--batch-size", "2", "--scan-layers"])
    with pytest.raises(SystemExit, match="scan-layers"):
        _run(["--config", "gpt2_124m", "--model-preset", "tiny",
              "--steps", "1", "--batch-size", "4", "--scan-layers",
              "--parallel", "pp", "--mesh", "dp=4,pp=2",
              "--microbatches", "2"])
    with pytest.raises(SystemExit, match="graph"):
        _run(["--config", "gpt2_124m", "--model-preset", "tiny",
              "--steps", "1", "--batch-size", "2", "--scan-layers",
              "--engine", "graph"])


def test_cli_bert_byte_corpus_requires_explicit_mask_token(tmp_path):
    """A byte-packed corpus (all sampled ids < 256) with a defaulted MLM
    mask token is refused — the default 103 is a real byte value there
    (ADVICE r4); an explicit --mlm-mask-token proceeds."""
    import pytest
    try:
        from nezha_tpu.data.native import load_library
        load_library()
    except Exception:
        pytest.skip("native runtime not available")
    rng = np.random.RandomState(0)
    (tmp_path / "train.tokens.u16").write_bytes(
        rng.randint(0, 256, 8192).astype(np.uint16).tobytes())
    with pytest.raises(SystemExit, match="byte-packed"):
        _run(["--config", "bert_base_zero1", "--model-preset", "tiny",
              "--steps", "1", "--batch-size", "8",
              "--data-dir", str(tmp_path)])
    metrics = _run(["--config", "bert_base_zero1", "--model-preset", "tiny",
                    "--steps", "2", "--batch-size", "8", "--log-every", "1",
                    "--mlm-mask-token", "300",
                    "--data-dir", str(tmp_path)])
    assert np.isfinite(metrics["loss"])


def test_cli_bert_scan_layers(devices8):
    """--scan-layers trains BERT's stacked encoder under zero1."""
    metrics = _run(["--config", "bert_base_zero1", "--model-preset", "tiny",
                    "--steps", "2", "--batch-size", "16", "--scan-layers",
                    "--mesh", "dp=8", "--log-every", "1"])
    assert np.isfinite(metrics["loss"])


def test_cli_scan_layers_full_preset_builders():
    """Both full-preset builders accept the scan_layers override (the
    tiny-only CLI tests would miss a zero-arg full-preset lambda)."""
    from nezha_tpu.cli.train import _configs
    cfgs = _configs()
    for name in ("gpt2_124m", "bert_base_zero1"):
        m = cfgs[name].build_model(scan_layers=True)
        assert m.cfg.scan_layers


def test_cli_resnet_remat(devices8):
    """--remat now covers the image configs (per-bottleneck checkpoint)."""
    metrics = _run(["--config", "resnet50_imagenet", "--model-preset", "tiny",
                    "--steps", "2", "--batch-size", "16", "--remat",
                    "--mesh", "dp=8", "--log-every", "1"])
    assert np.isfinite(metrics["loss"])


def test_cli_scan_layers_gspmd_matches_single(devices8):
    """--scan-layers composes with GSPMD tensor parallel: the stacked
    trunk shards via the SAME Megatron rule table (leading layer dim
    prepended) and matches single-device numerics step-for-step."""
    ref = _final_losses("gpt2_124m", 3, 8,
                        ["--parallel", "single", "--scan-layers"])
    tp = _final_losses("gpt2_124m", 3, 8,
                       ["--parallel", "gspmd", "--mesh", "dp=2,tp=4",
                        "--scan-layers"])
    np.testing.assert_allclose(tp, ref, rtol=1e-3)
    # And the unrolled single matches the scan single (layout-invariant).
    ref_unrolled = _final_losses("gpt2_124m", 3, 8, ["--parallel", "single"])
    np.testing.assert_allclose(ref, ref_unrolled, rtol=1e-4)


def test_cli_wd_exclude_1d(devices8):
    """--wd-exclude-1d masks weight decay off 1-D leaves; invalid combos
    reject loudly."""
    import pytest
    m = _run(["--config", "gpt2_124m", "--model-preset", "tiny",
              "--steps", "2", "--batch-size", "8", "--wd-exclude-1d",
              "--mesh", "dp=8", "--log-every", "1"])
    assert np.isfinite(m["loss"])
    # Composes with the stacked trunk (the mask is layout-aware).
    m = _run(["--config", "gpt2_124m", "--model-preset", "tiny",
              "--steps", "2", "--batch-size", "8", "--wd-exclude-1d",
              "--scan-layers", "--mesh", "dp=8", "--log-every", "1"])
    assert np.isfinite(m["loss"])
    with pytest.raises(SystemExit, match="wd-exclude-1d"):
        _run(["--config", "bert_base_zero1", "--model-preset", "tiny",
              "--steps", "1", "--batch-size", "8", "--wd-exclude-1d",
              "--parallel", "zero1", "--mesh", "dp=8"])
    with pytest.raises(SystemExit, match="wd-exclude-1d"):
        _run(["--config", "mlp_mnist", "--steps", "1", "--batch-size", "8",
              "--wd-exclude-1d"])
    with pytest.raises(SystemExit, match="graph"):
        _run(["--config", "gpt2_124m", "--model-preset", "tiny",
              "--steps", "1", "--batch-size", "4", "--wd-exclude-1d",
              "--engine", "graph"])


def test_cli_wd_exclude_1d_changes_decay_not_masked_leaves():
    """The mask really turns decay off for 1-D leaves: with lr frozen and
    zero gradients, decayed leaves shrink and masked leaves don't."""
    import jax
    from nezha_tpu import optim
    from nezha_tpu.models.gpt2 import GPT2, GPT2Config

    model = GPT2(GPT2Config(vocab_size=64, max_positions=16, num_layers=1,
                            num_heads=2, hidden_size=16))
    params = model.init(jax.random.PRNGKey(0))["params"]
    opt = optim.adamw(1e-2, weight_decay=0.5,
                      mask=optim.matrix_decay_mask)
    state = opt.init(params)
    zeros = jax.tree_util.tree_map(lambda p: np.zeros_like(p), params)
    upd, _ = opt.update(zeros, state, params)
    flat = dict(jax.tree_util.tree_leaves_with_path(upd))
    for path, u in flat.items():
        nd = np.asarray(u).ndim
        if nd >= 2:
            assert np.any(np.asarray(u) != 0.0), path  # decay applied
        else:
            np.testing.assert_array_equal(np.asarray(u), 0.0, err_msg=str(path))


def test_cli_gpt2_rejects_out_of_vocab_corpus(tmp_path):
    """Token files with ids >= the model vocab are refused up front (they
    would NaN the CE via out-of-range target gathers, silently)."""
    import pytest
    (tmp_path / "train.tokens.u16").write_bytes(
        np.random.RandomState(0).randint(0, 700, 8192)
        .astype(np.uint16).tobytes())
    with pytest.raises(SystemExit, match="vocab"):
        _run(["--config", "gpt2_124m", "--model-preset", "tiny",
              "--steps", "1", "--batch-size", "4", "--seq-len", "64",
              "--data-dir", str(tmp_path)])


def test_cli_scan_layers_sp_matches_single(devices8):
    """--scan-layers composes with ring-attention sequence parallelism:
    the per-hop collectives run inside the lax.scan body under shard_map,
    matching single-device numerics step-for-step."""
    ref = _final_losses("gpt2_124m", 3, 8,
                        ["--parallel", "single", "--scan-layers"])
    sp = _final_losses("gpt2_124m", 3, 8,
                       ["--parallel", "sp", "--mesh", "dp=2,sp=4",
                        "--attn-impl", "ring", "--scan-layers"])
    np.testing.assert_allclose(sp, ref, rtol=1e-3)
    # Ulysses all-to-all + scan + remat compose too (memory-knob stack).
    uly = _final_losses("gpt2_124m", 3, 8,
                        ["--parallel", "sp", "--mesh", "dp=2,sp=4",
                         "--attn-impl", "ulysses", "--scan-layers",
                         "--remat"])
    np.testing.assert_allclose(uly, ref, rtol=1e-3)


def test_cli_bert_eval_and_lm_heldout_eval(tmp_path, monkeypatch):
    """--eval works for BERT (masked perplexity over synthetic MLM) and
    both LM configs evaluate held-out val.tokens files deterministically."""
    import functools

    from nezha_tpu.data import native

    # One loader thread: the TRAINING stream is then a function of --seed
    # alone. With the default two, each draws its own window stream into
    # one queue and which batch comes out first is the scheduler's choice,
    # so two runs can train on different batches (step-1 losses 6.2595 /
    # 6.2383) and the same held-out file reads 517.14 / 517.13.
    monkeypatch.setattr(native, "TokenLoader", functools.partial(
        native.TokenLoader, num_workers=1))
    m = _run(["--config", "bert_base_zero1", "--model-preset", "tiny",
              "--steps", "2", "--batch-size", "8", "--parallel", "single",
              "--eval", "--log-every", "1"])
    assert "eval_perplexity" in m or any("perplexity" in k for k in m), m

    rng = np.random.RandomState(0)
    (tmp_path / "train.tokens.u16").write_bytes(
        rng.randint(0, 512, 40000).astype(np.uint16).tobytes())
    (tmp_path / "val.tokens.u16").write_bytes(
        rng.randint(0, 512, 4000).astype(np.uint16).tobytes())
    lm = ["--config", "gpt2_124m", "--model-preset", "tiny",
          "--steps", "2", "--batch-size", "4", "--seq-len", "64",
          "--parallel", "single",
          "--data-dir", str(tmp_path), "--eval", "--log-every", "1"]
    m1, m2 = _run(lm), _run(lm)
    k = [x for x in m1 if "perplexity" in x][0]
    assert np.isfinite(m1[k])
    np.testing.assert_allclose(m1[k], m2[k], rtol=1e-5)  # deterministic
    # BERT over the same held-out tokens (explicit mask id: byte-ish vocab)
    m3 = _run(["--config", "bert_base_zero1", "--model-preset", "tiny",
               "--steps", "2", "--batch-size", "8", "--parallel", "single",
               "--mlm-mask-token", "300", "--data-dir", str(tmp_path),
               "--eval", "--log-every", "1"])
    k3 = [x for x in m3 if "perplexity" in x][0]
    assert np.isfinite(m3[k3])


def test_cli_graph_bf16(devices8):
    """--graph-bf16 trains the IR-authored bf16 policy through the CLI
    (single and graph-dp); non-graph engines reject."""
    import pytest
    m = _run(["--config", "gpt2_124m", "--model-preset", "tiny",
              "--steps", "2", "--batch-size", "4", "--engine", "graph",
              "--parallel", "single", "--graph-bf16", "--log-every", "1"])
    assert np.isfinite(m["loss"])
    m = _run(["--config", "gpt2_124m", "--model-preset", "tiny",
              "--steps", "2", "--batch-size", "8", "--engine", "graph",
              "--parallel", "dp", "--mesh", "dp=8", "--graph-bf16",
              "--log-every", "1"])
    assert np.isfinite(m["loss"])
    with pytest.raises(SystemExit, match="graph-bf16"):
        _run(["--config", "gpt2_124m", "--model-preset", "tiny",
              "--steps", "1", "--batch-size", "4", "--graph-bf16"])
    with pytest.raises(SystemExit, match="graph-bf16"):
        _run(["--config", "mlp_mnist", "--steps", "1", "--batch-size", "4",
              "--engine", "graph", "--graph-bf16"])


def test_cli_scan_layers_resume_and_knob_compositions(tmp_path, devices8):
    """scan-layers composes with checkpoint resume, --grad-accum,
    --clip-norm, and --wd-exclude-1d; MoE composes with the decay mask."""
    ck = str(tmp_path / "ck")
    _run(["--config", "gpt2_124m", "--model-preset", "tiny", "--steps", "3",
          "--batch-size", "8", "--scan-layers", "--mesh", "dp=8",
          "--ckpt-dir", ck])
    m = _run(["--config", "gpt2_124m", "--model-preset", "tiny",
              "--steps", "2", "--batch-size", "8", "--scan-layers",
              "--mesh", "dp=8", "--ckpt-dir", ck, "--log-every", "1"])
    assert m["step"] == 5  # resumed 3 -> 5
    m = _run(["--config", "gpt2_124m", "--model-preset", "tiny",
              "--steps", "2", "--batch-size", "8", "--scan-layers",
              "--grad-accum", "2", "--clip-norm", "1.0", "--wd-exclude-1d",
              "--mesh", "dp=8", "--log-every", "1"])
    assert np.isfinite(m["loss"])
    m = _run(["--config", "gpt2_124m", "--model-preset", "tiny",
              "--steps", "2", "--batch-size", "8", "--moe-experts", "4",
              "--wd-exclude-1d", "--mesh", "dp=8", "--log-every", "1"])
    assert np.isfinite(m["loss"])
    # BERT stacked encoder under GSPMD TP; scan + int8 gradient wire.
    m = _run(["--config", "bert_base_zero1", "--model-preset", "tiny",
              "--steps", "2", "--batch-size", "8", "--parallel", "gspmd",
              "--mesh", "dp=4,tp=2", "--scan-layers", "--log-every", "1"])
    assert np.isfinite(m["loss"])
    m = _run(["--config", "gpt2_124m", "--model-preset", "tiny",
              "--steps", "2", "--batch-size", "8", "--parallel", "dp",
              "--mesh", "dp=8", "--scan-layers", "--grad-allreduce",
              "int8", "--log-every", "1"])
    assert np.isfinite(m["loss"])
    # Sharded (gspmd) checkpoint resume with the stacked trunk.
    ck2 = str(tmp_path / "ck2")
    _run(["--config", "gpt2_124m", "--model-preset", "tiny", "--steps", "2",
          "--batch-size", "8", "--parallel", "gspmd", "--mesh", "dp=4,tp=2",
          "--scan-layers", "--ckpt-dir", ck2])
    m = _run(["--config", "gpt2_124m", "--model-preset", "tiny",
              "--steps", "2", "--batch-size", "8", "--parallel", "gspmd",
              "--mesh", "dp=4,tp=2", "--scan-layers", "--ckpt-dir", ck2,
              "--log-every", "1"])
    assert m["step"] == 4 and np.isfinite(m["loss"])


def test_cli_run_dir_telemetry(devices8, tmp_path):
    """--run-dir captures the run: metrics.jsonl with step rates,
    spans.jsonl, and a summary.json carrying per-collective payload bytes
    and compile-cache counts — all matching the frozen telemetry schema —
    and nezha-telemetry renders a report from it. Telemetry is OFF again
    after the run (the disabled fast path is the default state)."""
    import os
    import sys

    from nezha_tpu import obs
    from nezha_tpu.cli.telemetry import main as telemetry_main

    run_dir = str(tmp_path / "run")
    metrics = _run(["--config", "mlp_mnist", "--steps", "6",
                    "--batch-size", "16", "--parallel", "dp",
                    "--mesh", "dp=8", "--log-every", "2",
                    "--run-dir", run_dir])
    assert np.isfinite(metrics["loss"])
    assert not obs.enabled()  # run scope closed on exit

    recs = obs.read_metrics(os.path.join(run_dir, "metrics.jsonl"))
    assert recs and all("steps_per_sec" in r for r in recs)
    assert recs[-1]["step"] == 6
    spans = obs.read_metrics(os.path.join(run_dir, "spans.jsonl"))
    assert any(s["name"] == "train.first_step" for s in spans)
    with open(os.path.join(run_dir, "summary.json")) as f:
        summary = json.load(f)
    # The dp gradient collective was accounted (trace-time payload bytes).
    ar = summary["collectives"]["all_reduce"]
    assert ar["calls"] >= 1 and ar["payload_bytes"] > 0
    assert summary["compile_cache"]["hits"] >= 0  # section always present
    assert summary["histograms"]["metric.steps_per_sec"]["count"] == 3

    # Frozen schema (tools/check_telemetry_schema.py): drift fails here.
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", "tools"))
    from check_telemetry_schema import check_run_dir
    assert check_run_dir(run_dir) == []

    # The report CLI renders the capture.
    from contextlib import redirect_stdout
    import io
    buf = io.StringIO()
    with redirect_stdout(buf):
        assert telemetry_main([run_dir]) == 0
    out = buf.getvalue()
    assert "step rate" in out and "all_reduce" in out
    assert "compile cache" in out


def test_cli_bert_mask_token_resolved_from_corpus_tokenizer(devices8,
                                                            tmp_path,
                                                            capsys):
    """No --mlm-mask-token and a non-byte-level corpus: the TRUE [MASK]
    id comes from the tokenizer metadata next to the tokens file — the
    vocab.txt layout and the nezha-pack-text meta sidecar — instead of
    silently defaulting to 103 (ADVICE r5: a learned WordPiece vocab puts
    [MASK] at id 4, where 103 is a real subword)."""
    import pytest
    try:
        from nezha_tpu.data.native import load_library
        load_library()
    except Exception:
        pytest.skip("native runtime not available")
    rng = np.random.RandomState(0)
    (tmp_path / "train.tokens.u16").write_bytes(
        rng.randint(5, 200, 8192).astype(np.uint16).tobytes())
    # Layout 1: the packing tokenizer's vocab.txt sits next to the tokens
    # (--save-tokenizer into the data dir): [MASK] at id 4.
    (tmp_path / "vocab.txt").write_text(
        "[PAD]\n[UNK]\n[CLS]\n[SEP]\n[MASK]\n" +
        "\n".join(f"tok{i}" for i in range(500)) + "\n", encoding="utf-8")
    m = _run(["--config", "bert_base_zero1", "--model-preset", "tiny",
              "--steps", "2", "--batch-size", "8", "--log-every", "1",
              "--data-dir", str(tmp_path)])
    assert np.isfinite(m["loss"])
    assert "[MASK] id 4 resolved" in capsys.readouterr().err
    # Layout 2: the meta sidecar wins even without an adjacent vocab.
    (tmp_path / "vocab.txt").unlink()
    (tmp_path / "train.tokens.u16.meta.json").write_text(
        json.dumps({"tokenizer_kind": "WordPieceTokenizer",
                    "vocab_size": 505, "mask_token_id": 7}),
        encoding="utf-8")
    m = _run(["--config", "bert_base_zero1", "--model-preset", "tiny",
              "--steps", "2", "--batch-size", "8", "--log-every", "1",
              "--data-dir", str(tmp_path)])
    assert np.isfinite(m["loss"])
    assert "[MASK] id 7 resolved" in capsys.readouterr().err
