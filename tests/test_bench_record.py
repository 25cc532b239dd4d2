"""bench.py / nezha-bench never hide a missing chip, and their records are
platform-labeled (ROADMAP Speed 1): a backend that fails to start, or one
that is not a TPU, fails the run unless the CPU was pinned explicitly;
the peak-FLOP/s table refuses a device kind it does not know; vs_baseline
is tracked PER PLATFORM, so a CPU-pinned run can neither regress nor
overwrite the TPU anchor. The e2e test runs the real main() with the
config benches stubbed out (their numerics are covered elsewhere; this
file pins the record/baseline plumbing)."""

import json
import os
import subprocess
import sys
import types

import pytest

import bench

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TOOLS = os.path.join(_ROOT, "tools")
if _TOOLS not in sys.path:
    sys.path.insert(0, _TOOLS)


# ------------------------------------------------------- backend init
def test_init_backend_failure_propagates(monkeypatch):
    """No CPU retry: a backend that cannot start fails the bench, with
    or without the explicit CPU pin."""
    import jax

    def dead_backend():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", dead_backend)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        bench._init_backend()
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        bench.main()


def test_init_backend_refuses_cpu_without_pin(monkeypatch):
    monkeypatch.delenv("NEZHA_BENCH_CPU", raising=False)
    with pytest.raises(RuntimeError, match="no TPU"):
        bench._init_backend()


def test_init_backend_env_pin(monkeypatch):
    monkeypatch.setenv("NEZHA_BENCH_CPU", "1")
    assert bench._init_backend().platform == "cpu"


def test_bench_py_exits_nonzero_without_a_chip():
    """The program itself, not just the helper: ``python bench.py`` on a
    machine whose jax finds no TPU exits non-zero and prints no record."""
    env = {k: v for k, v in os.environ.items() if k != "NEZHA_BENCH_CPU"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, os.path.join(_ROOT, "bench.py")],
                       capture_output=True, text=True, env=env, cwd=_ROOT,
                       timeout=120)
    assert p.returncode != 0
    assert "no TPU" in p.stderr and p.stdout.strip() == ""


def test_nezha_bench_refuses_cpu_without_explicit_platform(monkeypatch):
    """``nezha-bench`` with no chip and no ``--platform cpu`` exits
    non-zero before it runs a suite; a failing backend propagates."""
    import jax

    from nezha_tpu.cli import bench as nb
    with pytest.raises(SystemExit, match="no TPU"):
        nb.main(["--quick", "--suites", "decode_attention"])
    assert nb._resolve_platform("cpu") == "cpu"     # the explicit pin

    def dead_backend():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "default_backend", dead_backend)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        nb._resolve_platform(None)


# ---------------------------------------------------------- peaks table
def test_peak_flops_table_is_keyed_by_device_kind():
    v5e = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert bench._peak_flops(v5e) == 197e12
    cpu = types.SimpleNamespace(platform="cpu", device_kind="cpu")
    assert bench._peak_flops(cpu) is None    # MFU is meaningless there


def test_peak_flops_unknown_device_kind_is_an_error(monkeypatch):
    monkeypatch.setenv("NEZHA_PEAK_TFLOPS", "123")   # the env knob is gone
    mystery = types.SimpleNamespace(platform="tpu", device_kind="TPU v9")
    with pytest.raises(ValueError, match="TPU v9"):
        bench._peak_flops(mystery)


def test_aot_compile_failure_propagates():
    class Broken:
        def lower(self, *args):
            raise RuntimeError("Mosaic refused the kernel")

    with pytest.raises(RuntimeError, match="Mosaic refused"):
        bench._aot_compile(Broken())


# -------------------------------------------------- baseline plumbing
def test_family_baseline_legacy_flat_record_is_tpu():
    legacy = {"gpt2_124m_tokens_per_sec_per_chip": 87564.0,
              "platform": "tpu",
              "resnet50_images_per_sec_per_chip": 2373.7}
    tpu = bench._family_baseline(legacy, "tpu")
    assert tpu["gpt2_124m_tokens_per_sec_per_chip"] == 87564.0
    # a CPU run sees NO anchors in a legacy tpu record
    assert bench._family_baseline(legacy, "cpu") == {}


def test_family_baseline_by_platform_overlays_flat():
    rec = {"gpt2_124m_tokens_per_sec_per_chip": 100.0, "platform": "tpu",
           "by_platform": {
               "tpu": {"gpt2_124m_tokens_per_sec_per_chip": 200.0},
               "cpu": {"gpt2_124m_tokens_per_sec_per_chip": 5.0}}}
    assert bench._family_baseline(rec, "tpu")[
        "gpt2_124m_tokens_per_sec_per_chip"] == 200.0
    assert bench._family_baseline(rec, "cpu")[
        "gpt2_124m_tokens_per_sec_per_chip"] == 5.0


def test_load_baseline_corruption_is_sticky(tmp_path):
    path = tmp_path / "b.json"
    path.write_text("{not json")
    rec, corrupt = bench._load_baseline(str(path))
    assert rec == {} and corrupt
    path.write_text("[1, 2]")       # parseable but not a record
    rec, corrupt = bench._load_baseline(str(path))
    assert rec == {} and corrupt
    rec, corrupt = bench._load_baseline(str(tmp_path / "missing.json"))
    assert rec == {} and not corrupt


# --------------------------------------------------------- e2e record
@pytest.fixture()
def stubbed_bench(monkeypatch):
    """main() with the config benches stubbed to constants — the run
    exercises backend init (under the explicit CPU pin), the
    dispatch-ping loop, and the whole baseline/record path, without
    minutes of CPU training."""
    monkeypatch.setenv("NEZHA_BENCH_CPU", "1")
    monkeypatch.setattr(bench, "bench_gpt2",
                        lambda on_tpu, peak, **kw: (1000.0, None, 0.01))
    monkeypatch.setattr(bench, "bench_resnet50",
                        lambda on_tpu, peak: (50.0, None, 0.02))
    monkeypatch.setattr(bench, "bench_bert",
                        lambda on_tpu, peak: (800.0, None, 0.01))
    monkeypatch.setattr(bench, "bench_wrn101",
                        lambda on_tpu, peak: (20.0, None, 0.01))
    monkeypatch.setattr(bench, "bench_mlp", lambda on_tpu: 5.0)
    return bench


def _run_main(capsys) -> dict:
    assert bench.main() == 0
    out = capsys.readouterr().out.strip().splitlines()[-1]
    return json.loads(out)


def test_bench_writes_platform_labeled_record(stubbed_bench, tmp_path,
                                              monkeypatch, capsys):
    """The CPU-pinned harness check: bench.py completes, labels the
    record with its platform and device kind, seeds the CPU baseline
    slot, and tracks vs_baseline against it on the next run — all
    without touching a pre-existing TPU anchor."""
    path = tmp_path / "baseline.json"
    # a legacy TPU record is already there — the CPU run must not read
    # or clobber it
    path.write_text(json.dumps(
        {"gpt2_124m_tokens_per_sec_per_chip": 87564.0,
         "platform": "tpu"}))
    monkeypatch.setenv("NEZHA_BENCH_BASELINE", str(path))

    rec = _run_main(capsys)
    assert rec["platform"] == "cpu" and rec["device_kind"]
    assert "mfu" not in rec               # no peak for a CPU: no MFU
    assert rec["value"] == 1000.0
    assert rec["vs_baseline"] == 1.0      # first CPU measurement
    saved = json.loads(path.read_text())
    # TPU anchor untouched; CPU anchors seeded in their own slot
    assert saved["gpt2_124m_tokens_per_sec_per_chip"] == 87564.0
    assert saved["by_platform"]["cpu"][
        "gpt2_124m_tokens_per_sec_per_chip"] == 1000.0
    assert saved["by_platform"]["cpu"][
        "resnet50_images_per_sec_per_chip"] == 50.0

    # second run: vs_baseline is CPU-vs-CPU, anchors not overwritten
    monkeypatch.setattr(bench, "bench_gpt2",
                        lambda on_tpu, peak, **kw: (1500.0, None, 0.01))
    rec2 = _run_main(capsys)
    assert rec2["vs_baseline"] == 1.5
    assert rec2["extras"]["resnet50_vs_baseline"] == 1.0
    saved2 = json.loads(path.read_text())
    assert saved2["by_platform"]["cpu"][
        "gpt2_124m_tokens_per_sec_per_chip"] == 1000.0


def test_bench_corrupt_baseline_never_overwritten(stubbed_bench,
                                                  tmp_path, monkeypatch,
                                                  capsys):
    path = tmp_path / "baseline.json"
    path.write_text("{torn write")
    monkeypatch.setenv("NEZHA_BENCH_BASELINE", str(path))
    rec = _run_main(capsys)
    assert rec["vs_baseline"] == 1.0
    # the corrupt file was left for a human, not reset to this run
    assert path.read_text() == "{torn write"


# ------------------------------------------- committed-record hygiene
def test_committed_bench_records_pass_hygiene_check():
    """THE tier-1 wire for tools/check_bench_record.py: every committed
    BENCH_*.json in the repo root must be a platform-labeled, schema-
    valid measurement. There is no exemption list — a crash record fails
    here the moment it is committed."""
    from check_bench_record import check_dir
    assert check_dir(_ROOT) == []


def test_bench_record_checker_flags_crash_and_unlabeled(tmp_path):
    """A crash record (rc != 0), an rc=0 run with no parsed metric, an
    unlabeled measurement and a torn file all fail, and no note in
    BENCH_NOTES.md excuses them; only dropping the bad files clears the
    directory."""
    from check_bench_record import check_dir, check_record
    crash = tmp_path / "BENCH_r99.json"
    crash.write_text(json.dumps(
        {"n": 99, "cmd": "python bench.py", "rc": 1,
         "tail": "RuntimeError: Unable to initialize backend 'tpu'",
         "parsed": None}))
    assert any("CRASH RECORD" in e for e in check_record(str(crash)))

    silent = tmp_path / "BENCH_s.json"
    silent.write_text(json.dumps(
        {"n": 1, "cmd": "x", "rc": 0, "tail": "", "parsed": None}))
    assert any("no parsed metric" in e for e in check_record(str(silent)))

    unlabeled = tmp_path / "BENCH_u.json"
    unlabeled.write_text(json.dumps(
        {"n": 1, "cmd": "x", "rc": 0, "tail": "",
         "parsed": {"metric": "m", "value": 1.0}}))
    assert any("no platform label" in e
               for e in check_record(str(unlabeled)))

    not_json = tmp_path / "BENCH_torn.json"
    not_json.write_text("{torn")
    assert any("not valid JSON" in e for e in check_record(str(not_json)))

    good = tmp_path / "BENCH_ok.json"
    good.write_text(json.dumps(
        {"n": 1, "cmd": "x", "rc": 0, "tail": "",
         "parsed": {"metric": "m", "value": 1.0}, "platform": "cpu"}))
    assert check_record(str(good)) == []

    # Directory sweep: one violation per bad file, and listing them in
    # the notes changes nothing.
    assert len(check_dir(str(tmp_path))) == 4
    (tmp_path / "BENCH_NOTES.md").write_text(
        "# notes\n\n## Superseded records\n\n"
        "- BENCH_r99.json — crash record\n"
        "- BENCH_s.json — printed nothing\n")
    assert len(check_dir(str(tmp_path))) == 4
    for bad in (crash, silent, unlabeled, not_json):
        bad.unlink()
    assert check_dir(str(tmp_path)) == []
