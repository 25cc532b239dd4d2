"""One tiny serve cell and one tiny train cell end to end on the CPU, under
the explicit rehearsal pin: the last line has the contract's keys, says
``platform: cpu`` and carries no device metric; and the request-timing
arithmetic on a fake clock."""

import json

import pytest

import chipbench_tiny
from chipbench import device
from chipbench.drivers import serve

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return chipbench_tiny.make_root(str(tmp_path_factory.mktemp("cbroot")))


def _run(capsys, monkeypatch, root, *argv):
    from chipbench import run

    monkeypatch.setenv(device.REHEARSAL_ENV, "cpu")
    assert run.main(["--root", root, *argv]) == 0
    lines = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
    return [json.loads(l) for l in lines]


def _check_result(result, bench_metrics):
    assert RESULT_KEYS <= set(result) <= RESULT_KEYS | {"breakdown"}
    assert result["device"]["platform"] == "cpu"
    assert "busy_s" not in result["device"]
    assert "breakdown" not in result
    # counts only: nothing whose source is a clock or a device trace
    for name in result["metrics"]:
        assert bench_metrics[name] == "program_counter", name


@pytest.fixture(scope="module")
def sources():
    """{metric: source} from the metric files (the manifest lists only the
    metrics of the cells it admits)."""
    import glob
    import os
    out = {}
    for path in glob.glob(os.path.join(chipbench_tiny.CHIPBENCH, "metrics",
                                       "*.json")):
        with open(path) as f:
            m = json.load(f)
        out[m["name"]] = m["source"]
    return out


def test_tiny_serve_cell_rehearses(capsys, monkeypatch, root, sources):
    *facts, result = _run(capsys, monkeypatch, root, "--workload", "tiny.chat",
                          "--seed", "3", "--seconds", "2", "--trace", "1")
    _check_result(result, sources)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 3
    assert result["metrics"]["engine.prefix_hit_share"]["value"] > 20.0
    f = facts[-1]["facts"]
    assert f["reference_check"]["ok"] and f["compilations_in_window"] == 0
    assert f["reference_check"]["greedy_tokens_checked"] > 0
    assert any(f["reference_check"]["prefix_cached_tokens"])
    assert f["generator_lateness_ms"]["p99"] is not None


def test_tiny_backlog_cell_shares_nothing(capsys, monkeypatch, root, sources):
    *facts, result = _run(capsys, monkeypatch, root, "--workload", "tiny.gen",
                          "--seed", "3", "--seconds", "1", "--trace", "1")
    _check_result(result, sources)
    assert result["correct"] is True
    assert result["metrics"]["sched.batch_occupancy"]["value"] > 90.0
    fill = result["metrics"]["engine.kv_pool_fill_share"]["value"]
    assert 0.0 < fill <= 100.0     # live rows + prompts the trie retains
    counters = facts[-1]["facts"]["counters"]
    assert counters["prompt_tokens_cached"] == 0     # must read 0 here
    assert counters["tokens_in_span"] > 0


def test_tiny_train_cell_rehearses(capsys, monkeypatch, root, sources):
    *facts, result = _run(capsys, monkeypatch, root, "--workload",
                          "tiny.train", "--seed", "3", "--seconds", "1",
                          "--trace", "0")
    _check_result(result, sources)
    assert result["correct"] is True and result["failed"] == 0
    f = facts[-1]["facts"]
    assert f["loss_rel_diff"] < f["loss_rtol"]
    assert f["last_loss"] < f["first_step_loss"]
    assert f["compilations_in_window"] == 0
    assert result["attempted"] == f["window_steps"] > 0


def test_no_accelerator_and_no_pin_is_an_error(monkeypatch):
    monkeypatch.delenv(device.REHEARSAL_ENV, raising=False)
    with pytest.raises(SystemExit) as e:
        device.start(1)
    assert "needs a TPU" in str(e.value)


def test_too_few_chips_is_an_error(monkeypatch):
    monkeypatch.setenv(device.REHEARSAL_ENV, "cpu")
    with pytest.raises(SystemExit) as e:
        device.start(64)
    assert "needs 64 chip" in str(e.value)


def test_ttft_runs_from_due_time_on_a_fake_clock(monkeypatch):
    now = [100.0]
    monkeypatch.setattr(serve, "clock", lambda: now[0])
    job = serve.ServeRun({"traffic": {}}, 0, 10.0, False, "/nonexistent")
    job.win = (100.0, 110.0)
    # due at 101.0, handed over late, first token at 101.5, then 101.6, 101.9
    job.recs["a"] = serve._Rec("a", 101.0)
    for t in (101.5, 101.6, 101.9):
        now[0] = t
        job._on_token("a", 7)
    # due before the window, first token inside it: counted, from due
    job.recs["b"] = serve._Rec("b", 99.0)
    now[0] = 100.25
    job._on_token("b", 7)
    # first token after the window's end: no sample
    job.recs["c"] = serve._Rec("c", 109.0)
    now[0] = 110.5
    job._on_token("c", 7)
    assert job.obs.samples["ttft_ms"] == pytest.approx([500.0, 1250.0])
    assert job.obs.samples["itl_ms"] == pytest.approx([100.0, 300.0])
    assert job.obs.counters["tokens_out"] == 4
