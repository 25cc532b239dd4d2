"""``chip_smoke.py`` (repo root): it refuses to run off the chip, and every
phase of it is rehearsed here on the CPU at the tiny preset.

The rehearsal is steered from this file — the module's size constants are
patched to the tiny preset and ``KERNEL_IMPL`` to ``"kernel"``, which runs
the Pallas kernels through the interpreter off-TPU — because the program
itself has no option for it: whatever it is given, ``main()`` needs a TPU.
It finds wrong paths, arguments and control flow before a chip call does;
it says nothing about the compiler (tests/test_tpu_compile.py) or the chip.
"""

import json
import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

import chip_smoke  # noqa: E402


def test_chip_smoke_exits_nonzero_off_the_chip():
    """``JAX_PLATFORMS=cpu python chip_smoke.py``: non-zero exit, and no
    ``"ok": true`` anywhere on stdout (with either option)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for argv in ([], ["--chips", "4"]):
        p = subprocess.run(
            [sys.executable, os.path.join(_ROOT, "chip_smoke.py"), *argv],
            capture_output=True, text=True, env=env, cwd=_ROOT, timeout=120)
        assert p.returncode != 0
        assert '"ok"' not in p.stdout
        assert "needs" in p.stderr and "TPU" in p.stderr


def test_compare_rule(monkeypatch):
    """The logit/margin rule itself, on made-up numbers: equal streams
    pass; a divergence at a near-tie is excused (and that request's
    first-decode-step logits, conditioned on another token, are left
    out); a divergence where the reference was decisive, or logits off by
    more than the tolerance, fail."""
    import numpy as np
    monkeypatch.setattr(chip_smoke, "NEW_TOKENS", 3)
    logits = np.zeros((2, 8), np.float32)
    logits[:, 1] = 1.0
    ref = {"prefill_logits": logits, "step_logits": logits,
           "tokens": [[1, 2, 3], [1, 2, 3]],
           "margins": np.array([[1.0, 1.0, 1.0], [0.01, 1.0, 1.0]])}
    tol = chip_smoke.LOGIT_TOL_ULPS * 2.0 ** -8
    same = dict(ref)
    facts = chip_smoke.compare("t", ref, same, {"wire": ref["tokens"]})
    assert facts["logit_tol"] == round(tol, 4)
    assert facts["step_logits_compared"] == 2
    assert facts["tokens_agreeing_of_3"] == {"wire": [3, 3]}

    # request 1 took another first token at a near-tie: excused, and its
    # step logits (now wildly different) are not compared
    tied = dict(ref, tokens=[[1, 2, 3], [5, 6, 7]],
                step_logits=logits + np.array([[0.0], [2.5]], np.float32))
    facts = chip_smoke.compare("t", ref, tied, {"direct": tied["tokens"]})
    assert facts["step_logits_compared"] == 1
    assert facts["tokens_agreeing_of_3"] == {"direct": [3, 0]}
    assert facts["ref_margin_at_divergence"] == {"direct/r1@0": 0.01}

    # request 0 diverges where the reference's margin was wide
    with pytest.raises(RuntimeError, match="diverges at token 1"):
        chip_smoke.compare("t", ref, same, {"wire": [[1, 9, 3], [1, 2, 3]]})
    # logits further apart than the tolerance
    off = dict(ref, prefill_logits=logits + 2 * tol)
    with pytest.raises(RuntimeError, match="logits off"):
        chip_smoke.compare("t", ref, off, {})
    # a stream that is too short
    with pytest.raises(RuntimeError, match="returned 2 tokens"):
        chip_smoke.compare("t", ref, same, {"wire": [[1, 2], [1, 2, 3]]})


@pytest.fixture()
def tiny_smoke(monkeypatch):
    for name, value in {
            "MODEL_PRESET": "tiny",
            "KERNEL_IMPL": "kernel",
            # off-TPU "auto" trains on composed attention; the forced
            # serving kernels report as kernels
            "EXPECT_IMPLS": {"train": "xla", "decode": "kernel",
                             "prefill": "kernel"},
            "TRAIN_STEPS": 3,
            "NEW_TOKENS": 5,
            "SERVE_SHAPE": ("--max-len", "96", "--max-batch-size", "6",
                            "--max-prefill-len", "16",
                            "--prefill-buckets", "8,16",
                            "--kv-block-size", "8",
                            "--kv-num-blocks", "64"),
            "PROMPT_LENS": (20, 27, 37, 10),
            "SHARED_PREFIX": (16, 4),
            "MHC_PRESET": "tiny",
            "MHC_TOKENS": (8, 24)}.items():
        monkeypatch.setattr(chip_smoke, name, value)
    return chip_smoke


@pytest.mark.parametrize("phases,expected", [
    ("one_chip", ["attention", "train", "serve_bf16", "serve_int8",
                  "mhc_block"]),
    ("four_chips", ["train_4", "serve_bf16_mesh4", "serve_int8_mesh4"]),
])
def test_chip_smoke_phases_rehearse_on_cpu(tiny_smoke, devices8, tmp_path,
                                           capsys, phases, expected):
    """Every phase runs to its end through the real CLI entry points:
    losses fall, every request finishes with zero errors and retries, and
    each path agrees with its reference under the logit/margin rule
    (the phase functions raise otherwise)."""
    getattr(tiny_smoke, phases)(str(tmp_path))
    facts = {}
    for line in capsys.readouterr().out.splitlines():
        if line.startswith('{"phase"'):
            row = json.loads(line)
            facts[row["phase"]] = row
    assert list(facts) == expected
    for name, row in facts.items():
        if name.startswith("serve"):
            agreed = row["tokens_agreeing_of_5"]
            assert all(len(v) == 5 for v in agreed.values())   # 5 requests
            assert max(row["max_logit_diff"].values()) <= row["logit_tol"]
    if phases == "one_chip":
        # float32 at the tiny preset, the kernels through the interpreter
        assert max(facts["mhc_block"]["streams_diff_over_rms"].values()) \
            < 1e-4
    if phases == "four_chips":
        assert facts["train_4"]["dp4"]["placement"]["batch_devices"] == 4
        assert facts["train_4"]["zero1"]["placement"][
            "state_split_devices"] == 4
        assert facts["serve_int8_mesh4"]["pools_split_over"] == 4
