"""MiniCPM-SALA's serve programs COMPILE for a TPU v5e - no chip needed.

The ENGINE's step and 1,024-token prefill programs of the cell's own
deployment (the ``full`` preset: four layers at the published widths, 192
slots, 65,536 positions, block 64, a pool of 65,536 blocks): the sparse
layer's ``k``, ``v`` (``bf16[65536,64,256]``) and ``ck``
(``f32[65536,8,128]``) in the growing group, each lightning layer's state
(``f32[193,32,128,128]``) in the state group. The described chip and the
programs' helper are ``test_tpu_compile.py``'s. A file of its own for the
reason ``test_tpu_compile_k_exaone.py`` gives.
"""

import json
import os
import re

import jax
import pytest

from test_tpu_compile import (  # noqa: F401  (fixtures, by name)
    _serve_programs,
    v5e,
    v5e_devices,
)

SLOTS, MAX_LEN, BLOCK, CHUNK, BLOCKS = 192, 65536, 64, 1024, 65536
LEAVES = {
    "k": f"bf16[{BLOCKS},{BLOCK},256]",
    "ck": f"f32[{BLOCKS},8,128]",
    "s": f"f32[{SLOTS + 1},32,128,128]",
}
CONFIG = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "chipbench", "configs", "minicpm-sala.json")


@pytest.fixture(scope="module")
def sala_programs(v5e):
    """{"step" | "prefill": compiled program}, compiled once."""
    from nezha_tpu.models.minicpm_sala import minicpm_sala

    model = minicpm_sala("full")
    with pytest.MonkeyPatch.context() as mp:
        # ``auto`` takes the kernels on a TPU backend only
        mp.setattr(jax, "default_backend", lambda: "tpu")
        return _serve_programs(
            model, False, v5e, slots=SLOTS, table=MAX_LEN // BLOCK,
            block=BLOCK, chunk=CHUNK, logits=model.cfg.vocab_held,
            growing_blocks=BLOCKS)


def _live(compiled):
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)


@pytest.mark.parametrize("program", ["step", "prefill"])
def test_sala_serve_programs_fit_and_copy_no_pool_or_state(sala_programs,
                                                           program):
    """Every leaf of both groups is in the program as declared, no ``copy``
    has a leaf's shape, and the live bytes are the configuration file's
    rehearsal (within 1%) and under 90% of the chip's 16 GB."""
    compiled = sala_programs[program]
    text = compiled.as_text()
    for leaf in LEAVES.values():
        assert re.search(re.escape(leaf), text), leaf
        assert not re.findall(r" = " + re.escape(leaf) + r"\S* copy", text)
    with open(CONFIG) as f:
        rehearsal = json.load(f)["serve"]["rehearsal"]
    stated = (rehearsal["step_program_bytes"]["live"] if program == "step"
              else rehearsal["prefill_live_bytes"][str(CHUNK)])
    assert abs(_live(compiled) - stated) < 0.01 * stated
    assert _live(compiled) < 0.9 * 16e9


def test_sala_step_runs_the_sparse_kernels_and_three_lightning_updates(
        sala_programs):
    """One ``nezha_sparse_score`` (every row's window scores and its
    last window's mean), one ``nezha_sparse_decode_paged`` over the
    (row, K/V head) lists, and one ``nezha_ssm_decode`` a lightning layer
    with its state pool aliased to its second result; no other kernel of
    this repo's."""
    text = sala_programs["step"].as_text()
    calls = [line.strip() for line in text.splitlines()
             if "tpu_custom_call" in line and " = " in line
             and re.match(r"(ROOT )?%?nezha_", line.strip())]
    names = sorted(re.match(r"(?:ROOT )?%?(nezha_[a-z_]+)", c).group(1)
                   for c in calls)
    assert names == ["nezha_sparse_decode_paged", "nezha_sparse_score"] + [
        "nezha_ssm_decode"] * 3, names
    ssm = [c for c in calls if "nezha_ssm_decode" in c]
    assert all(re.search(re.escape(LEAVES["s"]), c) for c in ssm)
    assert all("output_to_operand_aliasing={{1}: (3, {})}" in c for c in ssm)
    decode = next(c for c in calls if "nezha_sparse_decode_paged" in c)
    assert re.search(r"= bf16\[384,16,128\]", decode)   # (row, head) x group


def test_sala_prefill_runs_no_kernel(sala_programs):
    """The 1,024-token prefill is ``jax.numpy``: no kernel of this repo's."""
    text = sala_programs["prefill"].as_text()
    assert re.findall(r"%?(nezha_\w+?)(?:\.\d+)? = .*tpu_custom_call",
                      text) == []
