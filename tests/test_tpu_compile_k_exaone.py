"""K-EXAONE's serve programs COMPILE for a TPU v5e - no chip needed (PR 30).

The ENGINE's step and 1,024-token prefill programs of the cell's own
deployment (the ``full`` preset: five layers at the published widths, 128
slots, 8,192 positions, block 64): the global layer's table of 128 entries
beside the window layers' rings of 3, 64 query heads over 8 K/V heads in
both paged decode calls. The described chip, the program builder and the
sort search are ``test_tpu_compile.py``'s.

A file of its own, and a small one: the suite's six workers take the files
with the most tests first, and the step program's compile (20 s on every
core) inside ``test_tpu_compile.py`` fell on the second half of
``test_paged_kv.py``'s timing gate, whose two halves must run under the same
load.
"""

import re

import jax
import pytest

from test_tpu_compile import (  # noqa: F401  (fixtures, by name)
    _serve_programs,
    moe_expert_calls,
    _head_rankings,
    _sorts_outside_conditional_branches,
    v5e,
    v5e_devices,
)

KX_SLOTS, KX_MAX_LEN, KX_BLOCK, KX_CHUNK = 128, 8192, 64, 1024
KX_POOLS = {"global": 1 + KX_SLOTS * (KX_MAX_LEN // KX_BLOCK),
            "window": 1 + KX_SLOTS * 3}


@pytest.fixture(scope="module")
def k_exaone_programs(v5e):
    """{"step" | "prefill": compiled program}, compiled once."""
    from nezha_tpu.models.exaone_moe import k_exaone

    model = k_exaone("full")
    with pytest.MonkeyPatch.context() as mp:
        # ``auto`` takes the kernel on a TPU backend only (see gpt2_programs)
        mp.setattr(jax, "default_backend", lambda: "tpu")
        return _serve_programs(
            model, False, v5e, slots=KX_SLOTS, table=KX_MAX_LEN // KX_BLOCK,
            block=KX_BLOCK, chunk=KX_CHUNK, logits=model.cfg.vocab_held)


@pytest.mark.parametrize("program", ["step", "prefill"])
def test_k_exaone_serve_programs_fit_and_copy_no_pool(k_exaone_programs,
                                                      program):
    """Both pools are in the program as lane-dense rows of 8 x 128 lanes,
    no ``copy`` has either pool's shape, each of the four sparse layers'
    experts is ONE ``nezha_moe_experts`` call and no ``ragged-dot`` (the
    step's 1,024 pair rows and the chunk's 8,192 alike: no shape keeps the
    compiler's grouped matmul), and arguments + temporaries stay under 90%
    of the chip's 16 GB."""
    compiled = k_exaone_programs[program]
    text = compiled.as_text()
    for n in KX_POOLS.values():
        pool = re.escape(f"bf16[{n},{KX_BLOCK},1024]")
        assert re.search(pool, text)
        assert not re.findall(r" = " + pool + r"\S* copy\(", text)
    rows = KX_SLOTS * 8 if program == "step" else KX_CHUNK * 8
    calls = moe_expert_calls(text)
    assert len(calls) == 4 and all(
        re.search(rf" = \(?f32\[{rows},6144\]", c) for c in calls), calls
    ma = compiled.memory_analysis()
    live = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    assert live < 0.9 * 16e9
    assert ma.temp_size_in_bytes < KX_POOLS["global"] * KX_BLOCK * 1024 * 2


def test_k_exaone_step_decodes_through_one_kernel_body_twice_named(
        k_exaone_programs):
    """One call a layer: the full-table call on the global layer, the
    ring call on each of the four window layers, all with 64 query heads
    (``bf16[128,64,1,128]``: the shape the cell's
    ``kernel.gqa_decode_roofline`` and ``kernel.window_decode_time_share``
    patterns anchor on), the only other kernels of this repo's in the step
    the four sparse layers' ``nezha_moe_experts``, and the
    step's fetch carries the four sparse layers' expert-load counter and
    the experts' kernel's (visits, touched) beside it."""
    text = k_exaone_programs["step"].as_text()
    calls = [line.strip() for line in text.splitlines()
             if "tpu_custom_call" in line and " = " in line
             and re.match(r"(ROOT )?%?nezha_", line.strip())]
    shape = re.escape(f"bf16[{KX_SLOTS},64,1,128]")
    paged = [c for c in calls if re.match(
        r"(ROOT )?%?nezha_decode_attention_paged\S* = " + shape, c)]
    window = [c for c in calls if re.match(
        r"(ROOT )?%?nezha_decode_attention_window\S* = " + shape, c)]
    assert (len(paged), len(window), len(calls)) == (1, 4, 5 + 4), calls
    assert len(moe_expert_calls(text)) == 4
    # 1,024-lane pools: the full table takes the per-row loop, a ring
    # (every step of it live) keeps the grid form and says so in its name
    assert "_grid" not in paged[0].split(" = ")[0], paged
    assert all("_grid" in c.split(" = ")[0] for c in window), window
    assert re.search(r"s32\[4,16\]", text.split("ENTRY", 1)[1])
    assert re.search(r"s32\[4,2\]", text.split("ENTRY", 1)[1])


def test_k_exaone_step_program_sorts_the_vocabulary_only_under_a_conditional(
        k_exaone_programs):
    """As the other two served models (``test_tpu_compile.py``), at 128
    slots and the 19,200 rows of the vocabulary held: no ``TopK`` or
    ``sort`` that runs whenever the step does takes a vocabulary-wide
    row (``_head_rankings``), and the nucleus's ``sort`` runs only in a
    branch of a ``conditional`` (the dropless experts sort their
    token-expert pairs)."""
    text = k_exaone_programs["step"].as_text()
    _head_rankings(text, KX_SLOTS, 19200)
    always = _sorts_outside_conditional_branches(text)
    assert not [line for line in always if f"[{KX_SLOTS},19200]" in line]
