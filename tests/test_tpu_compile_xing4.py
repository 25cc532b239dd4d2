"""Xing4.0's serve programs COMPILE for a TPU v5e - no chip needed (PR 36).

The ENGINE's step and 1,024-token prefill programs of the cell's own
deployment (the ``full`` preset: two dense and four sparse layers at the
published widths, every expert and the whole vocabulary held, 32 slots,
16,384 positions, block 64): a latent table of 256 entries a slot
(``bf16[8193,64,640]`` a layer) and, round every sublayer, the two
``nezha_mhc`` kernels over four float32 streams of 3,584. The described
chip, the program builder and the sort search are ``test_tpu_compile.py``'s.
A file of its own for the reason ``test_tpu_compile_k_exaone.py`` gives.
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

X4_SLOTS, X4_MAX_LEN, X4_BLOCK, X4_CHUNK = 32, 16384, 64, 1024
X4_POOL = f"bf16[{1 + X4_SLOTS * (X4_MAX_LEN // X4_BLOCK)},{X4_BLOCK},640]"
X4_LAYERS, X4_SPARSE = 6, 4


@pytest.fixture(scope="module")
def xing4_programs(v5e):
    """{"step" | "prefill": compiled program}, compiled once."""
    from nezha_tpu.models.xing4 import xing4

    model = xing4("full")
    with pytest.MonkeyPatch.context() as mp:
        # ``auto`` takes the kernels on a TPU backend only (see gpt2_programs)
        mp.setattr(jax, "default_backend", lambda: "tpu")
        return _serve_programs(
            model, False, v5e, slots=X4_SLOTS, table=X4_MAX_LEN // X4_BLOCK,
            block=X4_BLOCK, chunk=X4_CHUNK, logits=model.cfg.vocab_held)


def _kernels(text: str) -> list:
    """This repo's kernel calls of a compiled program, by name."""
    return re.findall(r"%?(nezha_\w+?)(?:\.\d+)? = .*tpu_custom_call", text)


@pytest.mark.parametrize("program", ["step", "prefill"])
def test_xing4_serve_programs_fit_and_copy_no_pool(xing4_programs, program):
    """The latent pool is in the program as declared and no ``copy`` has its
    shape; each sparse layer's experts are ONE ``nezha_moe_experts`` call
    over the 64 experts held whole; each of the 12 sublayers has ONE
    ``nezha_mhc_pre`` and ONE ``nezha_mhc_post``, the latter writing the
    streams over the ones it read; arguments + temporaries stay under 90%
    of the chip's 16 GB."""
    compiled = xing4_programs[program]
    text = compiled.as_text()
    assert re.search(re.escape(X4_POOL), text)
    assert not re.findall(r" = " + re.escape(X4_POOL) + r"\S* copy\(", text)
    rows = (X4_SLOTS if program == "step" else X4_CHUNK) * 4
    calls = moe_expert_calls(text)
    assert len(calls) == X4_SPARSE and all(
        re.search(rf" = \(?f32\[{rows},3584\]", c) for c in calls), calls
    kernels = _kernels(text)
    assert kernels.count("nezha_mhc_pre") == 2 * X4_LAYERS, kernels
    assert kernels.count("nezha_mhc_post") == 2 * X4_LAYERS, kernels
    tokens = X4_SLOTS if program == "step" else X4_CHUNK
    post = [line for line in text.splitlines()
            if re.match(r"\s*(ROOT )?%?nezha_mhc_post\S* = ", line)]
    assert all(re.search(rf" = f32\[{tokens},14336\]", c)
               and "output_to_operand_aliasing={{}: (0, {})}" in c
               for c in post), post
    ma = compiled.memory_analysis()
    live = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    assert live < 0.9 * 16e9
    # a pool-shaped temporary is what a re-layout costs in memory
    assert ma.temp_size_in_bytes < 8193 * 64 * 640 * 2


def test_xing4_step_decodes_in_the_paged_kernel_and_returns_the_residual(
        xing4_programs):
    """ONE ``nezha_mla_decode_paged`` a layer with a ``bf16[32,32,1,512]``
    result (the name and the shape the accepted ``kernel.mla_decode_*``
    patterns take); the step's results carry the four sparse layers'
    expert-load counter, the experts' kernel's (visits, touched) and, last,
    one float32: the pass's worst Sinkhorn residual."""
    text = xing4_programs["step"].as_text()
    kernels = _kernels(text)
    assert kernels.count("nezha_mla_decode_paged") == X4_LAYERS, kernels
    assert sorted(set(kernels)) == ["nezha_mhc_post", "nezha_mhc_pre",
                                    "nezha_mla_decode_paged",
                                    "nezha_moe_experts"], kernels
    latent = [line for line in text.splitlines()
              if re.match(r"\s*(ROOT )?%?nezha_mla_decode_paged\S* = ", line)]
    assert all("bf16[32,32,1,512]" in c for c in latent), latent
    entry = text.split("ENTRY", 1)[1]
    assert re.search(r"s32\[4,64\]", entry)
    assert re.search(r"s32\[4,2\]", entry)
    root = [line for line in entry.splitlines() if "ROOT" in line][-1]
    assert re.search(r"f32\[\]\S*\) tuple\(", root), root


def test_xing4_prefill_folds_the_long_table_and_names_both_mhc_kernels(
        xing4_programs):
    """A 1,024-token chunk attends the 16,384-position table a key block at
    a time (``MLAttention._prefill_blocked``: one ``while`` a layer), and
    the only kernels of this repo's in it are the experts' and the two
    ``nezha_mhc``."""
    text = xing4_programs["prefill"].as_text()
    assert sorted(set(_kernels(text))) == [
        "nezha_mhc_post", "nezha_mhc_pre", "nezha_moe_experts"]
    folds = [line for line in text.splitlines()
             if " while(" in line and "f32[1,32,1024,128]" in line]
    assert len(folds) == X4_LAYERS


def test_xing4_step_program_sorts_the_vocabulary_only_under_a_conditional(
        xing4_programs):
    """As the other served models: no ``TopK`` or ``sort`` that runs
    whenever the step does takes a vocabulary-wide row
    (``_head_rankings``), and the nucleus's ``sort`` runs only in a
    branch of a ``conditional``, at 32 slots and the whole vocabulary of
    131,072."""
    text = xing4_programs["step"].as_text()
    _head_rankings(text, X4_SLOTS, 131072)
    always = _sorts_outside_conditional_branches(text)
    assert not [line for line in always if f"[{X4_SLOTS},131072]" in line]
