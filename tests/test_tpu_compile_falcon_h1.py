"""Falcon-H1's serve programs COMPILE for a TPU v5e - no chip needed
(PR 38).

The ENGINE's step and 1,024-token prefill programs of the cell's own
deployment (the ``full`` preset: four layers at the published widths, 256
slots, 2,048 positions, block 64): every layer holds an entry in two
groups, its K/V rows in the growing table (``bf16[8193,64,512]``) and its
SSM state and convolution tail in the state group (``s``
``f32[257,32,256,128]``, ``conv`` ``bf16[257,120,128]``). The described
chip and the program builder are ``test_tpu_compile.py``'s. A file of its
own for the reason ``test_tpu_compile_k_exaone.py`` gives.
"""

import json
import os
import re

import jax
import pytest

from test_tpu_compile import (  # noqa: F401  (fixtures, by name)
    _serve_programs,
    _head_rankings,
    _sorts_outside_conditional_branches,
    v5e,
    v5e_devices,
)

FH_SLOTS, FH_MAX_LEN, FH_BLOCK, FH_CHUNK = 256, 2048, 64, 1024
FH_LEAVES = {
    "s": f"f32[{FH_SLOTS + 1},32,256,128]",
    "conv": f"bf16[{FH_SLOTS + 1},120,128]",
    "k": f"bf16[{1 + FH_SLOTS * (FH_MAX_LEN // FH_BLOCK)},{FH_BLOCK},512]",
}
CONFIG = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "chipbench", "configs", "falcon-h1-34b.json")


@pytest.fixture(scope="module")
def falcon_programs(v5e):
    """{"step" | "prefill": compiled program}, compiled once."""
    from nezha_tpu.models.falcon_h1 import falcon_h1

    model = falcon_h1("full")
    with pytest.MonkeyPatch.context() as mp:
        # ``auto`` takes the kernels on a TPU backend only (see gpt2_programs)
        mp.setattr(jax, "default_backend", lambda: "tpu")
        return _serve_programs(
            model, False, v5e, slots=FH_SLOTS, table=FH_MAX_LEN // FH_BLOCK,
            block=FH_BLOCK, chunk=FH_CHUNK, logits=model.cfg.vocab_held)


def _live(compiled):
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)


@pytest.mark.parametrize("program", ["step", "prefill"])
def test_falcon_serve_programs_fit_and_copy_no_pool_or_state(falcon_programs,
                                                             program):
    """Every leaf of both groups is in the program as declared, no ``copy``
    has a leaf's shape (the state is rewritten in place: 1.08 GB a layer),
    and the live bytes are the configuration file's rehearsal (within 1%)
    and under 90% of the chip's 16 GB: the rule that gives 256 slots."""
    compiled = falcon_programs[program]
    text = compiled.as_text()
    for leaf in FH_LEAVES.values():
        assert re.search(re.escape(leaf), text), leaf
        assert not re.findall(r" = " + re.escape(leaf) + r"\S* copy\(", text)
    with open(CONFIG) as f:
        rehearsal = json.load(f)["serve"]["rehearsal"]
    stated = (rehearsal["step_program_bytes"]["live"] if program == "step"
              else rehearsal["prefill_live_bytes"][str(FH_CHUNK)])
    assert abs(_live(compiled) - stated) < 0.01 * stated
    assert _live(compiled) < 0.9 * 16e9
    # a leaf-shaped temporary is what a re-layout costs in memory
    assert compiled.memory_analysis().temp_size_in_bytes < (
        (FH_SLOTS + 1) * 32 * 256 * 128 * 4)


def test_falcon_step_updates_the_state_in_place_and_decodes_in_the_paged_kernel(
        falcon_programs):
    """A layer: one ``nezha_kda_conv`` and one ``nezha_ssm_decode``, each
    with its pool aliased to its second result, and ONE grouped call of
    the paged decode kernel (20 query heads over 4 K/V heads) with a
    ``bf16[256,20,1,128]`` result, the shape ``kernel.decode_time_share``'s
    accepted pattern takes (the state update's tuple result it does not);
    no other kernel of this repo's."""
    text = falcon_programs["step"].as_text()
    calls = [line.strip() for line in text.splitlines()
             if "tpu_custom_call" in line and " = " in line
             and re.match(r"(ROOT )?%?nezha_", line.strip())]
    ssm = [c for c in calls if re.match(
        r"(ROOT )?%?nezha_ssm_decode\S* = \(f32\[256,32,128\]\S*, "
        + re.escape(FH_LEAVES["s"]), c)]
    conv = [c for c in calls if re.match(
        r"(ROOT )?%?nezha_kda_conv\S* = \(f32\[256,40,128\]\S*, "
        + re.escape(FH_LEAVES["conv"]), c)]
    gqa = [c for c in calls if re.match(
        r"(ROOT )?%?nezha_decode_attention_paged\S* = "
        + re.escape("bf16[256,20,1,128]"), c)]
    assert (len(ssm), len(conv), len(gqa), len(calls)) == (4, 4, 4, 12), calls
    assert all("output_to_operand_aliasing={{1}: (3, {})}" in c
               for c in ssm + conv)
    accepted = re.compile(r"^%?\S+ = bf16\[\d+,\d+,1,\d+\]\S* custom-call\("
                          r".*tpu_custom_call")
    assert all(accepted.search(c.removeprefix("ROOT ")) for c in gqa)
    assert not any(accepted.search(c.removeprefix("ROOT "))
                   for c in ssm + conv)


def test_falcon_prefill_runs_no_kernel_and_scans_eight_chunks(falcon_programs):
    """The SSD chunked form is ``jax.numpy``: the 1,024-token prefill
    program holds no kernel of this repo's, and one ``while`` a layer over
    the bucket's 8 chunks of 128 tokens (the state ``[2 groups, 16 heads,
    256, 128]`` carried)."""
    text = falcon_programs["prefill"].as_text()
    kernels = re.findall(r"%?(nezha_\w+?)(?:\.\d+)? = .*tpu_custom_call", text)
    assert kernels == []
    scans = [line for line in text.splitlines()
             if " while(" in line and "f32[2,16,256,128]" in line]
    assert len(scans) == 4


def test_falcon_step_program_sorts_the_vocabulary_only_under_a_conditional(
        falcon_programs):
    """As the other served models: no ``TopK`` or ``sort`` that runs
    whenever the step does takes a vocabulary-wide row
    (``_head_rankings``), and the nucleus's ``sort`` runs only in a
    branch of a ``conditional``, at 256 slots and the 32,640 rows of the
    vocabulary held."""
    text = falcon_programs["step"].as_text()
    _head_rankings(text, FH_SLOTS, 32640)
    always = _sorts_outside_conditional_branches(text)
    assert not [line for line in always if f"[{FH_SLOTS},32640]" in line]
