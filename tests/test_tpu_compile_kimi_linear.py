"""Kimi-Linear's serve programs COMPILE for a TPU v5e - no chip needed
(PR 32).

The ENGINE's step and 1,024-token prefill programs of the cell's own
deployment (the ``full`` preset: five layers at the published widths, 256
slots, 16,384 positions, block 64): the four KDA layers' state group (one
entry a slot: ``s`` ``f32[257,32,128,128]``, ``conv`` ``bf16[257,288,128]``)
beside the MLA layer's latent table of 256 entries a slot
(``bf16[65537,64,640]``). The described chip, the program builder and the
sort search are ``test_tpu_compile.py``'s. A file of its own for the reason
``test_tpu_compile_k_exaone.py`` gives.
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

KL_SLOTS, KL_MAX_LEN, KL_BLOCK, KL_CHUNK = 256, 16384, 64, 1024
KL_LEAVES = {
    "s": f"f32[{KL_SLOTS + 1},32,128,128]",
    "conv": f"bf16[{KL_SLOTS + 1},288,128]",
    "latent": f"bf16[{1 + KL_SLOTS * (KL_MAX_LEN // KL_BLOCK)},{KL_BLOCK},640]",
}


@pytest.fixture(scope="module")
def kimi_programs(v5e):
    """{"step" | "prefill": compiled program}, compiled once."""
    from nezha_tpu.models.kimi_linear import kimi_linear

    model = kimi_linear("full")
    with pytest.MonkeyPatch.context() as mp:
        # ``auto`` takes the kernels on a TPU backend only (see gpt2_programs)
        mp.setattr(jax, "default_backend", lambda: "tpu")
        return _serve_programs(
            model, False, v5e, slots=KL_SLOTS, table=KL_MAX_LEN // KL_BLOCK,
            block=KL_BLOCK, chunk=KL_CHUNK, logits=model.cfg.vocab_held)


@pytest.mark.parametrize("program", ["step", "prefill"])
def test_kimi_serve_programs_fit_and_copy_no_pool_or_state(kimi_programs,
                                                           program):
    """Every leaf of both groups is in the program as declared, no ``copy``
    has a leaf's shape (the state is rewritten in place: 0.54 GB a layer),
    each of the four sparse layers' experts is ONE ``nezha_moe_experts``
    call and no ``ragged-dot`` (the step's 2,048 pair rows and the chunk's
    8,192 alike: no shape keeps the compiler's grouped matmul), and
    arguments + temporaries stay under 90% of the chip's 16 GB."""
    compiled = kimi_programs[program]
    text = compiled.as_text()
    for leaf in KL_LEAVES.values():
        assert re.search(re.escape(leaf), text), leaf
        assert not re.findall(r" = " + re.escape(leaf) + r"\S* copy\(", text)
    rows = KL_SLOTS * 8 if program == "step" else KL_CHUNK * 8
    calls = moe_expert_calls(text)
    assert len(calls) == 4 and all(
        re.search(rf" = \(?f32\[{rows},2304\]", c) for c in calls), calls
    ma = compiled.memory_analysis()
    live = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    assert live < 0.9 * 16e9
    # a leaf-shaped temporary is what a re-layout costs in memory
    assert ma.temp_size_in_bytes < (KL_SLOTS + 1) * 32 * 128 * 128 * 4


def test_kimi_step_updates_the_state_in_place_and_decodes_in_the_paged_kernel(
        kimi_programs):
    """One ``nezha_kda_conv`` and one ``nezha_kda_decode`` a KDA layer, each
    with its pool aliased to its second result, and ONE ``nezha_decode_attention_latent`` on the MLA
    layer with a ``bf16[256,32,1,512]`` result (the shape
    ``kernel.decode_time_share``'s accepted pattern takes; the state
    update's tuple result it does not); the four sparse layers'
    ``nezha_moe_experts`` calls are the step's only other kernels of this
    repo's; the step's fetch carries the four sparse layers' expert-load
    counter and the experts' kernel's (visits, touched) beside it."""
    text = kimi_programs["step"].as_text()
    calls = [line.strip() for line in text.splitlines()
             if "tpu_custom_call" in line and " = " in line
             and re.match(r"(ROOT )?%?nezha_", line.strip())]
    kda = [c for c in calls if re.match(
        r"(ROOT )?%?nezha_kda_decode\S* = \(f32\[256,32,128\]\S*, "
        + re.escape(KL_LEAVES["s"]), c)]
    conv = [c for c in calls if re.match(
        r"(ROOT )?%?nezha_kda_conv\S* = \(f32\[256,96,128\]\S*, "
        + re.escape(KL_LEAVES["conv"]), c)]
    latent = [c for c in calls if re.match(
        r"(ROOT )?%?nezha_decode_attention_latent\S* = "
        + re.escape("bf16[256,32,1,512]"), c)]
    assert (len(kda), len(conv), len(latent), len(calls)) == (
        4, 4, 1, 9 + 4), calls
    assert len(moe_expert_calls(text)) == 4
    assert all("output_to_operand_aliasing={{1}: (3, {})}" in c
               for c in kda + conv)
    accepted = re.compile(r"^%?\S+ = bf16\[\d+,\d+,1,\d+\]\S* custom-call\("
                          r".*tpu_custom_call")
    assert accepted.search(latent[0].removeprefix("ROOT "))
    assert not any(accepted.search(c.removeprefix("ROOT "))
                   for c in kda + conv)
    assert re.search(r"s32\[4,64\]", text.split("ENTRY", 1)[1])
    assert re.search(r"s32\[4,2\]", text.split("ENTRY", 1)[1])


def test_kimi_prefill_runs_no_kernel_and_scans_sixteen_chunks(kimi_programs):
    """The chunked KDA form is ``jax.numpy``: the 1,024-token prefill
    program holds no kernel of this repo's but the four sparse layers'
    ``nezha_moe_experts`` (PR 33: a chunk's 8,192 pair rows go through the
    same kernel as a step's, no ``ragged-dot`` custom call is left), and
    one ``while`` a KDA layer over the bucket's 16 chunks of 64 tokens x
    32 heads x 128."""
    text = kimi_programs["prefill"].as_text()
    kernels = re.findall(r"%?(nezha_\w+?)(?:\.\d+)? = .*tpu_custom_call", text)
    assert sorted(kernels) == ["nezha_moe_experts"] * 4, kernels
    scans = [line for line in text.splitlines()
             if " while(" in line and "f32[16,32,64,128]" in line]
    assert len(scans) == 4


def test_kimi_step_program_sorts_the_vocabulary_only_under_a_conditional(
        kimi_programs):
    """As the other served models: no ``TopK`` or ``sort`` that runs
    whenever the step does takes a vocabulary-wide row
    (``_head_rankings``), and the nucleus's ``sort`` runs only in a
    branch of a ``conditional``, at 256 slots and the 40,960 rows of the
    vocabulary held."""
    text = kimi_programs["step"].as_text()
    _head_rankings(text, KL_SLOTS, 40960)
    always = _sorts_outside_conditional_branches(text)
    assert not [line for line in always if f"[{KL_SLOTS},40960]" in line]
