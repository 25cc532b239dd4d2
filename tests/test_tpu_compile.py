"""The main path's Pallas kernels COMPILE for a TPU v5e — no chip needed.

The TPU compiler is installed beside the CPU backend and compiles for a
chip that is described, not attached
(``/opt/skills/guides/on-chip-measurement`` section 2.3). Interpret-mode
parity tests prove a kernel's arithmetic and nothing about the compiler:
every decode variant passed them for eighteen PRs while the TPU lowering
refused its block shapes. Each case here lowers one kernel with
``interpret=False`` at GPT-2 124M head shapes (H=12, D=64, bf16) and the
block/bucket sizes ``chip_smoke.py`` serves with, and compiles it for one
v5e device. A compile that passes is not a chip run; it says nothing about
results or times.
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp

import jax
import jax.numpy as jnp
import pytest

from nezha_tpu.ops.pallas import (
    flash_attention,
    flash_decode_attention,
    flash_decode_attention_sharded,
    flash_prefill_attention,
    flash_prefill_attention_sharded,
    fused_layer_norm,
)

H, D = 12, 64
BF16 = jnp.bfloat16
BLOCK, POOL, MAX_LEN, BATCH = 16, 512, 1024, 8   # chip_smoke.SERVE_SHAPE
TABLE = MAX_LEN // BLOCK


@pytest.fixture(scope="module")
def v5e_devices():
    """The four devices of a described v5e 2x2 host, with the persistent
    compilation cache off around the module (such a compile is written to
    the cache but cannot be read back without a chip, so the next one
    would warn)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def v5e(v5e_devices):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(v5e_devices[0])


def _grad_sum(fn):
    return jax.grad(lambda *a: fn(*a).astype(jnp.float32).sum(),
                    argnums=(0, 1, 2))


def _flash(causal, seq, with_lengths=False):
    args = [((BATCH, H, seq, D), BF16)] * 3
    if with_lengths:
        args.append(((BATCH,), jnp.int32))
    return _grad_sum(lambda q, k, v, lens=None: flash_attention(
        q, k, v, causal=causal, interpret=False, kv_lengths=lens)), args


# gpt2-124m.batch-gen's deployment (chipbench/configs/gpt2-124m.json): 256
# slots over the default pool of 1 scratch + 256 x 64 blocks.
CELL_BATCH, CELL_POOL = 256, 16385


def _decode(pool_dtype, batch=BATCH, blocks=POOL):
    pool = ((blocks, BLOCK, H * D), pool_dtype)     # lane-dense rows
    args = [((batch, H, 1, D), BF16), pool, pool, ((batch,), jnp.int32),
            ((batch, TABLE), jnp.int32)]
    if pool_dtype == jnp.int8:
        args += [((blocks, H), jnp.float32)] * 2
    return (lambda q, k, v, lens, tab, *sc: flash_decode_attention(
        q, k, v, lens, block_tables=tab, interpret=False,
        block_scales=sc or None)), args


def _prefill(pool_dtype, chunk):
    q = ((1, H, chunk, D), BF16)
    pool = ((POOL, BLOCK, H * D), pool_dtype)
    args = [q, q, q, pool, pool, ((1, TABLE), jnp.int32),
            ((1,), jnp.int32)]
    if pool_dtype == jnp.int8:
        args += [((POOL, H), jnp.float32)] * 2
    return (lambda q, kc, vc, kp, vp, tab, st, *sc: flash_prefill_attention(
        q, kc, vc, kp, vp, tab, st, interpret=False,
        block_scales=sc or None)), args


CASES = {
    # training: the trainer's default batch 8 x seq 1024, fwd + bwd
    "flash-causal-fwd-bwd-s1024": lambda: _flash(True, 1024),
    # BERT: non-causal S=512, without and with right-padding lengths
    "flash-noncausal-fwd-bwd-s512": lambda: _flash(False, 512),
    "flash-noncausal-kvlen-fwd-bwd-s512": lambda: _flash(
        False, 512, with_lengths=True),
    # serving decode: dense slots, paged bf16 pool, paged int8 pool
    "decode-dense": lambda: (
        lambda q, k, v, lens: flash_decode_attention(q, k, v, lens,
                                                     interpret=False),
        [((BATCH, H, 1, D), BF16)] + [((BATCH, H, MAX_LEN, D), BF16)] * 2
        + [((BATCH,), jnp.int32)]),
    "decode-paged-bf16": lambda: _decode(BF16),
    "decode-paged-int8": lambda: _decode(jnp.int8),
    "decode-paged-bf16-256slots": lambda: _decode(BF16, CELL_BATCH,
                                                  CELL_POOL),
    "decode-paged-int8-256slots": lambda: _decode(jnp.int8, CELL_BATCH,
                                                  CELL_POOL),
    # serving prefill at both of the smoke's buckets; int8 fuses the write
    "prefill-float-c64": lambda: _prefill(BF16, 64),
    "prefill-float-c256": lambda: _prefill(BF16, 256),
    "prefill-int8-fused-write-c64": lambda: _prefill(jnp.int8, 64),
    "prefill-int8-fused-write-c256": lambda: _prefill(jnp.int8, 256),
    # the opt-in fused layer norm (ln_impl="pallas"), fwd + bwd
    "layer-norm-fwd-bwd": lambda: (
        _grad_sum(lambda x, s, b: fused_layer_norm(x, s, b,
                                                   interpret=False)),
        [((BATCH * 1024, 768), BF16), ((768,), jnp.float32),
         ((768,), jnp.float32)]),
}


# The pallas_call(name=...) of each kernel a case must find in its HLO.
# (The layer norm's forward kernel is dead code under a sum's gradient.)
FLASH_NAMES = ("nezha_flash_fwd", "nezha_flash_bwd_dq", "nezha_flash_bwd_dkv")
KERNEL_NAMES = {
    "flash-causal-fwd-bwd-s1024": FLASH_NAMES,
    "flash-noncausal-fwd-bwd-s512": FLASH_NAMES,
    "flash-noncausal-kvlen-fwd-bwd-s512": FLASH_NAMES,
    "decode-dense": ("nezha_decode_attention_dense",),
    "decode-paged-bf16": ("nezha_decode_attention_paged",),
    "decode-paged-int8": ("nezha_decode_attention_paged_int8",),
    "decode-paged-bf16-256slots": ("nezha_decode_attention_paged",),
    "decode-paged-int8-256slots": ("nezha_decode_attention_paged_int8",),
    "prefill-float-c64": ("nezha_prefill_attention_paged",),
    "prefill-float-c256": ("nezha_prefill_attention_paged",),
    "prefill-int8-fused-write-c64": ("nezha_prefill_attention_paged_int8",),
    "prefill-int8-fused-write-c256": ("nezha_prefill_attention_paged_int8",),
    "layer-norm-fwd-bwd": ("nezha_layer_norm_bwd",),
}


@pytest.fixture(scope="module")
def hlo_of(v5e):
    """case -> the HLO text of its kernel compiled for one v5e device
    (each case compiles once for the tests that read it)."""
    texts = {}

    def compile_case(case):
        if case not in texts:
            fn, shapes = CASES[case]()
            args = [jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)
                    for shape, dtype in shapes]
            texts[case] = jax.jit(fn).lower(*args).compile().as_text()
        return texts[case]

    return compile_case


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(hlo_of, case):
    assert "tpu_custom_call" in hlo_of(case)


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_keeps_its_name_in_the_v5e_hlo(hlo_of, case):
    """Every Pallas call is a custom-call instruction whose OWN name
    carries the kernel's ``nezha_<kernel>_<variant>`` name (under jvp_ /
    transpose_jvp_ for a differentiated one): what a v5e trace prints
    before `` = `` and what the benchmark's patterns can anchor on. No
    Pallas call of a main-path kernel is anonymous."""
    own_names = [line.split(" = ", 1)[0].split()[-1].lstrip("%")
                 for line in hlo_of(case).splitlines()
                 if "tpu_custom_call" in line and " = " in line]
    assert own_names
    assert all("nezha_" in n for n in own_names), own_names
    kernels = {re.sub(r"^(transpose_)?(jvp_)?_*|_*(\.\d+)?$", "", n)
               for n in own_names}
    assert kernels == set(KERNEL_NAMES[case]), own_names


@pytest.mark.parametrize("case", ["decode-paged-bf16-256slots",
                                  "decode-paged-int8-256slots"])
def test_paged_decode_is_one_call_with_the_shape_the_benchmark_reads(
        hlo_of, case):
    """At the serving cell's own shape a layer's decode attention is
    exactly ONE custom call, named ``nezha_decode_attention_paged*``,
    whose result is ``bf16[256,12,1,64]``: ``kernel.decode_roofline``
    divides the matched seconds by the matched events (a kernel split
    in two calls would read double), and the ``kernel.decode_*``
    patterns anchor on that result shape (another shape matches
    nothing)."""
    calls = [line.strip() for line in hlo_of(case).splitlines()
             if "tpu_custom_call" in line and " = " in line]
    assert len(calls) == 1, calls
    assert "_grid" not in calls[0].split(" = ")[0]   # 768 lanes: the loop
    assert re.match(
        r"(ROOT )?%?nezha_decode_attention_paged\S* = "
        r"bf16\[256,12,1,64\]\S* custom-call\(", calls[0]), calls[0]


# The sharded serve engine's path: the same kernels PER HEAD SHARD under a
# nested shard_map over a 1x4 "tp" mesh (12 heads / 4), block tables and
# lengths replicated — Mosaic inside shard_map, partitioned by the TPU
# compiler for the four-chip host.
MESH_CASES = {
    "decode-paged-bf16": (flash_decode_attention_sharded, _decode, BF16),
    "decode-paged-int8": (flash_decode_attention_sharded, _decode,
                          jnp.int8),
    "prefill-float-c256": (flash_prefill_attention_sharded,
                           lambda dt: _prefill(dt, 256), BF16),
    "prefill-int8-fused-write-c256": (flash_prefill_attention_sharded,
                                      lambda dt: _prefill(dt, 256),
                                      jnp.int8),
}


@pytest.mark.parametrize("case", sorted(MESH_CASES))
def test_nested_shard_map_kernel_compiles_for_v5e_mesh4(v5e_devices, case):
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    sharded, shapes_of, pool_dtype = MESH_CASES[case]
    _, shapes = shapes_of(pool_dtype)
    mesh = Mesh(np.array(v5e_devices).reshape(4), ("tp",))
    decode = sharded is flash_decode_attention_sharded

    # int32 operands (lengths / tables / starts) replicate; q, chunks
    # and scales shard on the head axis, the pools ([N, bs, H*D]) on the
    # lanes: H / 4 contiguous heads a shard.
    def spec(shape, dtype):
        if dtype == jnp.int32:
            return P()
        return P(None, None, "tp") if len(shape) == 3 else P(None, "tp")

    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(
                mesh, spec(shape, dtype)))
            for shape, dtype in shapes]
    if decode:
        def fn(q, k, v, lens, tab, *sc):
            return sharded(q, k, v, lens, mesh, block_tables=tab,
                           block_scales=sc or None, interpret=False)
    else:
        def fn(q, kc, vc, kp, vp, tab, st, *sc):
            return sharded(q, kc, vc, kp, vp, tab, st, mesh,
                           block_scales=sc or None, interpret=False)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    if decode:
        # A head shard's pool is [N, bs, 192]: not whole 128-lane tiles,
        # so the paged decode kernel keeps its GRID form there and says
        # so in its name (``_paged_call``; Mosaic refuses the loop's
        # copies: "Slice shape along dimension 2 must be aligned to
        # tiling (128), but is 192").
        names = re.findall(r"%?(nezha_decode_attention_\w+?)(?:\.\d+)? = ",
                           text)
        assert names and all(n.endswith("_grid") for n in names), names


# ---- GPT-2's serve programs at gpt2-124m.batch-gen's deployment (PR 27):
# the ENGINE's step and 256-token prefill programs, one layer deep, 256
# slots, table 64, block 16, the default pool of 16,385 blocks. The K/V
# pool is lane-dense (``[N, bs, H*D]``: whole 128-lane tiles, the device's
# own row-major layout), so the parameter, the scatter that writes a token
# and both paged kernels take ONE layout and nothing re-lays the pool out.
# Before PR 27 (``[N, H, bs, D]``, a 64-wide minor dimension) every program
# copied each pool three times: 86.7% of the cell's device time.
def _serve_programs(model, quantized, v5e, *, slots, table, block, chunk,
                    logits):
    """{"step" | "prefill": compiled program} of ``model`` for one v5e."""
    from nezha_tpu.ops.pallas import ring_entries
    from nezha_tpu.serve.engine import _build_prefill, _build_step

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=v5e)

    variables = jax.tree_util.tree_map(
        lambda a: spec(a.shape, a.dtype),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    layers = model.cache_leaves(block, BF16, quantized)
    ring = {w: ring_entries(w, block) for _, w, _ in layers if w}

    def entries(group, w):      # table entries a slot, by kind of group
        return 1 if group == "state" else ring.get(w, table)

    caches = [{name: spec((1 + slots * entries(g, w),) + tuple(shape), dt)
               for name, (shape, dt) in leaves.items()}
              for g, w, leaves in layers]
    groups = tuple(g for g, _, _ in layers)
    tables = {g: spec((slots, entries(g, w)), jnp.int32)
              for g, w, _ in layers}
    b = slots
    state = (spec((b, logits), jnp.float32),
             spec((b,), jnp.int32), spec((b, 2), jnp.uint32),
             spec((b,), jnp.float32), spec((b,), jnp.int32),
             spec((b,), jnp.float32), spec((b,), jnp.int32),
             spec((b,), jnp.int32))
    last, pos, keys, temps, top_ks, top_ps, eos, budgets = state
    step = jax.jit(_build_step(model, 64, 0, 1, groups=groups),
                   donate_argnums=(1,)).lower(
        variables, caches, tables, last, pos, spec((b,), jnp.bool_), keys,
        temps, top_ks, top_ps, eos, budgets).compile()
    i32, f32 = spec((), jnp.int32), spec((), jnp.float32)
    prefill = jax.jit(_build_prefill(model, chunk, quantized=quantized,
                                     groups=groups),
                      donate_argnums=(1,)).lower(
        variables, caches, tables, spec((1, chunk), jnp.int32), i32, i32, i32,
        i32, f32, i32, f32, i32, i32, *state).compile()
    return {"step": step, "prefill": prefill}


CELL_TABLE, CELL_CHUNK = MAX_LEN // BLOCK, 256


@pytest.fixture(scope="module")
def gpt2_programs(v5e):
    """pool kind -> {"step" | "prefill": compiled program}, compiled once
    for the tests below. ``auto`` takes the kernels on a TPU backend
    only, and this process's backend is the CPU: the TEST says "tpu"
    around the trace (no option of the program does)."""
    from nezha_tpu.models.gpt2 import gpt2_124m

    model = gpt2_124m(num_layers=1)
    programs = {}

    def of(kind):
        if kind not in programs:
            with pytest.MonkeyPatch.context() as mp:
                mp.setattr(jax, "default_backend", lambda: "tpu")
                programs[kind] = _serve_programs(
                    model, kind == "int8", v5e, slots=CELL_BATCH,
                    table=CELL_TABLE, block=BLOCK, chunk=CELL_CHUNK,
                    logits=model.cfg.vocab_size)
        return programs[kind]

    return of


@pytest.mark.parametrize("program", ["step", "prefill"])
@pytest.mark.parametrize("kind", ["bf16", "int8"])
def test_gpt2_serve_program_has_no_pool_shaped_copy(gpt2_programs, kind,
                                                    program):
    """The mechanism's "does it engage" reading, at compile time: no
    ``copy`` (alone or as a fusion's root) whose result has the pool's
    shape, in the step or the 256-token prefill program, for the bf16 and
    the int8 pool; the paged kernel is in the program; and the program's
    temporaries are smaller than ONE pool (a pool-shaped temporary is
    what a re-layout costs in memory)."""
    compiled = gpt2_programs(kind)[program]
    text = compiled.as_text()
    dt = "s8" if kind == "int8" else "bf16"
    pool = re.escape(f"{dt}[{CELL_POOL},{BLOCK},{H * D}]")
    assert re.search(pool, text)                # the lane-dense pool
    assert not re.findall(r" = " + pool + r"\S* copy\(", text)
    kernel = ("nezha_decode_attention_paged" if program == "step"
              else "nezha_prefill_attention_paged")
    assert re.search(r"%?" + kernel + r"\S* = .*custom-call\(", text)
    pool_bytes = CELL_POOL * BLOCK * H * D * (1 if kind == "int8" else 2)
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes


# ---- Mistral-Small-4's serve programs (PR 26): decode attention through
# the paged decode kernel's latent form under this model's name for it
# (``nezha_mla_decode_paged``, PR 35; the composed view before), prefill
# attention composed over the gathered table, and, since PR 33, the
# experts' grouped matmuls as ONE Pallas call a layer (``nezha_moe_experts``,
# which Mosaic compiles here at the published widths), so what is compiled
# is the ENGINE's step and 1,024-token prefill programs, one layer deep,
# with the serving cell's slots, table and pool.
M4_SLOTS, M4_MAX_LEN, M4_BLOCK, M4_CHUNK = 128, 4096, 64, 1024


@pytest.fixture(scope="module")
def mistral4_programs(v5e):
    """{"step" | "prefill": HLO text}: compiled once for the tests below."""
    from nezha_tpu.models.mistral4 import mistral_small4

    model = mistral_small4("full", num_hidden_layers=1)
    with pytest.MonkeyPatch.context() as mp:
        # a kernel is compiled on a TPU backend only (see gpt2_programs)
        mp.setattr(jax, "default_backend", lambda: "tpu")
        programs = _serve_programs(
            model, False, v5e, slots=M4_SLOTS, table=M4_MAX_LEN // M4_BLOCK,
            block=M4_BLOCK, chunk=M4_CHUNK, logits=model.cfg.vocab_held)
    return {name: compiled.as_text() for name, compiled in programs.items()}


def moe_expert_calls(text):
    """The ``nezha_moe_experts*`` custom calls of an HLO module (what the
    benchmark's ``kernel.moe_*`` patterns count), after asserting that the
    compiler's own grouped matmul is in it nowhere."""
    assert not re.findall(r"%ragged-dot\S* = ", text)
    return [line.strip() for line in text.splitlines()
            if "tpu_custom_call" in line and re.match(
                r"(ROOT )?%?nezha_moe_experts\S* = ", line.strip())]


@pytest.mark.parametrize("program", ["step", "prefill"])
def test_mistral4_serve_programs_compile_for_v5e(mistral4_programs, program):
    text = mistral4_programs[program]
    # the routed experts of the one layer are ONE kernel call and no
    # ``ragged-dot``, in the step (512 pair rows) and in the 1,024-token
    # chunk (4,096: no shape keeps the compiler's grouped matmul)
    rows = M4_SLOTS * 4 if program == "step" else M4_CHUNK * 4
    (call,) = moe_expert_calls(text)
    assert re.match(rf"(ROOT )?%?nezha_moe_experts\S* = \(?f32\[{rows},4096\]",
                    call), call
    pool = re.escape(f"bf16[{1 + M4_SLOTS * (M4_MAX_LEN // M4_BLOCK)},"
                     f"{M4_BLOCK},384]")
    assert re.search(pool, text)            # the latent pool, 384-lane rows


def test_mistral4_step_program_has_no_pool_shaped_copy(mistral4_programs):
    """The latent pool's minor dimension is whole 128-lane tiles, so the
    device's own layout is the row-major one every consumer takes: no
    ``copy`` of the pool's shape in the step program (a 320-wide row made
    the compiler put the BLOCK axis minor and copy the pool twice a
    layer: 12 copies of 0.34 GB a step at six layers)."""
    text = mistral4_programs["step"]
    pool = re.escape(f"bf16[{1 + M4_SLOTS * (M4_MAX_LEN // M4_BLOCK)},"
                     f"{M4_BLOCK},384]")
    assert not re.findall(r" = " + pool + r"\S* copy\(", text)
    # and the program's fetch carries the expert-load counter
    assert re.search(r"s32\[1,32\]", text.split("ENTRY", 1)[1])


def test_mistral4_step_decodes_in_the_paged_kernel_and_gathers_no_view(
        mistral4_programs):
    """The mechanism's "does it engage" reading, at compile time (PR 35):
    the one layer's step program holds exactly ONE ``nezha_mla_decode_paged``
    call (the name the benchmark's ``kernel.mla_decode_*`` patterns match)
    with a ``bf16[128,32,1,256]`` result, and none of the composed view's
    ops: no gather of every table entry of every row
    (``bf16[8192,64,384]``), no scores over all 4,096 positions
    (``f32[128,32,4096]``). The prefill program holds no such call (its
    table of 4,096 keys stays one gathered view)."""
    step, prefill = mistral4_programs["step"], mistral4_programs["prefill"]
    calls = [line.strip() for line in step.splitlines()
             if "tpu_custom_call" in line and re.match(
                 r"(ROOT )?%?nezha_mla_decode_paged\S* = ", line.strip())]
    assert len(calls) == 1, calls
    assert f" = bf16[{M4_SLOTS},32,1,256]" in calls[0]
    view = M4_SLOTS * (M4_MAX_LEN // M4_BLOCK)
    assert not re.search(re.escape(f"bf16[{view},{M4_BLOCK},384]"), step)
    assert not re.search(re.escape(f"f32[{M4_SLOTS},32,{M4_MAX_LEN}]"), step)
    assert "nezha_decode_attention_latent" not in step
    assert not re.search(r"nezha_(mla_decode|decode_attention)", prefill)


# ---- Sampling sorts the vocabulary only inside a conditional (PR 29): the
# nucleus threshold comes from the k_max head the native ``TopK`` returns,
# and the sort is the second branch of one ``lax.cond`` on "some row's
# nucleus is wider than the head". Before, ``sort f32[256,50257]`` ran every
# decode step: 18.5 ms of a 51 ms step in the GPT-2 cell.
def _sorts_outside_conditional_branches(text):
    """The ``sort`` instructions of an HLO module that run whenever the
    program does: those not in a conditional's branch computation, nor in
    anything only such a branch calls."""
    bodies = {m[1]: m[2] for m in re.finditer(
        r"^(?:ENTRY )?%(\S+) \(.*?\) -> [^\n]*\{\n(.*?)^\}", text,
        re.M | re.S)}
    branch_only = set()
    for m in re.finditer(r"branch_computations=\{([^}]*)\}"
                         r"|(?:true|false)_computation=(%\S+)", text):
        branch_only.update(
            n.strip().strip("%,") for n in (m[1] or m[2]).split(","))
    frontier = list(branch_only)
    while frontier:
        for name in re.findall(r"%([\w.\-]+)", bodies.get(frontier.pop(), "")):
            if name in bodies and name not in branch_only:
                branch_only.add(name)
                frontier.append(name)
    assert any(re.search(r" sort\(", bodies[n]) for n in branch_only
               if n in bodies)              # the wide-nucleus branch is there
    return [line.strip()[:160] for name, body in bodies.items()
            if name not in branch_only
            for line in body.splitlines() if re.search(r" sort\(", line)]


@pytest.mark.parametrize("served", ["gpt2", "mistral4"])
def test_serve_step_program_sorts_the_vocabulary_only_under_a_conditional(
        request, served):
    """At the cells' deployments (GPT-2: 256 slots, vocabulary 50,257;
    Mistral-Small-4: 128 slots, 32,768 held): the native ``TopK`` is in
    the step program and no vocabulary-wide ``sort`` outside the branch
    computations of a ``conditional``. GPT-2's step has no other sort
    at all; Mistral's dropless experts sort their token-expert pairs."""
    if served == "gpt2":
        text = request.getfixturevalue("gpt2_programs")("bf16")[
            "step"].as_text()
        vocab = f"[{CELL_BATCH},50257]"
    else:
        text = request.getfixturevalue("mistral4_programs")["step"]
        vocab = f"[{M4_SLOTS},32768]"
    assert re.search(r'custom_call_target="TopK"', text)
    always = _sorts_outside_conditional_branches(text)
    assert not [line for line in always if vocab in line]
    if served == "gpt2":
        assert always == []
