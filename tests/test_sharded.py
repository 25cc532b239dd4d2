"""Tensor-sharded serving (serve/sharded): the M-device engine under a
1xM mesh on the suite's 8 forced host devices.

What this file pins, per ISSUE 14's acceptance:

- greedy outputs BIT-IDENTICAL to the single-device engine across the
  parity suites (paged bf16/f32, int8 + per-block scales, speculative
  decode) at mesh 2;
- the frozen program contract PER MESH — ``1 step +
  len(prefill_buckets)`` executor entries, misses frozen after warmup;
- ``--mesh 4`` serves a config whose KV + params exceed a single
  device's budget, provable from ``memory_report`` /
  ``bytes_resident_per_shard`` accounting;
- train->serve resharding: CRC-verified streaming load, bitwise
  round-trip through ``nezha-reshard``, and the ``serve.reshard``
  chaos drill — a corrupt leaf or injected fault is a typed
  ``ReshardError`` and the engine REFUSES to start;
- seeded chaos at mesh 2 (prefill errors, NaN bursts, KV bind
  failures, replica kill under the router) with zero slot/block/scale
  leaks per shard (``leak_check`` covers sharding loss too);
- migration composes: gather-on-export from a mesh-2 source installs
  bit-identically into a single-device destination;
- the mesh telemetry (``serve.mesh.devices`` gauge,
  ``serve.mesh.collective_bytes`` counter, report ``mesh:`` line) is
  captured schema-clean.
"""

import dataclasses
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from nezha_tpu import faults, obs
from nezha_tpu.faults import FaultPlan
from nezha_tpu.models.gpt2 import GPT2, GPT2Config
from nezha_tpu.serve import Engine, Request, Scheduler, ServeConfig
from nezha_tpu.serve.engine import SpeculativeConfig
from nezha_tpu.serve.sharded import (
    ReshardError,
    ShardedEngine,
    reshard_checkpoint,
    save_serve_checkpoint,
    verify_roundtrip,
)

CFG = dict(vocab_size=64, max_positions=64, num_layers=2, num_heads=4,
           hidden_size=32)
SCFG = ServeConfig(max_batch_size=3, max_len=32, max_prefill_len=8,
                   prefill_buckets=(4, 8), k_max=16, queue_capacity=8,
                   cache_dtype=jnp.float32)
PROMPTS = [[3, 5, 7, 9], [11, 2, 4], [1, 2, 3, 4, 5, 6, 7, 8, 9]]


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    faults.clear()
    yield
    faults.clear()


@pytest.fixture(scope="module")
def model_and_vars():
    model = GPT2(GPT2Config(**CFG))
    return model, model.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def single_engine(model_and_vars):
    model, variables = model_and_vars
    return Engine(model, variables, SCFG)


@pytest.fixture(scope="module")
def mesh2_engine(model_and_vars):
    model, variables = model_and_vars
    return ShardedEngine(model, variables, SCFG, mesh_devices=2)


def _greedy(engine, prompts, max_new=6):
    sched = Scheduler(engine)
    for i, p in enumerate(prompts):
        sched.submit(Request(prompt=p, max_new_tokens=max_new,
                             request_id=f"r{i}"))
    sched.run_until_idle(max_iters=400)
    assert not sched.has_work()
    return {k: v.tokens for k, v in sched.results.items()}


# ----------------------------------------------------- parity + contract
def test_mesh2_greedy_parity_bit_identical(single_engine, mesh2_engine):
    """The headline gate: same weights, same prompts, greedy decode —
    the 2-device tensor-parallel engine emits exactly the single-device
    engine's tokens (attention is head-parallel; the per-proj reduces
    are the only cross-device math)."""
    ref = _greedy(single_engine, PROMPTS)
    got = _greedy(mesh2_engine, PROMPTS)
    assert got == ref
    assert all(v for v in ref.values())


def test_frozen_program_contract_per_mesh(mesh2_engine):
    """Steady state per mesh is exactly ``1 step +
    len(prefill_buckets)`` executor entries with misses FROZEN: more
    traffic through warmed buckets compiles nothing."""
    _greedy(mesh2_engine, PROMPTS)   # warm both buckets + the step
    stats = mesh2_engine.compile_stats()
    assert stats["entries"] == 1 + len(SCFG.prefill_buckets)
    misses0 = stats["misses"]
    _greedy(mesh2_engine, [[7, 7, 7], [9] * 7])
    after = mesh2_engine.compile_stats()
    assert after["entries"] == 1 + len(SCFG.prefill_buckets)
    assert after["misses"] == misses0, "a sharded dispatch recompiled"


def test_mesh2_int8_parity_and_scale_shards(model_and_vars):
    """PR 9's parity suite under the mesh: int8 blocks + per-(block,
    head) scales shard on the head axis; greedy outputs match the
    single-device int8 engine bit for bit, and the per-shard leak
    oracle (books + scale shapes + sharding) stays clean."""
    model, variables = model_and_vars
    cfg = dataclasses.replace(SCFG, kv_dtype="int8")
    ref = _greedy(Engine(model, variables, cfg), PROMPTS)
    eng = ShardedEngine(model, variables, cfg, mesh_devices=2)
    assert _greedy(eng, PROMPTS) == ref
    eng.pool.leak_check()
    assert eng.pool.bytes_resident_per_shard == 0   # all freed
    sh = eng.pool.caches[0]["k_scale"].sharding
    assert not sh.is_fully_replicated


def test_mesh2_speculative_parity(model_and_vars):
    """PR 13's parity suite under the mesh: the fused
    draft->verify->accept program (draft pool mirrored + head-sharded
    too) emits exactly the classic greedy stream."""
    model, variables = model_and_vars
    cfg = dataclasses.replace(
        SCFG, speculative=SpeculativeConfig(draft_k=2, draft_layers=1))
    ref = _greedy(Engine(model, variables, cfg), PROMPTS[:2])
    eng = ShardedEngine(model, variables, cfg, mesh_devices=2)
    assert _greedy(eng, PROMPTS[:2]) == ref
    eng.pool.leak_check()       # recurses into the mirrored draft pool


def test_mesh2_forced_kernel_parity(model_and_vars):
    """``decode_impl="kernel"`` under the mesh: the raw Mosaic call can
    never be handed to the auto-partitioner, so the force routes
    through the nested-shard_map per-shard kernel (interpret mode on
    CPU) — and stays bit-identical to the single-device forced-kernel
    engine."""
    model, variables = model_and_vars
    cfg = dataclasses.replace(SCFG, decode_impl="kernel")
    ref = _greedy(Engine(model, variables, cfg), PROMPTS[:2], max_new=4)
    eng = ShardedEngine(model, variables, cfg, mesh_devices=2)
    assert _greedy(eng, PROMPTS[:2], max_new=4) == ref


# ------------------------------------------------- over-budget serving
def test_mesh4_serves_config_over_single_device_budget(model_and_vars):
    """THE scale-axis acceptance: a config whose KV + params exceed a
    hypothetical single-device budget serves on ``--mesh 4`` because
    each shard holds ~1/4 of the bytes — provable from the committed
    arrays' own shard accounting, then actually served."""
    model, variables = model_and_vars
    cfg = dataclasses.replace(SCFG, max_batch_size=4, max_len=64,
                              kv_num_blocks=None)
    eng = ShardedEngine(model, variables, cfg, mesh_devices=4)
    rep = eng.memory_report()
    assert rep["mesh_devices"] == 4
    # KV divides exactly by 4; params shard except the replicated tail
    # (layernorms, wpe, row-parallel biases).
    assert rep["kv_capacity_bytes_per_device"] * 4 == \
        rep["kv_capacity_bytes"]
    assert rep["params_bytes_per_device"] < rep["params_bytes"]
    # The budget story: a device half the logical footprint cannot
    # hold the model + KV, but each mesh-4 shard fits comfortably.
    budget = rep["bytes_total"] // 2
    assert rep["bytes_total"] > budget
    assert rep["bytes_per_device"] < budget
    # ...and it actually serves.
    out = _greedy(eng, [[5, 17, 3]], max_new=4)
    assert len(out["r0"]) == 4
    # Resident accounting is per-shard exact while a request is live.
    sched = Scheduler(eng)
    sched.submit(Request(prompt=[2, 4, 6, 8], max_new_tokens=4,
                         request_id="live"))
    sched.step()
    assert eng.pool.bytes_resident > 0
    assert eng.pool.bytes_resident_per_shard * 4 == \
        eng.pool.bytes_resident
    sched.run_until_idle(max_iters=100)
    eng.pool.leak_check()


# --------------------------------------------------------- resharding
def _train_ckpt(tmp_path, model, variables, step=5):
    from nezha_tpu import optim
    from nezha_tpu.train.checkpoint import save_checkpoint
    from nezha_tpu.train.loop import init_train_state
    state = init_train_state(model, optim.sgd(0.1),
                             jax.random.PRNGKey(0))
    state["variables"] = variables
    d = str(tmp_path / "ck")
    save_checkpoint(d, state, step)
    return d


def test_reshard_streams_crc_verified_and_roundtrips(model_and_vars,
                                                     tmp_path):
    model, variables = model_and_vars
    ck = _train_ckpt(tmp_path, model, variables)
    from nezha_tpu.parallel.mesh import make_mesh
    mesh = make_mesh({"tp": 4}, devices=jax.devices()[:4])
    rv, step = reshard_checkpoint(ck, model, mesh)
    assert step == 5
    for a, b in zip(jax.tree_util.tree_leaves(variables["params"]),
                    jax.tree_util.tree_leaves(rv["params"])):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # Megatron layout landed: the qkv weight is feature-sharded.
    assert not rv["params"]["h0"]["attn"]["qkv"]["w"] \
        .sharding.is_fully_replicated
    # Bitwise round trip through the serve-topology save.
    out = str(tmp_path / "serve4")
    save_serve_checkpoint(out, rv, step)
    assert verify_roundtrip(out, rv, step) == []
    # ...and the serve-topology save itself reshards (any mesh size).
    mesh2 = make_mesh({"tp": 2}, devices=jax.devices()[:2])
    rv2, _ = reshard_checkpoint(out, model, mesh2)
    for a, b in zip(jax.tree_util.tree_leaves(variables["params"]),
                    jax.tree_util.tree_leaves(rv2["params"])):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_reshard_cli_roundtrip(model_and_vars, tmp_path, capsys):
    model, variables = model_and_vars
    del model, variables
    from nezha_tpu.cli.train import TINY_GPT2_KW
    tiny = GPT2(GPT2Config(**TINY_GPT2_KW))
    ck = _train_ckpt(tmp_path, tiny, tiny.init(jax.random.PRNGKey(1)))
    from nezha_tpu.cli import reshard as cli_reshard
    out = str(tmp_path / "out")
    rc = cli_reshard.main(["--ckpt-dir", ck, "--mesh", "2",
                           "--model-preset", "tiny", "--out", out,
                           "--verify", "--json"])
    assert rc == 0
    report = json.loads(capsys.readouterr().out)
    assert report["roundtrip_ok"] is True
    assert report["mesh_devices"] == 2
    assert report["params_bytes_per_device"] < report["params_bytes"]


def test_reshard_refuses_corrupt_and_missing(model_and_vars, tmp_path):
    """The corrupt-checkpoint-at-boot story: a flipped byte fails the
    PR 4 CRC manifest and surfaces as the typed ``ReshardError`` — the
    engine never starts (``nezha-serve --mesh`` maps it to SystemExit)."""
    model, variables = model_and_vars
    ck = _train_ckpt(tmp_path, model, variables)
    from nezha_tpu.parallel.mesh import make_mesh
    mesh = make_mesh({"tp": 2}, devices=jax.devices()[:2])
    # Corrupt one params leaf, keep the original manifest.
    path = os.path.join(ck, "step_00000005.npz")
    z = np.load(path)
    flat = {k: np.array(z[k]) for k in z.files}
    z.close()
    key = sorted(k for k in flat
                 if k.startswith("variables/params/"))[0]
    flat[key].flat[0] += 1.0
    np.savez(path, **flat)
    with pytest.raises(ReshardError, match="CRC32 mismatch"):
        reshard_checkpoint(ck, model, mesh)
    # Missing checkpoint entirely: typed, not a stack trace.
    with pytest.raises(ReshardError, match="no training checkpoint"):
        reshard_checkpoint(str(tmp_path / "empty"), model, mesh)


def test_serve_reshard_fault_drill(model_and_vars, tmp_path):
    """The pinned ``serve.reshard`` chaos point: an injected error at
    the reshard entry is the SAME typed refusal a corrupt leaf
    produces, end to end through the CLI (engine refuses to start)."""
    model, variables = model_and_vars
    ck = _train_ckpt(tmp_path, model, variables)
    from nezha_tpu.parallel.mesh import make_mesh
    mesh = make_mesh({"tp": 2}, devices=jax.devices()[:2])
    faults.install(FaultPlan.parse("serve.reshard:error@1"))
    with pytest.raises(ReshardError, match="injected reshard fault"):
        reshard_checkpoint(ck, model, mesh)
    faults.clear()
    # The plan consumed its one shot above; a clean retry succeeds —
    # refusal is fail-stop, not fail-broken.
    rv, _ = reshard_checkpoint(ck, model, mesh)
    assert rv["params"] is not None


# ----------------------------------------------------- chaos at mesh 2
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_chaos_mesh2_zero_leaks_per_shard(model_and_vars, kv_dtype):
    """PR 6/7/9's chaos oracles re-run under the mesh: seeded prefill
    errors, mid-stream NaN bursts, and KV bind failures against a
    mesh-2 engine — every request retires typed, every slot frees, and
    the per-shard leak check (ref-count books + scale shapes + head
    sharding) balances."""
    model, variables = model_and_vars
    cfg = dataclasses.replace(SCFG, queue_capacity=16, kv_dtype=(
        "int8" if kv_dtype == "int8" else "bf16"))
    eng = ShardedEngine(model, variables, cfg, mesh_devices=2)
    sched = Scheduler(eng)
    faults.install(FaultPlan.parse(
        "serve.prefill:error@3;serve.step.logits:nan@4;"
        "serve.kv.bind:error@9", seed=7))
    for i in range(10):
        sched.submit(Request(prompt=[(3 + 5 * i) % 64, 2, 9],
                             max_new_tokens=4, request_id=f"c{i}",
                             seed=i))
    sched.run_until_idle(max_iters=600)
    faults.clear()
    assert not sched.has_work()
    assert len(sched.results) == 10
    reasons = {r.finish_reason for r in sched.results.values()}
    assert reasons <= {"length", "error", "eos"}
    assert "error" in reasons            # the plan genuinely fired
    assert eng.pool.num_free == cfg.max_batch_size
    eng.pool.leak_check()
    assert eng.pool.bytes_resident_per_shard == 0


def test_replica_kill_chaos_with_mesh2():
    """PR 6's replica-kill chaos with ``--mesh 2`` workers: two
    thread-hosted replicas, each a 2-device tensor-parallel engine
    behind a real socket; a mid-load kill fails the in-flight request
    over and the supervisor restarts the member — zero silent losses,
    the router blind to the mesh."""
    import threading
    import time

    from nezha_tpu.cli.serve import build_parser
    from nezha_tpu.serve.router import Router
    from nezha_tpu.serve.supervisor import (RouterConfig, Supervisor,
                                            ThreadBackend)
    wargs = build_parser().parse_args(
        ["--random-init", "--model-preset", "tiny", "--mesh", "2",
         "--max-batch-size", "2", "--max-len", "48",
         "--max-prefill-len", "8", "--queue-capacity", "4",
         "--platform", "cpu"])
    cfg = RouterConfig(replicas=2, probe_interval_s=0.1, probe_misses=3,
                       route_retries=2, retry_backoff_base_s=0.01,
                       retry_backoff_max_s=0.05,
                       restart_backoff_base_s=0.05,
                       restart_backoff_max_s=0.5,
                       drain_timeout_s=20.0, seed=0)
    sup = Supervisor(ThreadBackend(wargs, drain_timeout_s=20.0), cfg)
    router = Router(sup, cfg)
    sup.start()
    try:
        assert router.wait_live(2, timeout_s=600), sup.describe()
        faults.install(FaultPlan.parse("serve.step:delay=0.05x*"))
        out = {}
        t = threading.Thread(target=lambda: out.update(dict(zip(
            ("code", "obj"),
            router.route({"id": "meshkill", "prompt_tokens": [5, 17, 3],
                          "max_new_tokens": 24})))))
        t.start()
        victim = None
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            busy = [r.rid for r in sup.replicas() if r.in_flight]
            if busy:
                victim = busy[0]
                break
            time.sleep(0.01)
        assert victim is not None
        time.sleep(0.2)
        sup.kill(victim)
        t.join(timeout=300)
        faults.clear()
        assert out["code"] == 200, out
        assert out["obj"]["finish_reason"] == "length"
        assert router.wait_live(2, timeout_s=600), sup.describe()
    finally:
        faults.clear()
        router.stop()
        sup.shutdown()


# ----------------------------------------------------------- migration
def test_migration_gather_on_export_from_mesh(model_and_vars):
    """Gather-on-export: a parked prompt on a mesh-2 source exports
    the FULL-HEAD int8+scales wire payload (shards gathered on read),
    and a single-device destination installs it — the migrated request
    prefix-hits instead of re-prefilling. The wire format is
    mesh-blind."""
    model, variables = model_and_vars
    cfg = dataclasses.replace(SCFG, kv_block_size=4)
    src = Scheduler(ShardedEngine(model, variables, cfg,
                                  mesh_devices=2))
    dst = Scheduler(Engine(model, variables, cfg))
    prompt = PROMPTS[2]          # 9 tokens -> 2 full blocks of 4
    src.submit(Request(prompt=prompt, max_new_tokens=4,
                       request_id="mig", prefill_only=True))
    src.run_until_idle(max_iters=50)
    from nezha_tpu.serve import migrate
    tokens, layers, nbytes = migrate.decode_wire(
        src.export_parked("mig"))
    assert len(tokens) == 8 and layers[0]["k"].shape[0] == 2
    # Full heads on the wire regardless of the source mesh.
    assert layers[0]["k"].shape[1] == CFG["num_heads"]
    assert dst.install_migrated(tokens, layers, nbytes) == 2
    assert src.ack_parked("mig")
    hits0 = dst.engine.pool.prefix_hits
    dst.submit(Request(prompt=prompt, max_new_tokens=4,
                       request_id="mig"))
    dst.run_until_idle(max_iters=100)
    assert dst.engine.pool.prefix_hits == hits0 + 1
    src.engine.pool.leak_check()
    dst.engine.pool.leak_check()


# ------------------------------------------------- per-shard kernel
def test_flash_decode_sharded_matches_unsharded_kernel():
    """The nested-shard_map decode kernel (the sharded engine's TPU
    decode path) computes exactly the unsharded kernel's output:
    heads are embarrassingly parallel, so an H/tp slice per device
    with replicated lengths + block tables must be a pure reshard.
    Interpret mode stands in for Mosaic on CPU, same as the rest of
    the kernel parity suite."""
    from nezha_tpu.ops.pallas import (flash_decode_attention,
                                      flash_decode_attention_sharded)
    from nezha_tpu.parallel.mesh import make_mesh

    b, h, d, nblk, bs = 3, 4, 8, 9, 8
    key = jax.random.PRNGKey(0)
    kq, kk, kv2, ks = jax.random.split(key, 4)
    q = jax.random.normal(kq, (b, h, 1, d), jnp.float32)
    kp = jax.random.normal(kk, (nblk, h, bs, d), jnp.float32)
    vp = jax.random.normal(kv2, (nblk, h, bs, d), jnp.float32)
    tables = jax.random.randint(ks, (b, 4), 1, nblk).astype(jnp.int32)
    lengths = jnp.asarray([5, 0, 17], jnp.int32)
    # the pool is lane-dense rows [n, bs, H*D]: a shard is the lanes of
    # H / tp contiguous heads
    from nezha_tpu.ops.quant import merge_heads, quantize_kv_block
    ref = flash_decode_attention(q, merge_heads(kp), merge_heads(vp),
                                 lengths, block_tables=tables,
                                 interpret=True)
    mesh = make_mesh({"tp": 2}, devices=jax.devices()[:2])
    got = flash_decode_attention_sharded(q, merge_heads(kp),
                                         merge_heads(vp), lengths, mesh,
                                         block_tables=tables,
                                         interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)
    # Int8 pools: scale rows shard with their heads.
    kq8, ksc = quantize_kv_block(kp)
    vq8, vsc = quantize_kv_block(vp)
    kq8, vq8 = merge_heads(kq8), merge_heads(vq8)
    ref8 = flash_decode_attention(q, kq8, vq8, lengths,
                                  block_tables=tables,
                                  block_scales=(ksc, vsc),
                                  interpret=True)
    got8 = flash_decode_attention_sharded(q, kq8, vq8, lengths, mesh,
                                          block_tables=tables,
                                          block_scales=(ksc, vsc),
                                          interpret=True)
    np.testing.assert_allclose(np.asarray(got8), np.asarray(ref8),
                               rtol=1e-6, atol=1e-6)


# ----------------------------------------------------------- telemetry
def test_mesh_telemetry_capture_and_report(model_and_vars, tmp_path):
    """A mesh-2 serving run's capture is schema-clean and carries the
    new instruments; the rendered report gains the ``mesh:`` line."""
    from nezha_tpu.analysis.telemetry_schema import check_run_dir
    from nezha_tpu.obs.report import render_serving_section
    model, variables = model_and_vars
    run_dir = str(tmp_path / "run")
    obs.start_run(run_dir, meta={"kind": "serve_mesh_test"})
    try:
        eng = ShardedEngine(model, variables, SCFG, mesh_devices=2)
        _greedy(eng, PROMPTS[:2])
    finally:
        obs.end_run()
    assert check_run_dir(run_dir) == []
    with open(os.path.join(run_dir, "summary.json")) as f:
        summary = json.load(f)
    assert summary["gauges"]["serve.mesh.devices"] == 2
    assert summary["counters"]["serve.mesh.collective_bytes"] > 0
    lines = render_serving_section(summary)
    mesh_lines = [l for l in lines if l.strip().startswith("mesh:")]
    assert mesh_lines and "2 devices" in mesh_lines[0]
    # The reshard span is schema-pinned (emitted inside a run).
    from nezha_tpu.analysis.telemetry_schema import PINNED_SPANS
    assert "serve.reshard_s" in PINNED_SPANS
