"""Int8-quantized KV blocks (ISSUE 9): the shared ops/quant core, the
per-block-scaled int8 paged pool, in-kernel dequant, and the serving
invariants re-proven under ``kv_dtype="int8"``.

Covers the extracted quantization core (round-trip error bounds, the
all-zero scale guard, deterministic NaN/inf saturation, and a
bit-identity regression pin that the wire collectives survived the
extraction), greedy decode parity (int8 engine vs the f32 engine and
one-shot generate; flash-decode kernel vs the gathered XLA fallback;
h=1 vs h=8 bit-identity), copy-on-write carrying scales with blocks
(live donor re-hits an intact cache), the stale-KV reuse invariant with
POISONED int8 storage AND poisoned scale rows, eviction freeing scales
with their blocks, the serve.kv.quant_error / bytes_resident /
quant_bits telemetry pins, the worker-argv CLI passthrough, the bench
record's dtype/bytes fields, and a seeded chaos acceptance at horizon 4
asserting zero slot/block/scale leaks with the frozen program set.
"""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nezha_tpu import faults, obs
from nezha_tpu.models.generate import generate
from nezha_tpu.models.gpt2 import GPT2, GPT2Config
from nezha_tpu.ops import quant
from nezha_tpu.serve import (
    Engine,
    Request,
    Scheduler,
    ServeConfig,
)

CFG = dict(vocab_size=97, max_positions=64, num_layers=2, num_heads=4,
           hidden_size=64)
# Matches test_paged_kv.PCFG, with int8 KV blocks: block_size 4 so tiny
# prompts span real blocks (full-block prefix hits, COW, lazy growth,
# per-block requant all fire at test sizes).
QCFG = ServeConfig(max_batch_size=3, max_len=48, max_prefill_len=8,
                   prefill_buckets=(4, 8), k_max=16, queue_capacity=8,
                   cache_dtype=jnp.float32, kv_block_size=4,
                   kv_dtype="int8")
FCFG = dataclasses.replace(QCFG, kv_dtype="bf16")   # f32 blocks (cache_dtype)

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for sub in ("tools", "benchmarks"):
    p = os.path.join(_ROOT, sub)
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture(scope="module")
def model_and_vars():
    model = GPT2(GPT2Config(**CFG))
    return model, model.init(jax.random.PRNGKey(0))


def _drain(sched, max_iters=400):
    sched.run_until_idle(max_iters=max_iters)
    assert not sched.has_work(), "scheduler did not drain"


def _greedy_ref(model, variables, prompt, n):
    return np.asarray(generate(
        model, variables, np.asarray([prompt], np.int32),
        max_new_tokens=n, temperature=0.0,
        cache_dtype=jnp.float32))[0, len(prompt):].tolist()


def _run(model, variables, cfg, reqs):
    eng = Engine(model, variables, cfg)
    sched = Scheduler(eng)
    rids = [sched.submit(Request(**kw)) for kw in reqs]
    _drain(sched)
    return eng, sched, [sched.results[r].tokens for r in rids]


# ------------------------------------------------------ ops/quant core
def test_quant_roundtrip_error_bound():
    """Symmetric absmax int8: per-block round-trip error is bounded by
    half a quantization step (scale / 2 = amax / 254), no clipping
    error at the extremes (amax itself maps to exactly ±127)."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(6, 4, 8, 16)) * 3.0, jnp.float32)
    q, s = quant.quantize_kv_block(x)
    assert q.dtype == jnp.int8 and s.shape == (6, 4)
    deq = quant.dequantize_kv_block(q, s, jnp.float32)
    err = np.abs(np.asarray(x) - np.asarray(deq))
    bound = np.asarray(s)[..., None, None] * 0.5 * (1 + 1e-6)
    assert (err <= bound).all()
    # The histogram sample helper agrees with the direct computation.
    assert float(quant.kv_roundtrip_error(x)) == pytest.approx(
        float(err.max()), rel=1e-6)
    # amax elements survive exactly (no clip loss at the extremes).
    amax_pos = np.unravel_index(np.argmax(np.abs(np.asarray(x))),
                               x.shape)
    assert np.asarray(q)[amax_pos] in (-127, 127)


def test_quant_all_zero_block_scale_guard():
    """An all-zero block takes scale 1.0 (the shared guard): quantizes
    to exact zeros, dequantizes to exact zeros, no div-by-zero, no
    NaN — the state every freshly-allocated pool block starts in."""
    z = jnp.zeros((3, 2, 4, 8), jnp.float32)
    q, s = quant.quantize_kv_block(z)
    assert (np.asarray(s) == 1.0).all()
    assert (np.asarray(q) == 0).all()
    assert (np.asarray(quant.dequantize_kv_block(q, s)) == 0.0).all()
    assert float(quant.kv_roundtrip_error(z)) == 0.0
    # Wire layout shares the guard.
    qw, sw = quant.quantize_blocks(jnp.zeros((256,), jnp.float32), 64)
    assert (np.asarray(sw) == 1.0).all() and (np.asarray(qw) == 0).all()


def test_quant_nonfinite_inputs_saturate_deterministically():
    """NaN/±inf inputs (the PR-4 fault surface reaching a KV write)
    saturate deterministically — NaN -> 0, ±inf -> ±f32 max — and the
    outputs (including scales) are always finite; two calls agree
    bit-for-bit. A NaN must never become a NaN SCALE poisoning every
    other element of the block."""
    bad = jnp.asarray([[[np.nan, np.inf, -np.inf, 1.0],
                        [0.5, np.nan, -2.0, np.inf]]], jnp.float32)
    q1, s1 = quant.quantize_kv_block(bad)
    q2, s2 = quant.quantize_kv_block(bad)
    assert np.array_equal(np.asarray(q1), np.asarray(q2))
    assert np.array_equal(np.asarray(s1), np.asarray(s2))
    assert np.isfinite(np.asarray(s1)).all()
    san = np.asarray(quant.sanitize(bad))
    assert san[0, 0, 0] == 0.0                          # NaN -> 0
    assert san[0, 0, 1] == np.float32(quant.SATURATE_MAX)   # +inf
    assert san[0, 0, 2] == -np.float32(quant.SATURATE_MAX)  # -inf
    # The whole round trip stays finite (SATURATE_MAX sits far enough
    # below f32 max that 127 * (amax/127) cannot overflow).
    assert np.isfinite(np.asarray(quant.dequantize_kv_block(q1, s1))).all()
    assert np.isfinite(float(quant.kv_roundtrip_error(bad)))


@pytest.mark.parametrize("h,d", [(4, 16), (12, 64)], ids=["hd64", "hd768"])
@pytest.mark.parametrize("fill", ["normal", "zeros", "nonfinite"])
def test_row_layout_policy_is_the_tile_policy_bit_for_bit(fill, h, d):
    """The paged pool stores lane-dense rows ``[n, bs, H*D]`` (PR 27);
    ``quantize_kv_rows`` / ``dequantize_kv_rows`` are the per-head tile
    policy above (still the WIRE's form) over that layout: same int8
    values, same per-(block, head) scales, same dequantized values,
    through ``split_heads`` / ``merge_heads`` — at a width under one
    128-lane tile and at GPT-2's 768, zero blocks and non-finite inputs
    included."""
    rng = np.random.default_rng(1)
    tiles = rng.normal(size=(5, h, 8, d)).astype(np.float32) * 2.0
    if fill == "zeros":
        tiles[1:3] = 0.0
    elif fill == "nonfinite":
        tiles[0, 0, 0, :3] = (np.nan, np.inf, -np.inf)
    tiles = jnp.asarray(tiles)
    rows = quant.merge_heads(tiles)
    assert rows.shape == (5, 8, h * d)
    assert np.array_equal(np.asarray(quant.split_heads(rows, h)),
                          np.asarray(tiles), equal_nan=True)
    # head g of a position sits in lanes g*d .. (g+1)*d
    assert np.array_equal(np.asarray(rows)[:, :, d:2 * d],
                          np.asarray(tiles)[:, 1], equal_nan=True)
    qt, st = quant.quantize_kv_block(tiles)
    qr, sr = quant.quantize_kv_rows(rows, h)
    assert qr.dtype == jnp.int8 and sr.shape == (5, h)
    assert np.array_equal(np.asarray(qr), np.asarray(quant.merge_heads(qt)))
    assert np.array_equal(np.asarray(sr), np.asarray(st))
    for dtype in (jnp.float32, jnp.bfloat16):
        assert np.array_equal(
            np.asarray(quant.dequantize_kv_rows(qr, sr, dtype), np.float32),
            np.asarray(quant.merge_heads(
                quant.dequantize_kv_block(qt, st, dtype)), np.float32))


def test_wire_collectives_bit_identical_after_extraction():
    """The regression pin ISSUE 9 demands: parallel/quantized.py's
    quantize/dequantize (now imported from ops/quant.py) must be
    BIT-IDENTICAL to the pre-extraction in-module implementation —
    re-derived here as golden code copied from the PR-1 source."""
    from nezha_tpu.parallel import quantized as wire

    def golden_quantize_blocks(x, block):
        xb = x.reshape(*x.shape[:-1], x.shape[-1] // block, block)
        amax = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
        scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(
            jnp.float32)
        q = jnp.clip(jnp.round(xb / scale), -127.0, 127.0).astype(
            jnp.int8)
        return q, scale

    rng = np.random.default_rng(7)
    for shape, block in (((2048,), 512), ((4, 768), 256), ((640,), 64)):
        x = jnp.asarray(rng.normal(size=shape) * 10, jnp.float32)
        q_new, s_new = wire._quantize_blocks(x, block)
        q_old, s_old = golden_quantize_blocks(x, block)
        assert np.array_equal(np.asarray(q_new), np.asarray(q_old))
        assert np.array_equal(np.asarray(s_new), np.asarray(s_old))
        assert np.array_equal(
            np.asarray(wire._dequantize(q_new, s_new)),
            np.asarray(q_old.astype(jnp.float32) * s_old))
        # And the public round-trip (the single-hop wire error probe).
        rt = wire.quantize_roundtrip(x, block)
        q, s = golden_quantize_blocks(
            jnp.pad(x.reshape(-1), (0, (-x.size) % block)), block)
        golden_rt = (q.astype(jnp.float32) * s).reshape(-1)[
            :x.size].reshape(x.shape)
        assert np.array_equal(np.asarray(rt), np.asarray(golden_rt))


# --------------------------------------------------------- pool layer
def test_quant_pool_scales_move_with_blocks(model_and_vars):
    """The single invariant: a block and its scale row move, ref-count,
    evict, and free together — scales are block-indexed leaves of the
    same caches pytree, so COW copies them and leak_check's structure
    oracle catches a caches tree rebuilt without them."""
    from nezha_tpu.serve import PagedSlotPool
    model, _ = model_and_vars
    pool = PagedSlotPool(model, capacity=2, max_len=16,
                         dtype=jnp.float32, block_size=4,
                         quantized=True)
    assert pool.quantized
    for layer in pool.caches:
        assert layer["k"].dtype == jnp.int8
        assert layer["k_scale"].shape == (pool.num_blocks,
                                          model.cfg.num_heads)
    # int8 block footprint ~ half of f32's quarter... compare against
    # the unquantized pool: f32 block = 4 bytes/elt, int8 = 1 + scales.
    dense = PagedSlotPool(model, capacity=2, max_len=16,
                          dtype=jnp.float32, block_size=4)
    assert pool.bytes_per_block < dense.bytes_per_block / 3
    s = pool.alloc()
    pool.bind_for_prompt(s, [1, 2, 3, 4, 5])
    pool.prepare_write(s, 0, 8)
    # Stamp block b0's scale row, COW-copy it, check the copy carried.
    b0 = int(pool.tables_host[s, 0])
    pool.caches = [dict(layer, k_scale=layer["k_scale"].at[b0].set(7.5))
                   for layer in pool.caches]
    pool._refs[b0] += 1                     # simulate a second holder
    pool.prepare_write(s, 0, 4)             # -> COW of b0
    nb = int(pool.tables_host[s, 0])
    assert nb != b0
    assert float(pool.caches[0]["k_scale"][nb, 0]) == 7.5
    pool._refs[b0] -= 1
    pool._free_blocks.append(b0) if pool._refs[b0] == 0 else None
    pool.leak_check()
    # Structure oracle: dropping a scale leaf is caught.
    broken = [{k: v for k, v in layer.items() if k != "v_scale"}
              for layer in pool.caches]
    good = pool.caches
    pool.caches = broken
    with pytest.raises(AssertionError, match="v_scale"):
        pool.leak_check()
    pool.caches = good
    pool.free(s)
    pool.leak_check()


# ------------------------------------------------------ engine parity
def test_int8_engine_greedy_parity_and_frozen_programs(model_and_vars):
    """Greedy, sampled, and chunked requests decode token-identically
    on the int8 and f32 engines (the tiny model's logit gaps dominate
    the bounded quant error — deterministic, pinned), greedy matches
    one-shot generate(), and the frozen program contract holds."""
    model, variables = model_and_vars
    reqs = [dict(prompt=[5, 17, 3, 42], max_new_tokens=10),
            dict(prompt=[7, 7], max_new_tokens=9, temperature=0.9,
                 top_k=10, seed=7),
            dict(prompt=[(7 * i + 3) % 97 for i in range(20)],
                 max_new_tokens=6)]
    eng_f, _, out_f = _run(model, variables, FCFG, reqs)
    eng_q, _, out_q = _run(model, variables, QCFG, reqs)
    assert out_q == out_f
    assert out_q[0] == _greedy_ref(model, variables,
                                   reqs[0]["prompt"], 10)
    assert out_q[2] == _greedy_ref(model, variables,
                                   reqs[2]["prompt"], 6)
    stats = eng_q.compile_stats()
    assert stats["entries"] == stats["misses"] == \
        1 + len(QCFG.prefill_buckets)
    eng_q.pool.leak_check()
    # bytes_resident reflects the narrow storage: at identical block
    # counts the int8 pool's resident bytes are < 1/3 of the f32
    # pool's (int8+scales vs 4-byte elements).
    assert eng_q.pool.bytes_per_block < eng_f.pool.bytes_per_block / 3


def test_int8_kernel_vs_xla_fallback_parity(model_and_vars):
    """decode_impl='kernel' (in-loop dequant) and 'xla' (gathered
    dequant) produce identical tokens: both apply the SAME dequant
    expression, so the escape hatch stays valid for the int8 cache."""
    model, variables = model_and_vars
    reqs = [dict(prompt=[5, 17, 3, 42], max_new_tokens=10),
            dict(prompt=[7, 7], max_new_tokens=9, temperature=0.9,
                 top_k=10, seed=7),
            dict(prompt=[(7 * i + 3) % 97 for i in range(20)],
                 max_new_tokens=6)]
    _, _, out_k = _run(model, variables,
                       dataclasses.replace(QCFG, decode_impl="kernel"),
                       reqs)
    _, _, out_x = _run(model, variables,
                       dataclasses.replace(QCFG, decode_impl="xla"),
                       reqs)
    assert out_k == out_x


def test_int8_horizon_bit_identity(model_and_vars):
    """h=1 vs h=8 bit-identity survives quantization: the per-step
    block requant depends only on (pool state, new row), which is the
    same sequence of writes whatever the horizon."""
    model, variables = model_and_vars
    reqs = [dict(prompt=[5, 17, 3, 42], max_new_tokens=10),
            dict(prompt=[9, 1], max_new_tokens=12, temperature=0.8,
                 top_k=12, seed=3)]
    _, _, o1 = _run(model, variables,
                    dataclasses.replace(QCFG, decode_horizon=1), reqs)
    _, _, o8 = _run(model, variables,
                    dataclasses.replace(QCFG, decode_horizon=8), reqs)
    assert o1 == o8


def test_int8_cow_preserves_donor_cache(model_and_vars):
    """COW carries scales: an exactly-block-aligned full-prefix hit
    writes into its last shared block (COWed first); the donor's
    cached block AND scale row stay intact — a third identical request
    re-hits the cache and still decodes identically."""
    model, variables = model_and_vars
    prompt = [(5 * i + 11) % 97 for i in range(12)]   # exactly 3 blocks
    eng = Engine(model, variables, QCFG)
    sched = Scheduler(eng)
    ref = _greedy_ref(model, variables, prompt, 6)
    a = sched.submit(Request(prompt=prompt, max_new_tokens=6))
    _drain(sched)
    assert sched.results[a].tokens == ref
    b = sched.submit(Request(prompt=prompt, max_new_tokens=6))
    c = sched.submit(Request(prompt=prompt, max_new_tokens=6))
    _drain(sched)
    assert eng.pool.prefix_hits == 2 and eng.pool.cow_copies >= 2
    assert sched.results[b].tokens == ref
    assert sched.results[c].tokens == ref
    eng.pool.leak_check()


def test_int8_stale_kv_and_stale_scales_never_attendable(
        model_and_vars):
    """The stale-KV reuse invariant extended to scales: retire a
    request, poison every FREED block's int8 content with ±127 and its
    scale rows with a huge sentinel (1e3), then serve a new request
    through the same storage — its tokens must match a clean-engine
    reference exactly. This covers both failure modes quantization
    adds: attending a stale position (huge dequantized value skews
    logits) and folding stale content into a fresh block's absmax (a
    1e3-scaled garbage entry entering the requant window would crush
    the real entries' precision)."""
    model, variables = model_and_vars
    cfg = dataclasses.replace(QCFG, prefix_cache=False)
    eng = Engine(model, variables, cfg)
    sched = Scheduler(eng)
    first = sched.submit(Request(
        prompt=[(7 * i + 1) % 97 for i in range(20)], max_new_tokens=8))
    _drain(sched)
    assert sched.results[first].finish_reason == "length"
    idx = jnp.asarray(sorted(eng.pool._free_blocks), jnp.int32)
    eng.pool.caches = [
        {"k": layer["k"].at[idx].set(127),
         "v": layer["v"].at[idx].set(-127),
         "k_scale": layer["k_scale"].at[idx].set(1.0e3),
         "v_scale": layer["v_scale"].at[idx].set(1.0e3)}
        for layer in eng.pool.caches]
    prompt2 = [9, 8, 7, 6, 5]
    second = sched.submit(Request(prompt=prompt2, max_new_tokens=8))
    _drain(sched)
    res = sched.results[second]
    assert res.finish_reason == "length", res.error
    assert res.tokens == _greedy_ref(model, variables, prompt2, 8)
    eng.pool.leak_check()


def test_int8_eviction_frees_scales_with_blocks(model_and_vars):
    """Eviction under pressure works on the quantized pool, and
    clearing the prefix cache leaves ZERO blocks resident — the
    eviction-frees-scales oracle (scales share the block index, so a
    freed block's scale row is recycled with it; leak_check's
    structure oracle confirms no path dropped the buffers)."""
    model, variables = model_and_vars
    cfg = dataclasses.replace(QCFG, max_batch_size=1, kv_num_blocks=8)
    eng = Engine(model, variables, cfg)
    sched = Scheduler(eng)
    p1 = [(3 * i + 2) % 97 for i in range(12)]       # 3 full blocks
    sched.submit(Request(prompt=p1, max_new_tokens=4))
    _drain(sched)
    assert len(eng.pool.trie) == 3
    p2 = [(5 * i + 1) % 97 for i in range(20)]
    r = sched.submit(Request(prompt=p2, max_new_tokens=3))
    _drain(sched)
    assert sched.results[r].finish_reason == "length"
    assert len(eng.pool.trie) < 3 + 5    # eviction happened
    eng.pool.leak_check()
    eng.pool.clear_prefix_cache()
    eng.pool.leak_check()
    assert eng.pool.blocks_used == 0
    assert eng.pool.bytes_resident == 0


# ------------------------------------------------- telemetry + chaos
def test_int8_chaos_zero_leaks_frozen_programs_schema(model_and_vars,
                                                      tmp_path):
    """The PR-7 chaos acceptance re-run on the int8 pool at horizon 4:
    seeded prefill errors + NaN bursts + kv.bind failures over 16
    templated requests (prefix hits + COW + per-block requant in
    play). Every request gets exactly one result, zero slot AND block
    leaks (scale oracle included), frozen program set, and the run-dir
    artifacts pass the pinned schema including serve.kv.quant_error /
    bytes_resident / quant_bits; the report labels the dtype."""
    model, variables = model_and_vars
    run_dir = str(tmp_path / "chaos_int8")
    obs.start_run(run_dir, meta={"kind": "chaos_int8"})
    try:
        cfg = dataclasses.replace(QCFG, decode_horizon=4,
                                  queue_capacity=16)
        eng = Engine(model, variables, cfg)
        sched = Scheduler(eng)
        faults.install(faults.FaultPlan.parse(
            "serve.prefill:error%0.08;serve.step.logits:nan%0.05;"
            "serve.kv.bind:error%0.03", seed=7))
        try:
            prefix = [(3 * i + 5) % 97 for i in range(8)]
            rids = []
            for i in range(16):
                prompt = (prefix + [i % 97, (2 * i) % 97]
                          if i % 2 else
                          [(11 * i + j) % 97 for j in range(6)])
                rids.append(sched.submit(Request(
                    prompt=prompt, max_new_tokens=6,
                    temperature=0.8 if i % 3 == 0 else 0.0,
                    top_k=10 if i % 3 == 0 else None, seed=i,
                    request_id=f"c{i}")))
            _drain(sched)
        finally:
            faults.clear()
        assert set(rids) <= set(sched.results)
        reasons = {sched.results[r].finish_reason for r in rids}
        assert reasons <= {"length", "error"}
        assert eng.pool.num_free == cfg.max_batch_size
        eng.pool.leak_check()
        stats = eng.compile_stats()
        assert stats["entries"] == stats["misses"] == \
            1 + len(cfg.prefill_buckets)
        eng.pool.clear_prefix_cache()
        eng.pool.leak_check()
        assert eng.pool.blocks_used == 0
        # Quant error was sampled at prefill writes and is bounded
        # (the tiny model's activations are O(10); a huge p-max would
        # mean a stale block's garbage entered a requant window).
        h = obs.histogram("serve.kv.quant_error").summary()
        assert h["count"] > 0
        assert 0 <= h["max"] < 10.0
    finally:
        obs.end_run()
    from check_telemetry_schema import check_run_dir
    assert check_run_dir(run_dir) == []
    with open(os.path.join(run_dir, "summary.json")) as f:
        summary = json.load(f)
    assert "serve.kv.quant_error" in summary["histograms"]
    assert "serve.kv.bytes_resident" in summary["gauges"]
    assert summary["gauges"]["serve.kv.quant_bits"] == 8
    # Dropping a quant instrument must FAIL the pinned schema.
    del summary["histograms"]["serve.kv.quant_error"]
    with open(os.path.join(run_dir, "summary.json"), "w") as f:
        json.dump(summary, f)
    assert any("serve.kv.quant_error" in e for e in check_run_dir(run_dir))
    summary["histograms"]["serve.kv.quant_error"] = dict(
        count=1, sum=0.01, min=0.01, max=0.01, mean=0.01, p50=0.01,
        p90=0.01, p99=0.01)
    with open(os.path.join(run_dir, "summary.json"), "w") as f:
        json.dump(summary, f)
    from nezha_tpu.obs.report import render_report
    report = render_report(run_dir)
    assert "dtype int8" in report and "quant err p99" in report


def test_bf16_run_reports_quant_schema_with_zeros(model_and_vars,
                                                 tmp_path):
    """Layout/dtype-invariant schema: a DEFAULT (bf16) serving run
    still carries the quant instruments — quant_bits reports the
    storage width, quant_error stays empty, and the report renders the
    dtype label without a quant-error clause."""
    model, variables = model_and_vars
    run_dir = str(tmp_path / "bf16_run")
    obs.start_run(run_dir, meta={"kind": "serve"})
    try:
        eng = Engine(model, variables, FCFG)
        sched = Scheduler(eng)
        sched.submit(Request(prompt=[1, 2, 3], max_new_tokens=2))
        _drain(sched)
        assert obs.histogram("serve.kv.quant_error").summary()[
            "count"] == 0
    finally:
        obs.end_run()
    from check_telemetry_schema import check_run_dir
    assert check_run_dir(run_dir) == []
    with open(os.path.join(run_dir, "summary.json")) as f:
        summary = json.load(f)
    assert summary["gauges"]["serve.kv.quant_bits"] == 32  # f32 pool
    from nezha_tpu.obs.report import render_report
    report = render_report(run_dir)
    assert "dtype f32" in report and "quant err" not in report


# ------------------------------------------------------- CLI + bench
def test_serve_cli_kv_dtype_passthrough():
    """--kv-dtype reaches ServeConfig and the spawned worker argv."""
    from nezha_tpu.cli.serve import _worker_argv, build_parser
    args = build_parser().parse_args(
        ["--random-init", "--kv-dtype", "int8", "--http", "8000",
         "--replicas", "2"])
    assert args.kv_dtype == "int8"
    argv = _worker_argv(args, 0, 9000)
    i = argv.index("--kv-dtype")
    assert argv[i + 1] == "int8"
    # Default stays bf16 (the bit-identical path).
    args2 = build_parser().parse_args(["--random-init"])
    assert args2.kv_dtype == "bf16"


def test_serving_benchmark_kv_dtype_record(tmp_path):
    """benchmarks/serving.py --kv-dtype int8: the record carries the
    dtype and byte accounting (bytes_per_block, peak_bytes_resident),
    requests finish cleanly, and the artifacts pass the pinned
    schema."""
    import serving as bench

    run_dir = str(tmp_path / "int8_bench")
    rec = bench.run(bench.build_parser().parse_args(
        ["--requests", "6", "--concurrency", "3", "--max-new-tokens",
         "4", "--max-batch-size", "3", "--max-len", "48",
         "--max-prefill-len", "8", "--kv-block-size", "4",
         "--kv-dtype", "int8", "--run-dir", run_dir]))
    assert rec["finished"] == 6
    assert rec["kv"]["dtype"] == "int8"
    assert rec["kv"]["bytes_per_block"] > 0
    assert rec["kv"]["peak_bytes_resident"] >= \
        rec["kv"]["peak_blocks_used"] * rec["kv"]["bytes_per_block"] > 0
    from check_telemetry_schema import check_run_dir
    assert check_run_dir(run_dir) == []

    rec_b = bench.run(bench.build_parser().parse_args(
        ["--requests", "4", "--concurrency", "2", "--max-new-tokens",
         "2", "--max-batch-size", "2", "--max-len", "32",
         "--max-prefill-len", "8", "--kv-block-size", "4"]))
    assert rec_b["kv"]["dtype"] == "bf16"
    # Same block geometry: int8 blocks cost a fraction of bf16's.
    assert rec["kv"]["bytes_per_block"] < rec_b["kv"]["bytes_per_block"]


def test_serveconfig_kv_dtype_validation():
    with pytest.raises(ValueError, match="kv_dtype"):
        ServeConfig(kv_dtype="fp4")
    with pytest.raises(TypeError, match="kv_layout"):
        ServeConfig(kv_layout="paged", kv_dtype="int8")
