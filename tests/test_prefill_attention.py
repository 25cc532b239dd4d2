"""Paged flash-prefill kernel (ISSUE 18): the Pallas chunked-prefill
attention that reads K/V through the block table with per-row start
offsets, and — on int8 pools — fuses the block write (fresh per-(block,
head) scales, stale-position zeroing) into the kernel epilogue in place
of the ``_quant_prefill_write`` gather/requant round-trip.

Pins, per the acceptance list:

- kernel vs the composed masked reference within 1e-5 (f32 and bf16
  inputs), including nonzero per-row starts (chunked continuation and
  shared-prefix partial prefills) — ONE program shape for all of them;
- int8 fused writes bit-identical to the ``quantize_kv_block`` policy
  (merged old-prefix/fresh-chunk content, sanitize, fresh scales), the
  in-kernel qerr equal to the reference max-abs dequant error, and
  over-cover table entries routed to the scratch block untouched;
- the nested-shard_map variant at mesh 2 matches unsharded bitwise;
- engine end-to-end: greedy tokens bit-identical between
  ``prefill_impl="kernel"`` and ``"xla"`` (bf16 cache and int8 pool,
  chunked + prefix-hit traffic), frozen ``1 + len(prefill_buckets)``
  program contract re-pinned per (mesh, dtype);
- the int8 kernel program lowers STRICTLY fewer scatters than the
  ``_quant_prefill_write`` chain (the fused write removes the
  per-layer gather/requant/scatter round-trip);
- chaos re-run (prefill faults + NaN bursts) on the kernel path with
  zero slot/block/scale leaks, and the new telemetry (kernel span,
  fused-write counter, kernel-active gauge) captured schema-clean.
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
from nezha_tpu.models.gpt2 import GPT2, GPT2Config
from nezha_tpu.ops import quant
from nezha_tpu.ops.pallas import (
    flash_prefill_attention,
    flash_prefill_attention_sharded,
)
from nezha_tpu.serve import Engine, Request, Scheduler, ServeConfig

CFG = dict(vocab_size=97, max_positions=64, num_layers=2, num_heads=4,
           hidden_size=64)
# kv_block_size 4 so the 12-token prompt spans real blocks: full-block
# prefix hits and mid-block continuation starts both fire at test sizes.
PCFG = ServeConfig(max_batch_size=3, max_len=48, max_prefill_len=8,
                   prefill_buckets=(4, 8), k_max=16, queue_capacity=8,
                   cache_dtype=jnp.float32, kv_block_size=4)
LONG = [5, 17, 3, 9, 11, 2, 7, 23, 41, 8, 1, 13]     # > max_prefill_len
PROMPTS = (LONG, [1, 2, 3], LONG)                    # 3rd = prefix hit

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _sub in ("tools",):
    _p = os.path.join(_ROOT, _sub)
    if _p not in sys.path:
        sys.path.insert(0, _p)


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
def engines(model_and_vars):
    """The four engines of the parity matrix — built once, reused by
    the parity, program-contract, and scatter-count pins (the frozen
    program set is the property that makes sharing safe)."""
    model, variables = model_and_vars
    out = {}
    for name, kw in (("bf16", dict(cache_dtype=jnp.bfloat16)),
                     ("int8", dict(kv_dtype="int8"))):
        for impl in ("kernel", "xla"):
            cfg = dataclasses.replace(PCFG, prefill_impl=impl, **kw)
            out[name, impl] = Engine(model, variables, cfg)
    return out


def _greedy(engine, prompts=PROMPTS, max_new=6):
    """Serial submit+drain so the repeated prompt takes a prefix hit."""
    sched = Scheduler(engine)
    outs = []
    for i, p in enumerate(prompts):
        rid = sched.submit(Request(prompt=list(p), max_new_tokens=max_new,
                                   request_id=f"r{i}"))
        sched.run_until_idle(max_iters=300)
        outs.append(list(sched.results[rid].tokens))
    return outs


# --------------------------------------------------- kernel-level refs
# The references below think in per-head tiles ``[n, H, bs, D]`` (the
# wire's form); the pool the kernels take is lane-dense rows
# ``[n, bs, H*D]``. ONE pair of helpers converts, the library's own.
def _rows(tiles, dtype=None):
    """Per-head tiles ``[n, H, bs, D]`` -> the pool's rows."""
    return quant.merge_heads(jnp.asarray(tiles, dtype))


def _tiles(rows, h):
    """The pool's rows -> per-head tiles (numpy)."""
    return np.asarray(quant.split_heads(jnp.asarray(rows), h))


# (heads, head_dim): 64 lanes (under one 128-lane tile: a step holds all
# heads) and GPT-2's 768 (a step holds the two heads of a lane block).
WIDTHS = [(4, 16), (12, 64)]
WIDTH_IDS = ["hd64", "hd768"]


def _ref_attn(q, k_all, v_all, starts, s_chunk):
    """Dense masked reference: rows attend their pool prefix plus the
    causal part of their own chunk."""
    b = q.shape[0]
    outs = []
    for i in range(b):
        st = int(starts[i])
        ln = st + s_chunk
        k, v = k_all[i][:, :ln], v_all[i][:, :ln]
        s = np.einsum("hsd,hld->hsl", q[i], k) / np.sqrt(q.shape[-1])
        qpos = st + np.arange(s_chunk)
        mask = np.arange(ln)[None, :] <= qpos[:, None]
        s = np.where(mask[None], s, -1e30)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        outs.append(np.einsum("hsl,hld->hsd", p, v))
    return np.stack(outs)


def _case(rng, starts, *, b=2, h=4, d=16, bs=8, m=12, s_chunk=16,
          extra_blocks=0):
    """One kernel test case: per-row tables covering start+chunk (plus
    ``extra_blocks`` over-cover entries past the write window), float
    pools, and a fresh chunk."""
    n = 2 + sum((int(st) + s_chunk + bs - 1) // bs + extra_blocks
                for st in starts)
    pool_k = rng.randn(n, h, bs, d).astype(np.float32)
    pool_v = rng.randn(n, h, bs, d).astype(np.float32)
    tab = np.zeros((b, m), np.int32)
    used = 1
    for i in range(b):
        need = (int(starts[i]) + s_chunk + bs - 1) // bs + extra_blocks
        assert need <= m
        for j in range(need):
            tab[i, j] = used
            used += 1
    q = rng.randn(b, h, s_chunk, d).astype(np.float32)
    kc = rng.randn(b, h, s_chunk, d).astype(np.float32)
    vc = rng.randn(b, h, s_chunk, d).astype(np.float32)
    return q, kc, vc, pool_k, pool_v, tab


def _gather(pool_k, pool_v, tab, starts, kc, vc, bs, m, s_chunk,
            scales=None):
    """Dense [B,H,L,D] views: pool prefix (dequantized when ``scales``)
    then the fresh chunk at each row's start."""
    b, h, _, d = kc.shape
    k_all = np.zeros((b, h, m * bs, d), np.float32)
    v_all = np.zeros_like(k_all)
    for i in range(b):
        st = int(starts[i])
        for p_ in range(st):
            blk, off = tab[i, p_ // bs], p_ % bs
            kr = pool_k[blk, :, off].astype(np.float32)
            vr = pool_v[blk, :, off].astype(np.float32)
            if scales is not None:
                kr = kr * scales[0][blk][:, None]
                vr = vr * scales[1][blk][:, None]
            k_all[i, :, p_] = kr
            v_all[i, :, p_] = vr
        for j in range(s_chunk):
            k_all[i, :, st + j] = kc[i, :, j]
            v_all[i, :, st + j] = vc[i, :, j]
    return k_all, v_all


@pytest.mark.parametrize("h,d", WIDTHS, ids=WIDTH_IDS)
@pytest.mark.parametrize("starts", [(0, 0), (8, 24), (5, 13)],
                         ids=["cold", "block-aligned", "mid-block"])
def test_kernel_matches_masked_reference_f32(starts, h, d):
    """One compiled shape serves cold prefills, chunked continuations
    (block-aligned starts), and shared-prefix partial prefills
    (mid-block starts) — all within 1e-5 of the dense masked path,
    at a pool width under one lane tile and at GPT-2's."""
    rng = np.random.RandomState(0)
    bs, m, s_chunk = 8, 12, 16
    q, kc, vc, pk, pv, tab = _case(rng, starts, h=h, d=d, bs=bs, m=m,
                                   s_chunk=s_chunk)
    out = flash_prefill_attention(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
        _rows(pk), _rows(pv), jnp.asarray(tab),
        jnp.asarray(starts, jnp.int32), interpret=True)
    k_all, v_all = _gather(pk, pv, tab, starts, kc, vc, bs, m, s_chunk)
    ref = _ref_attn(q, k_all, v_all, starts, s_chunk)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5)


def test_kernel_matches_masked_reference_bf16():
    """bf16 chunk + bf16 pool (the engine's bf16 cache layout): the
    kernel attends the same bf16-cast values the composed
    gather-after-write path sees, f32 accumulation, within 1e-5 of a
    reference computed from those cast values."""
    rng = np.random.RandomState(1)
    starts, bs, m, s_chunk = (8, 24), 8, 12, 16
    q, kc, vc, pk, pv, tab = _case(rng, starts, bs=bs, m=m,
                                   s_chunk=s_chunk)
    to_bf = lambda x: jnp.asarray(x, jnp.bfloat16)
    back = lambda x: np.asarray(jnp.asarray(to_bf(x), jnp.float32))
    out = flash_prefill_attention(
        to_bf(q), to_bf(kc), to_bf(vc), _rows(pk, jnp.bfloat16),
        _rows(pv, jnp.bfloat16), jnp.asarray(tab),
        jnp.asarray(starts, jnp.int32), interpret=True)
    k_all, v_all = _gather(back(pk), back(pv), tab, starts, back(kc),
                           back(vc), bs, m, s_chunk)
    ref = _ref_attn(back(q), k_all, v_all, starts, s_chunk)
    # The shared softmax core rounds probabilities to v.dtype (bf16)
    # exactly like the decode/flash kernels — the f32 reference can
    # only match to bf16 resolution; the ≤1e-5 acceptance is pinned by
    # the f32 kernel-vs-masked test above and by the engine's bf16
    # BIT-parity (kernel and composed path see the same cast values).
    np.testing.assert_allclose(
        np.asarray(jnp.asarray(out, jnp.float32)), ref, atol=2e-2)


@pytest.mark.parametrize("h,d", WIDTHS, ids=WIDTH_IDS)
@pytest.mark.parametrize("starts", [(0, 0), (5, 13)],
                         ids=["cold", "mid-block"])
def test_int8_fused_write_matches_quant_policy(starts, h, d):
    """The epilogue write IS ``_quant_prefill_write``: merged
    old-prefix/fresh-chunk rows, stale positions zeroed, sanitize,
    fresh per-(block, head) scales via the exact ``quantize_kv_block``
    policy — int8 pools bit-identical to the reference, scales to
    float tolerance, qerr equal to the reference max-abs dequant
    error and bounded by ``kv_roundtrip_error`` per merged block.
    Over-cover table entries (blocks past the write window) and the
    untouched rest of the pool come back byte-identical; scratch
    block 0 is zeroed with unit scales."""
    rng = np.random.RandomState(2)
    bs, m, s_chunk = 8, 12, 16
    q, kc, vc, pk_f, pv_f, tab = _case(rng, starts, h=h, d=d, bs=bs, m=m,
                                       s_chunk=s_chunk, extra_blocks=1)
    pk = rng.randint(-127, 128, pk_f.shape).astype(np.int8)
    pv = rng.randint(-127, 128, pv_f.shape).astype(np.int8)
    ks = (np.abs(rng.randn(*pk.shape[:2])) * 0.02 + 0.01).astype(
        np.float32)
    vs = (np.abs(rng.randn(*pv.shape[:2])) * 0.02 + 0.01).astype(
        np.float32)
    out, kp_n, vp_n, ks_n, vs_n, qerr = flash_prefill_attention(
        jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc),
        _rows(pk), _rows(pv), jnp.asarray(tab),
        jnp.asarray(starts, jnp.int32),
        block_scales=(jnp.asarray(ks), jnp.asarray(vs)), interpret=True)
    kp_n, vp_n = _tiles(kp_n, h), _tiles(vp_n, h)
    ks_n, vs_n = np.asarray(ks_n), np.asarray(vs_n)

    exp_kp, exp_vp = pk.copy(), pv.copy()
    exp_ks, exp_vs = ks.copy(), vs.copy()
    exp_kp[0] = 0
    exp_vp[0] = 0
    exp_ks[0] = 1.0
    exp_vs[0] = 1.0
    maxerr, rt_bound = 0.0, 0.0
    for i in range(len(starts)):
        st = int(starts[i])
        for t in range(st // bs, (st + s_chunk - 1) // bs + 1):
            blk = tab[i, t]
            wpos = t * bs + np.arange(bs)
            for pool, sc, ch, exp_p, exp_s in (
                    (pk, ks, kc, exp_kp, exp_ks),
                    (pv, vs, vc, exp_vp, exp_vs)):
                old = pool[blk].astype(np.float32) * sc[blk][:, None,
                                                            None]
                merged = np.zeros_like(old)
                for r in range(bs):
                    if wpos[r] < st:
                        merged[:, r] = old[:, r]
                    elif wpos[r] < st + s_chunk:
                        merged[:, r] = ch[i, :, wpos[r] - st]
                qn, sn = quant.quantize_kv_block(jnp.asarray(merged))
                exp_p[blk] = np.asarray(qn)
                exp_s[blk] = np.asarray(sn)
                deq = (np.asarray(qn).astype(np.float32)
                       * np.asarray(sn)[:, None, None])
                live = wpos < st + s_chunk
                maxerr = max(maxerr,
                             float(np.max(np.abs(merged - deq)[:, live])))
                rt_bound = max(rt_bound, float(
                    quant.kv_roundtrip_error(jnp.asarray(merged))))
    assert np.array_equal(kp_n, exp_kp)
    assert np.array_equal(vp_n, exp_vp)
    np.testing.assert_allclose(ks_n, exp_ks, rtol=1e-6)
    np.testing.assert_allclose(vs_n, exp_vs, rtol=1e-6)
    assert abs(float(qerr) - maxerr) < 1e-6
    assert float(qerr) <= rt_bound + 1e-6
    # Attention over the dequantized prefix + fresh chunk.
    k_all, v_all = _gather(pk, pv, tab, starts, kc, vc, bs, m, s_chunk,
                           scales=(ks, vs))
    ref = _ref_attn(q, k_all, v_all, starts, s_chunk)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5)


def test_sharded_mesh2_matches_unsharded():
    """The nested-shard_map variant (the sharded engine's path) is a
    pure reshard: attention equal to tolerance, int8 pools + scales
    BITWISE equal, qerr identical (pmax over head shards)."""
    from nezha_tpu.parallel.mesh import make_mesh

    rng = np.random.RandomState(3)
    starts, bs, m, s_chunk = (5, 13), 8, 12, 16
    q, kc, vc, pk, pv, tab = _case(rng, starts, bs=bs, m=m,
                                   s_chunk=s_chunk)
    mesh = make_mesh({"tp": 2}, devices=jax.devices()[:2])
    args = (jnp.asarray(q), jnp.asarray(kc), jnp.asarray(vc))
    ref = flash_prefill_attention(
        *args, _rows(pk), _rows(pv), jnp.asarray(tab),
        jnp.asarray(starts, jnp.int32), interpret=True)
    got = flash_prefill_attention_sharded(
        *args, _rows(pk), _rows(pv), jnp.asarray(tab),
        jnp.asarray(starts, jnp.int32), mesh, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)

    pk8 = rng.randint(-127, 128, pk.shape).astype(np.int8)
    pv8 = rng.randint(-127, 128, pv.shape).astype(np.int8)
    ks = (np.abs(rng.randn(*pk.shape[:2])) * 0.02 + 0.01).astype(
        np.float32)
    vs = (np.abs(rng.randn(*pv.shape[:2])) * 0.02 + 0.01).astype(
        np.float32)
    q8 = (_rows(pk8), _rows(pv8), jnp.asarray(tab),
          jnp.asarray(starts, jnp.int32))
    scales = (jnp.asarray(ks), jnp.asarray(vs))
    ref8 = flash_prefill_attention(*args, *q8, block_scales=scales,
                                   interpret=True)
    got8 = flash_prefill_attention_sharded(*args, *q8, mesh,
                                           block_scales=scales,
                                           interpret=True)
    np.testing.assert_allclose(np.asarray(got8[0]), np.asarray(ref8[0]),
                               rtol=1e-6, atol=1e-6)
    for a, b in zip(got8[1:5], ref8[1:5]):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert float(got8[5]) == float(ref8[5])


# ----------------------------------------------- q_offsets (PR 20)
@pytest.mark.parametrize("starts", [(0, 0), (8, 24), (5, 13)],
                         ids=["cold", "block-aligned", "mid-block"])
def test_q_offsets_full_chunk_bitwise_default(starts):
    """``q_offsets=starts`` with a full-width query slab is the same
    computation as the legacy two-prefetch program — output BITWISE
    equal (the sequence-sharded engine's parity guarantee bottoms out
    here: a shard seeing the whole chunk reproduces the replicated
    path exactly)."""
    rng = np.random.RandomState(5)
    bs, m, s_chunk = 8, 12, 16
    q, kc, vc, pk, pv, tab = _case(rng, starts, bs=bs, m=m,
                                   s_chunk=s_chunk)
    A, st32 = jnp.asarray, jnp.asarray(starts, jnp.int32)
    ref = flash_prefill_attention(A(q), A(kc), A(vc), _rows(pk),
                                  _rows(pv), A(tab), st32, interpret=True)
    got = flash_prefill_attention(A(q), A(kc), A(vc), _rows(pk),
                                  _rows(pv), A(tab), st32, q_offsets=st32,
                                  interpret=True)
    assert np.array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("starts", [(8, 24), (5, 13)],
                         ids=["block-aligned", "mid-block"])
def test_q_offsets_shard_slices_bitwise(starts):
    """The sequence-shard read layout: each of two query half-slabs
    (``S_q = S_kc / 2``) at ``q_offsets = starts + k * S_q`` against
    the FULL chunk K/V equals the corresponding slice of the
    full-width output bitwise — chunked continuations and mid-block
    shared-prefix starts both stay traced scalars in ONE program per
    (S_q, S_kc) signature."""
    rng = np.random.RandomState(6)
    bs, m, s_chunk = 8, 12, 16
    q, kc, vc, pk, pv, tab = _case(rng, starts, bs=bs, m=m,
                                   s_chunk=s_chunk)
    A, st32 = jnp.asarray, jnp.asarray(starts, jnp.int32)
    full = np.asarray(flash_prefill_attention(
        A(q), A(kc), A(vc), _rows(pk), _rows(pv), A(tab), st32,
        interpret=True))
    half = s_chunk // 2
    for k in range(2):
        got = flash_prefill_attention(
            A(q[:, :, k * half:(k + 1) * half]), A(kc), A(vc), _rows(pk),
            _rows(pv), A(tab), st32, q_offsets=st32 + k * half,
            interpret=True)
        assert np.array_equal(np.asarray(got),
                              full[:, :, k * half:(k + 1) * half])


def test_q_offsets_rejects_int8_pools():
    """``q_offsets`` is a read-layout feature of the float path; the
    int8 fused write needs the full chunk's queries resident, so the
    combination is a typed refusal, not silent corruption."""
    rng = np.random.RandomState(7)
    starts, bs, m, s_chunk = (8, 24), 8, 12, 16
    q, kc, vc, pk, pv, tab = _case(rng, starts, bs=bs, m=m,
                                   s_chunk=s_chunk)
    pk8 = rng.randint(-127, 128, pk.shape).astype(np.int8)
    pv8 = rng.randint(-127, 128, pv.shape).astype(np.int8)
    ks = np.ones(pk.shape[:2], np.float32)
    vs = np.ones(pv.shape[:2], np.float32)
    A, st32 = jnp.asarray, jnp.asarray(starts, jnp.int32)
    with pytest.raises(ValueError, match="float path"):
        flash_prefill_attention(
            A(q), A(kc), A(vc), _rows(pk8), _rows(pv8), A(tab), st32,
            block_scales=(A(ks), A(vs)), q_offsets=st32, interpret=True)


# ------------------------------------------------------- engine parity
@pytest.mark.parametrize("dtype", ["bf16", "int8"])
def test_engine_greedy_parity_and_frozen_programs(engines, dtype):
    """End-to-end through the engine: greedy tokens BIT-IDENTICAL
    between the kernel and composed-XLA prefill under chunked +
    prefix-hit traffic, with the frozen ``1 + len(prefill_buckets)``
    program contract re-pinned on BOTH impls (the kernel replaces the
    chunk attention + write inside the same per-bucket program — it
    must not add one)."""
    ek, ex = engines[dtype, "kernel"], engines[dtype, "xla"]
    assert ek.prefill_kernel_active and not ex.prefill_kernel_active
    tk, tx = _greedy(ek), _greedy(ex)
    assert tk == tx
    for eng in (ek, ex):
        stats = eng.compile_stats()
        assert stats["entries"] == 1 + len(PCFG.prefill_buckets)
        assert eng.pool.prefix_hits >= 1          # 3rd prompt re-hit
        eng.pool.leak_check()


def test_mesh2_engine_kernel_parity(model_and_vars):
    """``prefill_impl="kernel"`` under the mesh routes through the
    nested-shard_map variant and stays bit-identical to the
    single-device forced-kernel int8 engine, same frozen program
    count (the per-mesh re-pin)."""
    from nezha_tpu.serve.sharded import ShardedEngine

    model, variables = model_and_vars
    cfg = dataclasses.replace(PCFG, prefill_impl="kernel",
                              kv_dtype="int8")
    ref = _greedy(Engine(model, variables, cfg))
    eng = ShardedEngine(model, variables, cfg, mesh_devices=2)
    assert eng.prefill_kernel_active
    assert _greedy(eng) == ref
    stats = eng.compile_stats()
    assert stats["entries"] == 1 + len(PCFG.prefill_buckets)
    eng.pool.leak_check()


def test_int8_kernel_strictly_fewer_scatters(engines):
    """The fused epilogue write removes the per-layer gather/requant/
    scatter round-trip: the kernel bucket program lowers STRICTLY
    fewer scatter ops than the ``_quant_prefill_write`` chain (the
    'fewer compiled programs' acceptance, measured at the HLO level
    where the round-trip actually lives)."""
    counts = {}
    for impl in ("kernel", "xla"):
        eng = engines["int8", impl]
        width = max(PCFG.prefill_buckets)
        scalars = (np.int32(width), np.int32(0), np.int32(0),
                   np.int32(0), np.float32(0.0), np.int32(0),
                   np.float32(1.0), np.int32(-1), np.int32(6))
        state = (eng.last_logits, eng.positions, eng.keys, eng.temps,
                 eng.top_ks, eng.top_ps, eng.eos_ids, eng.budgets)
        lowered = jax.jit(eng._prefill_fns[width]).lower(
            eng.variables, eng.pool.caches, eng.pool.device_tables(),
            jnp.zeros((1, width), jnp.int32), *scalars, *state)
        counts[impl] = lowered.as_text().count("scatter")
    assert counts["kernel"] < counts["xla"], counts


# --------------------------------------------------- chaos + telemetry
def test_chaos_kernel_prefill_zero_leaks_and_telemetry(model_and_vars,
                                                       tmp_path):
    """The chaos acceptance re-run on the kernel path: seeded prefill
    errors + NaN bursts over templated int8 traffic (prefix hits and
    chunked continuations in play). Every request resolves, zero
    slot/block/scale leaks, frozen programs — and the run captures
    the PR's telemetry schema-clean: ``serve.prefill.kernel_s`` spans,
    a nonzero ``serve.prefill.fused_writes_total``, the kernel-active
    gauge, and the report's ``prefill[kernel]`` label."""
    model, variables = model_and_vars
    run_dir = str(tmp_path / "chaos_prefill_kernel")
    obs.start_run(run_dir, meta={"kind": "chaos_prefill_kernel"})
    try:
        cfg = dataclasses.replace(PCFG, prefill_impl="kernel",
                                  kv_dtype="int8", queue_capacity=12)
        eng = Engine(model, variables, cfg)
        sched = Scheduler(eng)
        faults.install(faults.FaultPlan.parse(
            "serve.prefill:error%0.1;serve.prefill.logits:nan%0.1",
            seed=11))
        try:
            rids = []
            for i in range(12):
                prompt = (LONG[:8] + [i % 97]
                          if i % 2 else
                          [(7 * i + j) % 97 for j in range(6)])
                rids.append(sched.submit(Request(
                    prompt=prompt, max_new_tokens=4,
                    request_id=f"c{i}")))
            sched.run_until_idle(max_iters=600)
            assert not sched.has_work()
        finally:
            faults.clear()
        assert set(rids) <= set(sched.results)
        reasons = {sched.results[r].finish_reason for r in rids}
        assert reasons <= {"length", "error"}
        assert eng.pool.num_free == cfg.max_batch_size
        eng.pool.leak_check()
        stats = eng.compile_stats()
        assert stats["entries"] == 1 + len(cfg.prefill_buckets)
        eng.pool.clear_prefix_cache()
        eng.pool.leak_check()
        assert eng.pool.blocks_used == 0
        assert obs.counter("serve.prefill.fused_writes_total").value > 0
    finally:
        obs.end_run()
    from check_telemetry_schema import check_run_dir
    assert check_run_dir(run_dir) == []
    with open(os.path.join(run_dir, "summary.json")) as f:
        summary = json.load(f)
    assert summary["gauges"]["serve.prefill.kernel_active"] == 1
    assert summary["counters"]["serve.prefill.fused_writes_total"] > 0
    with open(os.path.join(run_dir, "spans.jsonl")) as f:
        span_names = {json.loads(ln)["name"] for ln in f if ln.strip()}
    assert "serve.prefill.kernel_s" in span_names
    from nezha_tpu.obs.report import render_report
    report = render_report(run_dir)
    assert "prefill[kernel, replicated]:" in report
    assert "fused writes" in report
    # Dropping the new instruments must FAIL the pinned schema.
    del summary["counters"]["serve.prefill.fused_writes_total"]
    with open(os.path.join(run_dir, "summary.json"), "w") as f:
        json.dump(summary, f)
    assert any("serve.prefill.fused_writes_total" in e
               for e in check_run_dir(run_dir))


def test_serve_config_validates_prefill_impl():
    with pytest.raises(ValueError, match="prefill_impl"):
        ServeConfig(prefill_impl="mosaic")
