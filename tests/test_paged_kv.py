"""Paged KV cache (ISSUE 8): ref-counted block pool + block tables +
shared-prefix prefill reuse.

Covers the pool/trie bookkeeping (alloc/free/ref counts/COW/eviction,
zero-leak accounting), the block-table operand of the flash-decode
kernel, engine parity against one-shot generate(),
prefix-hit reuse (a templated request takes block references instead of
re-prefilling — and still decodes bit-identically), the stale-KV reuse
invariant on float32 and bfloat16 pools (a freed block rebound to a new
request is never attendable before that request overwrites it — proven by
poisoning freed storage with a huge sentinel; the int8 pool's case, scale
rows included, is tests/test_kv_quant.py's), typed block-exhaustion
backpressure (victim retired, batch survives), the ``serve.kv.bind`` fault
point,
and a seeded chaos run asserting zero slot AND block leaks with the
frozen program count and schema-valid artifacts.
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
from nezha_tpu.serve import (
    Engine,
    KVBlocksExhausted,
    PagedSlotPool,
    PrefixTrie,
    Request,
    Scheduler,
    ServeConfig,
)

CFG = dict(vocab_size=97, max_positions=64, num_layers=2, num_heads=4,
           hidden_size=64)
# Paged serving shapes: block_size 4 so tiny prompts span real blocks
# (full-block prefix hits, COW, lazy growth all fire at test sizes).
PCFG = ServeConfig(max_batch_size=3, max_len=48, max_prefill_len=8,
                   prefill_buckets=(4, 8), k_max=16, queue_capacity=8,
                   cache_dtype=jnp.float32, kv_block_size=4)

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


# ------------------------------------------------------------- the pool
def test_paged_pool_alloc_bind_free_refcounts(model_and_vars):
    model, _ = model_and_vars
    pool = PagedSlotPool(model, capacity=2, max_len=16,
                         dtype=jnp.float32, block_size=4)
    # Default: every slot can reach max_len: 1 scratch + 2 slots * 4.
    assert pool.num_blocks == 9 and pool.blocks_per_slot == 4
    assert pool.blocks_used == 0
    s = pool.alloc()
    assert pool.bind_for_prompt(s, [1, 2, 3, 4, 5]) == 0  # cold: no hits
    pool.prepare_write(s, 0, 8)        # bind blocks 0..1 of the slot
    assert pool.blocks_used == 2
    assert 0 not in pool.tables_host[s, :2]   # scratch never allocated
    pool.prepare_write(s, 8, 12)       # lazy growth
    assert pool.blocks_used == 3
    pool.leak_check()
    pool.free(s)
    assert pool.blocks_used == 0 and pool.num_free == 2
    assert (pool.tables_host[s] == 0).all()   # table reset to scratch
    # The slot layer (the scheduler's whole view of the pool): slots run
    # out before blocks do here, and the last slot freed is the next out.
    a, b = pool.alloc(), pool.alloc()
    assert {a, b} == {0, 1} and pool.alloc() is None
    assert pool.num_active == 2 and pool.occupancy == 1.0
    pool.free(a)
    assert pool.num_free == 1 and pool.alloc() == a
    pool.free(a)
    pool.free(b)
    with pytest.raises(ValueError, match="double free"):
        pool.free(s)
    with pytest.raises(ValueError, match="out of range"):
        pool.free(7)
    pool.leak_check()
    # Exhaustion is typed: a slot that wants more blocks than exist.
    small = PagedSlotPool(model, capacity=1, max_len=16,
                          dtype=jnp.float32, block_size=4, num_blocks=3)
    t = small.alloc()
    small.prepare_write(t, 0, 8)       # both usable blocks bound
    with pytest.raises(KVBlocksExhausted):
        small.prepare_write(t, 8, 12)
    small.free(t)
    small.leak_check()


def test_prefix_trie_match_insert_evict():
    trie = PrefixTrie(block_size=4)
    refs = {}

    def take(b):
        refs[b] = refs.get(b, 0) + 1

    def release(b):
        refs[b] -= 1

    toks = list(range(12))
    assert trie.match(toks) == []
    assert trie.insert(toks, [10, 11, 12], take) == 3
    assert trie.match(toks) == [10, 11, 12]
    assert trie.match(toks[:8] + [99, 99, 99, 99]) == [10, 11]
    assert trie.match([99] * 12) == []
    # Re-inserting the same path adds nothing (first writer wins).
    assert trie.insert(toks, [20, 21, 22], take) == 0
    assert refs == {10: 1, 11: 1, 12: 1}
    # A diverging suffix shares the matched prefix path.
    toks2 = toks[:8] + [50, 51, 52, 53]
    assert trie.insert(toks2, [10, 11, 30], take) == 1
    assert trie.match(toks2) == [10, 11, 30]
    # Eviction is leaf-first LRU: interior nodes survive their children.
    trie.match(toks)            # touch the 10->11->12 path (newer)
    assert trie.evict(1, release) == 1
    assert refs[30] == 0        # LRU leaf went first
    assert trie.match(toks) == [10, 11, 12]
    assert trie.evict(10, release) == 3
    assert all(v == 0 for v in refs.values()) and len(trie) == 0


def test_flash_decode_block_table_operand_parity():
    """The kernel's paged mode (block-table gather via scalar prefetch)
    matches the dense kernel over an explicit gather — including the
    per-row length skip (length 0 row stays exactly zero)."""
    from nezha_tpu.ops.pallas import flash_decode_attention
    from nezha_tpu.ops.quant import merge_heads

    rng = np.random.default_rng(0)
    b, h, d, bs, m, n = 3, 2, 16, 8, 4, 10
    q = jnp.asarray(rng.normal(size=(b, h, 1, d)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(n, h, bs, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(n, h, bs, d)), jnp.float32)
    tables = jnp.asarray(rng.integers(0, n, size=(b, m)), jnp.int32)
    lengths = jnp.asarray([0, 13, 32], jnp.int32)
    kd = kp[tables].transpose(0, 2, 1, 3, 4).reshape(b, h, m * bs, d)
    vd = vp[tables].transpose(0, 2, 1, 3, 4).reshape(b, h, m * bs, d)
    dense = flash_decode_attention(q, kd, vd, lengths)
    # the pool itself is lane-dense rows [n, bs, H*D]
    kp, vp = merge_heads(kp), merge_heads(vp)
    paged = flash_decode_attention(q, kp, vp, lengths,
                                   block_tables=tables)
    np.testing.assert_allclose(np.asarray(paged), np.asarray(dense),
                               atol=1e-5)
    assert np.all(np.asarray(paged)[0] == 0.0)   # inactive row
    # Traced tables under jit: same program shape the engine compiles.
    jitted = jax.jit(lambda *a: flash_decode_attention(
        a[0], a[1], a[2], a[3], block_tables=a[4]))
    np.testing.assert_allclose(
        np.asarray(jitted(q, kp, vp, lengths, tables)),
        np.asarray(dense), atol=1e-5)


# --------------------------------------------------------- engine parity
def test_paged_engine_matches_generate(model_and_vars):
    """Greedy requests (one of them a chunked prompt), batched with a
    sampled one, match one-shot generate() token for token — the block
    indirection is a memory layout, never a semantic — and the sampled
    request fills its budget. The frozen program count holds."""
    model, variables = model_and_vars
    reqs = [dict(prompt=[5, 17, 3, 42], max_new_tokens=10),
            dict(prompt=[7, 7], max_new_tokens=9, temperature=0.9,
                 top_k=10, seed=7),
            dict(prompt=[(7 * i + 3) % 97 for i in range(20)],
                 max_new_tokens=6)]
    eng = Engine(model, variables, PCFG)
    sched = Scheduler(eng)
    rids = [sched.submit(Request(**kw)) for kw in reqs]
    _drain(sched)
    outs = [sched.results[r].tokens for r in rids]
    stats = eng.compile_stats()
    assert stats["entries"] == stats["misses"] == \
        1 + len(PCFG.prefill_buckets)
    eng.pool.leak_check()
    assert outs[0] == _greedy_ref(model, variables, reqs[0]["prompt"], 10)
    assert len(outs[1]) == 9
    assert outs[2] == _greedy_ref(model, variables, reqs[2]["prompt"], 6)


def test_prefix_hit_skips_prefill_and_decodes_identically(
        model_and_vars, tmp_path):
    """Templated traffic: a request whose prompt shares a cached
    full-block prefix takes references instead of re-prefilling — the
    prefill work drops to the un-cached tail (observable in the chunk
    counter), the hit is counted, and the decoded tokens are identical
    to a cold engine's. Program count stays frozen (partial-prefix
    prefill reuses the same bucket programs)."""
    model, variables = model_and_vars
    prefix = [(3 * i + 5) % 97 for i in range(16)]   # 4 full blocks
    tail_a, tail_b = [33, 44], [55]
    obs.start_run(str(tmp_path / "hits"), meta={"kind": "test"})
    try:
        eng = Engine(model, variables, PCFG)
        sched = Scheduler(eng)
        a = sched.submit(Request(prompt=prefix + tail_a,
                                 max_new_tokens=4))
        _drain(sched)
        assert eng.pool.prefix_hits == 0 and len(eng.pool.trie) == 4
        chunks_cold = obs.counter("serve.prefill.chunks_total").value
        assert chunks_cold == 3            # 18 tokens = 8 + 8 + tail

        b = sched.submit(Request(prompt=prefix + tail_b,
                                 max_new_tokens=4))
        _drain(sched)
        assert eng.pool.prefix_hits == 1
        assert obs.counter("serve.kv.prefix_hits_total").value == 1
        # Hit: the 16 cached positions are referenced, not re-run —
        # prefill shrinks to ONE tail chunk.
        assert obs.counter("serve.prefill.chunks_total").value \
            == chunks_cold + 1
    finally:
        obs.end_run()
    stats = eng.compile_stats()
    assert stats["entries"] == stats["misses"] == \
        1 + len(PCFG.prefill_buckets)
    eng.pool.leak_check()

    cold = Engine(model, variables,
                  dataclasses.replace(PCFG, prefix_cache=False))
    sc = Scheduler(cold)
    b2 = sc.submit(Request(prompt=prefix + tail_b, max_new_tokens=4))
    _drain(sc)
    assert sched.results[b].tokens == sc.results[b2].tokens
    assert cold.pool.prefix_hits == 0 and len(cold.pool.trie) == 0


def test_cow_on_shared_block_write_with_live_donor(model_and_vars):
    """An exactly-block-aligned full-prefix hit must WRITE into its
    last shared block (the final prompt token re-runs to seed logits):
    that block is copied first (copy-on-write), the donor's cached
    copy stays intact — proven by a THIRD identical request hitting
    the cache again and still decoding identically — and the books
    balance."""
    model, variables = model_and_vars
    prompt = [(5 * i + 11) % 97 for i in range(12)]   # exactly 3 blocks
    eng = Engine(model, variables, PCFG)
    sched = Scheduler(eng)
    ref = _greedy_ref(model, variables, prompt, 6)
    a = sched.submit(Request(prompt=prompt, max_new_tokens=6))
    _drain(sched)
    assert sched.results[a].tokens == ref
    assert eng.pool.cow_copies == 0
    # Aligned full hit: shared_len caps at n-1 inside the last cached
    # block -> prepare_write COWs it before the tail chunk runs.
    b = sched.submit(Request(prompt=prompt, max_new_tokens=6))
    c = sched.submit(Request(prompt=prompt, max_new_tokens=6))
    _drain(sched)
    assert eng.pool.prefix_hits == 2 and eng.pool.cow_copies >= 2
    assert sched.results[b].tokens == ref
    assert sched.results[c].tokens == ref
    eng.pool.leak_check()


# ------------------------------------------- stale-KV reuse invariant
_POISON = 1.0e3   # finite but logit-wrecking if a single stale
                  # position ever gets nonzero attention weight
                  # (NaN would ALSO poison legitimately-masked scores
                  # through the additive -inf mask — the pool's
                  # guarantee is zero WEIGHT on stale positions, which
                  # only a finite sentinel tests honestly; the flash
                  # kernel path additionally never loads them)


def _poison_free_storage(eng):
    """Overwrite every cache position a retired request left behind
    (all free blocks) with a huge sentinel. If ANY stale position were
    attendable before its new owner overwrites it, the sentinel would
    visibly skew the logits and the token-for-token reference
    comparison below would fail."""
    idx = jnp.asarray(sorted(eng.pool._free_blocks), jnp.int32)
    eng.pool.caches = [
        {kv: leaf.at[idx].set(_POISON)
         for kv, leaf in layer.items()}
        for layer in eng.pool.caches]


@pytest.mark.parametrize("storage", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_stale_kv_never_attendable_after_rebind(model_and_vars, storage):
    """THE reuse invariant slots.py documents: a freed block rebound
    to a new request must never be attendable before that
    request overwrites it. Serve a request, retire it, poison all freed
    storage, then serve a different request through the same
    storage — its tokens must match a clean reference exactly
    (generate() over a whole-batch cache of the same dtype). The int8
    pool's case, scale rows included, is test_kv_quant.py's
    ``test_int8_stale_kv_and_stale_scales_never_attendable``."""
    model, variables = model_and_vars
    # prefix_cache off: every block the first request bound is
    # genuinely FREED at retirement (no trie refs), so the poison
    # covers the exact storage the second request rebinds.
    cfg = dataclasses.replace(PCFG, prefix_cache=False,
                              cache_dtype=storage)
    eng = Engine(model, variables, cfg)
    sched = Scheduler(eng)
    first = sched.submit(Request(
        prompt=[(7 * i + 1) % 97 for i in range(20)], max_new_tokens=8))
    _drain(sched)
    assert sched.results[first].finish_reason == "length"
    _poison_free_storage(eng)
    prompt2 = [9, 8, 7, 6, 5]
    second = sched.submit(Request(prompt=prompt2, max_new_tokens=8))
    _drain(sched)
    res = sched.results[second]
    assert res.finish_reason == "length", res.error
    ref = np.asarray(generate(
        model, variables, np.asarray([prompt2], np.int32),
        max_new_tokens=8, temperature=0.0,
        cache_dtype=storage))[0, len(prompt2):].tolist()
    assert res.tokens == ref
    eng.pool.leak_check()


# ------------------------------------------------ occupancy + exhaustion
def test_paged_admits_more_residents_than_dense_at_equal_memory(
        model_and_vars):
    """The paged pool's occupancy claim at engine level: with a
    device KV budget of 96 token-positions, a worst-case reservation of
    max_len positions a request admits 96 // 48 = 2 residents, while the
    paged pool runs 4 short requests concurrently — because blocks bind
    for tokens actually written, not for max_len."""
    model, variables = model_and_vars
    budget = 96
    paged = Engine(model, variables, dataclasses.replace(
        PCFG, max_batch_size=4, kv_block_size=8,
        kv_num_blocks=1 + budget // 8))                # 12 * 8 = 96
    sched = Scheduler(paged)
    for i in range(6):
        sched.submit(Request(prompt=[3 + i, 1, 4, 1], max_new_tokens=8,
                             request_id=f"r{i}"))
    peak = 0
    for _ in range(400):
        if not sched.has_work():
            break
        sched.step()
        peak = max(peak, len(sched._live))
    assert not sched.has_work()
    assert all(sched.results[f"r{i}"].finish_reason == "length"
               for i in range(6))
    assert peak == 4 > budget // PCFG.max_len   # more, at equal memory
    paged.pool.leak_check()


def test_block_exhaustion_retires_victim_not_batch(model_and_vars):
    """Decode-time block exhaustion is REQUEST-SCOPED backpressure:
    with 5 usable blocks and two requests that each need 5, one row's
    lazy bind fails mid-decode -> that request retires with a typed
    'kv blocks exhausted' error (its blocks freed same-iteration), the
    survivor finishes its full budget, and nothing leaks."""
    model, variables = model_and_vars
    eng = Engine(model, variables, dataclasses.replace(
        PCFG, max_batch_size=2, kv_num_blocks=6, prefix_cache=False))
    sched = Scheduler(eng)
    a = sched.submit(Request(prompt=[1, 2, 3, 4], max_new_tokens=16,
                             request_id="a"))
    b = sched.submit(Request(prompt=[5, 6, 7, 8], max_new_tokens=16,
                             request_id="b"))
    _drain(sched)
    reasons = {sched.results[r].finish_reason for r in (a, b)}
    assert reasons == {"length", "error"}
    errored = next(r for r in (a, b)
                   if sched.results[r].finish_reason == "error")
    survivor = next(r for r in (a, b) if r != errored)
    assert "kv blocks exhausted" in sched.results[errored].error
    assert len(sched.results[survivor].tokens) == 16
    assert eng.pool.num_free == 2
    eng.pool.leak_check()
    # A request that could NEVER fit bounces at submit, holding nothing.
    with pytest.raises(ValueError, match="KV blocks"):
        sched.submit(Request(prompt=list(range(1, 30)),
                             max_new_tokens=17))


def test_lru_eviction_reclaims_cache_blocks(model_and_vars):
    """When the free list dries up, LRU trie-only blocks are evicted to
    serve new bindings (the cache is a best-effort accelerant, never a
    reservation); with kv_eviction='none' the same pressure surfaces
    as typed backpressure instead."""
    model, variables = model_and_vars
    cfg = dataclasses.replace(PCFG, max_batch_size=1, kv_num_blocks=8)
    eng = Engine(model, variables, cfg)
    sched = Scheduler(eng)
    p1 = [(3 * i + 2) % 97 for i in range(12)]       # 3 full blocks
    sched.submit(Request(prompt=p1, max_new_tokens=4))
    _drain(sched)
    assert len(eng.pool.trie) == 3
    # 7 usable blocks, 3 cached: a request needing 6 evicts from the
    # trie instead of failing.
    p2 = [(5 * i + 1) % 97 for i in range(20)]
    r = sched.submit(Request(prompt=p2, max_new_tokens=3))
    _drain(sched)
    assert sched.results[r].finish_reason == "length"
    assert len(eng.pool.trie) < 3 + 5    # eviction happened
    eng.pool.leak_check()

    none = Engine(model, variables, dataclasses.replace(
        cfg, kv_eviction="none"))
    sn = Scheduler(none)
    sn.submit(Request(prompt=p1, max_new_tokens=4))
    _drain(sn)
    r2 = sn.submit(Request(prompt=p2, max_new_tokens=3))
    # Admission sees available_blocks() without eviction, and with
    # NOTHING in flight no retirement can ever free the cache-pinned
    # blocks — waiting would livelock, so the head retires with a
    # typed error instead (never a hang, never a crash).
    sn.step()
    assert sn.queue_depth == 0
    assert sn.results[r2].finish_reason == "error"
    assert "kv blocks exhausted" in sn.results[r2].error
    none.pool.clear_prefix_cache()       # operator relief valve
    r3 = sn.submit(Request(prompt=p2, max_new_tokens=3))
    _drain(sn)
    assert sn.results[r3].finish_reason == "length"
    none.pool.leak_check()


def test_prefix_hit_falls_back_to_cold_prefill_in_tight_pool(
        model_and_vars):
    """Pathological tight pool: a fully-cached prompt's hit pins the
    very block its own copy-on-write then needs (free list empty, the
    only reclaimable block is the one the hit just referenced). The
    engine must fall back to a COLD prefill — releasing the hit's
    references makes the block evictable again — and serve the
    request, not retire it with a deterministic error a pool with no
    prefix cache would never produce."""
    model, variables = model_and_vars
    # 3 usable blocks, blocks_per_slot 3 (max_len 12, bs 4).
    eng = Engine(model, variables, dataclasses.replace(
        PCFG, max_batch_size=2, max_len=12, kv_num_blocks=4))
    pool = eng.pool
    prompt_a = [11, 22, 33, 44]              # exactly one full block
    s0 = pool.alloc()
    eng.prefill(s0, prompt_a, max_new_tokens=4)
    pool.free(s0)                            # A cached: 1 trie-only block
    s0 = pool.alloc()
    # B (live): 7-token prompt binds the remaining 2 free blocks
    # (bucket-8 span) and stays resident.
    eng.prefill(s0, [60 + i for i in range(7)], max_new_tokens=1)
    assert pool.available_blocks() == 1      # A's cache block only
    s1 = pool.alloc()
    # The hit references A's block (ref 2 -> unevictable), then COW
    # finds no free and no reclaimable block: pre-fix this raised
    # KVBlocksExhausted out of prefill; the fallback must recover.
    eng.prefill(s1, prompt_a, max_new_tokens=4)
    # The old cache entry was evicted to feed the cold rebind, and the
    # rebuilt block was re-registered — one fresh entry, books balanced.
    assert len(pool.trie.match(prompt_a)) == 1
    pool.leak_check()
    # Retire B (as the scheduler would) so s1's decode growth has a
    # block to bind, and check the recovered row decodes normally.
    pool.free(s0)
    active = np.zeros((2,), bool)
    active[s1] = True
    tok, emitted = eng.step(active)
    assert emitted[s1] == 1
    pool.free(s1)
    pool.leak_check()


def test_eviction_skips_leaves_still_bound_by_live_requests(
        model_and_vars):
    """Exhaustion must only surface after every RECLAIMABLE block has
    been reclaimed: the LRU-oldest trie leaf may still be bound by a
    live prefix-hit request (ref > 1 — releasing the trie's ref frees
    nothing), and eviction has to skip it and take a younger ref-1
    leaf instead of destroying cache value and then failing anyway."""
    model, _ = model_and_vars
    pool = PagedSlotPool(model, capacity=3, max_len=16,
                         dtype=jnp.float32, block_size=4, num_blocks=5)
    t1, t2 = list(range(4)), [50 + i for i in range(4)]
    s1 = pool.alloc()                      # stays LIVE holding t1's block
    pool.bind_for_prompt(s1, t1)
    pool.prepare_write(s1, 0, 4)
    pool.register_prefix(s1, t1)           # trie ref -> block ref 2
    s2 = pool.alloc()                      # donor of the younger entry
    pool.bind_for_prompt(s2, t2)
    pool.prepare_write(s2, 0, 4)
    pool.register_prefix(s2, t2)
    pool.free(s2)                          # t2's block: trie-only, ref 1
    assert pool.available_blocks() == 3    # 2 free + 1 evictable
    # A request needing all 3: the LRU leaf (t1's, ref 2) must be
    # SKIPPED and t2's ref-1 leaf evicted — no KVBlocksExhausted.
    s3 = pool.alloc()
    pool.bind_for_prompt(s3, [70 + i for i in range(12)])
    pool.prepare_write(s3, 0, 12)
    assert len(pool.trie) == 1             # t1's entry survived
    assert pool.trie.match(t1) != []
    pool.free(s3)
    pool.free(s1)
    pool.leak_check()


def test_decode_binding_clamped_to_remaining_budget(model_and_vars):
    """A pool sized EXACTLY for a request's admission footprint must
    serve it to completion: with decode_horizon larger than the
    remaining budget, lazy binding only grows the write window by
    min(horizon, budget) — a row one token from finishing is never
    retired for blocks it would never write."""
    model, variables = model_and_vars
    # prompt 4 + max_new 4 = 8 tokens = exactly 2 blocks = the whole
    # usable pool; horizon 8 would naively demand [4, 12) = 3 blocks.
    eng = Engine(model, variables, dataclasses.replace(
        PCFG, max_batch_size=1, kv_num_blocks=3, prefix_cache=False,
        decode_horizon=8))
    sched = Scheduler(eng)
    rid = sched.submit(Request(prompt=[1, 2, 3, 4], max_new_tokens=4))
    _drain(sched)
    res = sched.results[rid]
    assert res.finish_reason == "length", res.error
    assert len(res.tokens) == 4
    eng.pool.leak_check()


# --------------------------------------------------- faults + chaos
def test_kv_bind_fault_injection_typed_backpressure(model_and_vars):
    """The serve.kv.bind fault point: an injected bind failure at
    admission retires ONLY that request (typed error, slot + blocks
    freed), and one injected mid-decode retires the victim with its
    pre-fault tokens — the engine never crashes and nothing leaks."""
    model, variables = model_and_vars
    eng = Engine(model, variables,
                 dataclasses.replace(PCFG, prefix_cache=False))
    sched = Scheduler(eng)
    try:
        faults.install(faults.FaultPlan.parse("serve.kv.bind:error@1"))
        bad = sched.submit(Request(prompt=[1, 2, 3], max_new_tokens=4,
                                   request_id="bad"))
        ok = sched.submit(Request(prompt=[4, 5, 6], max_new_tokens=4,
                                  request_id="ok"))
        _drain(sched)
        assert sched.results[bad].finish_reason == "error"
        assert "injected" in sched.results[bad].error
        assert sched.results[ok].finish_reason == "length"
        assert len(sched.results[ok].tokens) == 4

        # Mid-decode: the 3rd bind of this request happens during lazy
        # decode growth (prefill spans 1 block, growth binds more).
        faults.install(faults.FaultPlan.parse("serve.kv.bind:error@3"))
        mid = sched.submit(Request(prompt=[7, 8, 9, 10],
                                   max_new_tokens=12,
                                   request_id="mid"))
        _drain(sched)
        res = sched.results[mid]
        assert res.finish_reason == "error"
        assert "kv blocks exhausted" in res.error
        assert 0 < len(res.tokens) < 12      # pre-fault tokens kept
    finally:
        faults.clear()
    assert eng.pool.num_free == PCFG.max_batch_size
    eng.pool.leak_check()


def test_chaos_paged_zero_block_leaks(model_and_vars, tmp_path):
    """The chaos acceptance on the paged pool at horizon 4: seeded
    prefill errors + NaN bursts + kv.bind failures over 16 requests
    with templated prompts (prefix hits + COW in play). EVERY request
    gets exactly one result, retired rows' block refs drop in the same
    iteration (zero slot leaks, zero block leaks — the ref-count books
    balance), the program set stays frozen, and the artifacts pass the
    pinned schema including the serve.kv.* instruments."""
    model, variables = model_and_vars
    run_dir = str(tmp_path / "chaos_paged")
    obs.start_run(run_dir, meta={"kind": "chaos_paged"})
    try:
        cfg = dataclasses.replace(PCFG, decode_horizon=4,
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
        # Zero slot leaks, zero block leaks, frozen programs.
        assert eng.pool.num_free == cfg.max_batch_size
        eng.pool.leak_check()
        stats = eng.compile_stats()
        assert stats["entries"] == stats["misses"] == \
            1 + len(cfg.prefill_buckets)
        # The cache (trie refs) is the ONLY thing still holding blocks;
        # dropping it must empty the pool completely.
        eng.pool.clear_prefix_cache()
        eng.pool.leak_check()
        assert eng.pool.blocks_used == 0
    finally:
        obs.end_run()
    from check_telemetry_schema import check_run_dir
    assert check_run_dir(run_dir) == []
    with open(os.path.join(run_dir, "summary.json")) as f:
        summary = json.load(f)
    assert "serve.kv.prefix_hits_total" in summary["counters"]
    assert "serve.kv.cow_copies_total" in summary["counters"]
    assert "serve.kv.blocks_used" in summary["gauges"]
    # Dropping a kv instrument must FAIL the pinned schema.
    del summary["counters"]["serve.kv.prefix_hits_total"]
    with open(os.path.join(run_dir, "summary.json"), "w") as f:
        json.dump(summary, f)
    assert any("serve.kv.prefix_hits_total" in e
               for e in check_run_dir(run_dir))
    from nezha_tpu.obs.report import render_report
    # (Report renders from the edited summary; the kv line keys on the
    # counters that remain — re-add and render.)
    summary["counters"]["serve.kv.prefix_hits_total"] = 1
    with open(os.path.join(run_dir, "summary.json"), "w") as f:
        json.dump(summary, f)
    report = render_report(run_dir)
    assert "kv:" in report and "prefix hits" in report


# ------------------------------------------------- config + bench + CLI
def test_serveconfig_kv_validation():
    with pytest.raises(TypeError, match="kv_layout"):
        ServeConfig(kv_layout="paged")   # the paged pool is the one layout
    with pytest.raises(ValueError, match="kv_block_size"):
        ServeConfig(kv_block_size=0)
    with pytest.raises(ValueError, match="kv_num_blocks"):
        ServeConfig(kv_num_blocks=1)
    with pytest.raises(ValueError, match="kv_eviction"):
        ServeConfig(kv_eviction="fifo")


def test_cli_refuses_the_removed_kv_layout_flag(capsys):
    """An old command line that still passes ``--kv-layout`` is told so
    by argparse (exit 2, the argument named), not served in silence."""
    from nezha_tpu.cli import serve as cli

    with pytest.raises(SystemExit) as e:
        cli.main(["--random-init", "--model-preset", "tiny",
                  "--kv-layout", "dense"])
    assert e.value.code == 2
    assert "--kv-layout" in capsys.readouterr().err


def test_serving_benchmark_shared_prefix_record(tmp_path):
    """benchmarks/serving.py --shared-prefix-frac: the templated-
    traffic record carries hit-rate, hit/miss TTFT, and the paged
    occupancy peaks, and the artifacts pass the pinned schema."""
    import serving as bench

    run_dir = str(tmp_path / "shared")
    rec = bench.run(bench.build_parser().parse_args(
        ["--requests", "10", "--concurrency", "3", "--max-new-tokens",
         "4", "--max-batch-size", "3", "--max-len", "48",
         "--max-prefill-len", "8", "--kv-block-size", "4",
         "--shared-prefix-frac", "0.8", "--shared-prefix-len", "16",
         "--run-dir", run_dir]))
    assert rec["finished"] == 10
    assert rec["kv"]["layout"] == "paged"
    assert rec["kv"]["prefix_hits"] > 0
    assert rec["kv"]["peak_resident_requests"] >= 1
    sp = rec["shared_prefix"]
    assert sp["len"] == 16 and sp["expected_hits"] > 0
    assert sp["prefix_hit_rate"] > 0
    assert sp["ttft_hit_s"]["p50"] > 0 and sp["ttft_miss_s"]["p50"] > 0
    from check_telemetry_schema import check_run_dir
    assert check_run_dir(run_dir) == []


def test_nezha_bench_gates_against_committed_baseline(tmp_path):
    """The unified nezha-bench entry point: --update seeds a
    per-platform baseline, a re-run gates OK against it, and a cooked
    regression (baseline 10x better) fails the gate with exit 1 —
    without touching the other platform's slot."""
    from nezha_tpu.cli import bench as nb

    sb = str(tmp_path / "BENCH_serving.json")
    db = str(tmp_path / "BENCH_decode_attention.json")
    # Loose threshold: this test pins the GATE MECHANISM (seed /
    # compare / fail / per-platform isolation), not CPU timing
    # stability — interpret-mode microbench times swing well past the
    # default 30% under parallel test load, while the cooked 10x
    # regression below still trips an 80% bound.
    args = ["--quick", "--serving-baseline", sb,
            "--decode-baseline", db, "--requests", "4",
            "--horizons", "1,4", "--threshold", "0.8",
            "--platform", "cpu"]
    assert nb.main(args + ["--update"]) == 0
    base = json.load(open(sb))
    assert "cpu" in base["by_platform"]
    # The committed sweep's tokens/sec comes from the capture-free
    # pass, with the stitched trace block grafted in from the separate
    # captured pass (ISSUE 12): every horizon slot carries one.
    sweep = base["by_platform"]["cpu"]["closed_loop_horizon_sweep"]
    assert "capture-free" in sweep["trace_source"]
    for h_rec in sweep["by_horizon"].values():
        assert h_rec["trace"] and h_rec["trace"]["count"] > 0
    # A foreign platform slot must survive updates untouched.
    base["by_platform"]["tpu"] = {"closed_loop_horizon_sweep": {
        "by_horizon": {"1": {"tokens_per_sec": 123456.0}}}}
    json.dump(base, open(sb, "w"))
    # (up to three tries: two back-to-back ms-scale timings beside a test
    # that compiles a full-size program for a described chip can differ
    # fivefold; the mechanism is what is pinned here, see above)
    for _ in range(3):
        rec = nb.run(nb.build_parser().parse_args(args))
        if rec["ok"]:
            break
    assert rec["ok"] and rec["platform"] == "cpu"
    assert rec["vs_baseline"]["serving"]  # gated something
    # Cook the cpu baseline 10x up -> regression detected, exit 1.
    base = json.load(open(sb))
    for h in base["by_platform"]["cpu"]["closed_loop_horizon_sweep"][
            "by_horizon"].values():
        h["tokens_per_sec"] *= 10
    json.dump(base, open(sb, "w"))
    assert nb.main(args) == 1
    base2 = json.load(open(sb))
    assert base2["by_platform"]["tpu"]["closed_loop_horizon_sweep"][
        "by_horizon"]["1"]["tokens_per_sec"] == 123456.0


# ------------------------------------- the paged decode kernel, every edge
# Last in the file: the timing-gated test above compares two back-to-back
# ms-scale timings, and this file starts with the run (`--dist loadfile`
# schedules the files with the most items first), so what stands before it
# decides what it runs beside. These cases grew from 15 to 33 in PR 31.
def _paged_case(kind, m, seed=0, b=8, h=3, d=16, bs=8):
    """A batch over a PERMUTED table (no two entries share a block, no
    row's blocks are in order) whose lengths sit on every edge of the
    kernel's iteration space: empty, one position, one block, one block
    and one, one grid step (c entries), one step and one, the full
    table less one, the full table. Returns the kernel's operands (the
    pools as lane-dense rows ``[n, bs, H*D]``; the reference below
    thinks in per-head tiles and ``merge_heads`` converts, in this one
    place) and the composed path's answer (gather, dequantize, masked
    dense attention in float32 over the same stored values)."""
    from nezha_tpu import ops
    from nezha_tpu.ops.pallas import decode_attention as da
    from nezha_tpu.ops.pallas.common import pick_block
    from nezha_tpu.ops.quant import (
        dequantize_kv_block,
        merge_heads,
        quantize_kv_block,
    )

    c = pick_block(m, da._ENTRIES_PER_STEP)
    lengths = np.minimum([0, 1, bs, bs + 1, c * bs, c * bs + 1,
                          m * bs - 1, m * bs], m * bs).astype(np.int32)
    assert len(lengths) == b
    rng = np.random.default_rng(seed)
    n = b * m + 1                                   # block 0: scratch
    tables = (rng.permutation(n - 1) + 1).reshape(b, m).astype(np.int32)
    dtype = jnp.bfloat16 if kind == "bf16" else jnp.float32
    q = jnp.asarray(rng.normal(size=(b, h, 1, d)), dtype)
    kp = jnp.asarray(rng.normal(size=(n, h, bs, d)), dtype)
    vp = jnp.asarray(rng.normal(size=(n, h, bs, d)), dtype)
    scales = None
    if kind == "int8":
        (kp, ksc), (vp, vsc) = quantize_kv_block(kp), quantize_kv_block(vp)
        scales = (ksc, vsc)
        k_all = dequantize_kv_block(kp[tables], ksc[tables])
        v_all = dequantize_kv_block(vp[tables], vsc[tables])
    else:
        k_all = kp[tables].astype(jnp.float32)
        v_all = vp[tables].astype(jnp.float32)
    k_all = k_all.transpose(0, 2, 1, 3, 4).reshape(b, h, m * bs, d)
    v_all = v_all.transpose(0, 2, 1, 3, 4).reshape(b, h, m * bs, d)
    mask = jnp.where(jnp.arange(m * bs)[None, :] < lengths[:, None],
                     0.0, -jnp.inf).astype(jnp.float32)
    ref = np.array(ops.dot_product_attention(
        q.astype(jnp.float32), k_all, v_all, mask=mask[:, None, None, :]))
    ref[lengths == 0] = 0.0          # the kernel's answer for no position
    owned = np.zeros(n, bool)
    for row, length in zip(tables, lengths):
        owned[row[:-(-int(length) // bs)]] = True
    return (q, merge_heads(kp), merge_heads(vp), jnp.asarray(lengths),
            jnp.asarray(tables), scales, ref, owned)


# bf16 tiles dot in bf16 (scores and P both rounded to 8 bits of
# mantissa); float32 and dequantized-int8-in-float32 tiles stay exact.
_PAGED_TOL = {"f32": 2e-6, "int8": 2e-6, "bf16": 3e-2}


@pytest.mark.parametrize("m,h,d", [(32, 3, 16), (24, 3, 16), (3, 3, 16),
                                   (32, 12, 64), (32, 2, 64), (24, 2, 64),
                                   (3, 2, 64), (24, 12, 64)],
                         ids=["m32", "m24-not-a-multiple", "m3-one-step",
                              "m32-hd768", "m32-hd128",
                              "m24-not-a-multiple-hd128", "m3-one-step-hd128",
                              "m24-not-a-multiple-hd768"])
@pytest.mark.parametrize("kind", ["f32", "bf16", "int8"])
def test_paged_decode_kernel_matches_composed_on_every_edge(kind, m, h, d):
    """The paged kernel (interpret mode) against the composed path, all
    edge lengths mixed in one batch: M a multiple of the module's
    entries-per-step constant, M that is not (the largest divisor
    steps it: 24 -> 12), and a table shorter than one step. The pool
    width decides the kernel's iteration space (``_paged_call``): under
    one 128-lane tile (48) the GRID form walks the table; whole tiles
    (128, and GPT-2's 768) take the per-row LOOP, whose last iteration
    of a row is partly live at every length that is not a multiple of
    an iteration's span."""
    from nezha_tpu.ops.pallas import flash_decode_attention

    q, kp, vp, lengths, tables, scales, ref, _ = _paged_case(kind, m, h=h,
                                                             d=d)
    out = np.asarray(flash_decode_attention(
        q, kp, vp, lengths, block_tables=tables, block_scales=scales,
        interpret=True), np.float32)
    assert np.abs(out - ref).max() <= _PAGED_TOL[kind]
    assert (out[0] == 0).all()                    # length 0: exact zeros


@pytest.mark.parametrize("h,d", [(3, 16), (2, 64), (12, 64)],
                         ids=["hd48-grid", "hd128-loop", "hd768-loop"])
@pytest.mark.parametrize("kind", ["f32", "bf16", "int8"])
def test_paged_decode_kernel_reads_no_block_a_row_does_not_own(kind, h, d):
    """Every block no row owns (scratch block 0, and each row's table
    entries at or past ceil(length / bs)) is NaN — on an int8 pool its
    scale is. The outputs stay finite and equal the clean pool's: an
    entry past a row's length is never folded, whatever it names, by
    the grid form (48 lanes) and by the loop (whole 128-lane tiles),
    whose every copy must name a block the row owns: a buffer row left
    unfilled, or filled from a NaN block, is ``0 * NaN`` in ``p @ V``."""
    from nezha_tpu.ops.pallas import flash_decode_attention

    q, kp, vp, lengths, tables, scales, ref, owned = _paged_case(
        kind, 24, h=h, d=d)
    if kind == "int8":
        scales = tuple(jnp.where(owned[:, None], sc, jnp.nan)
                       for sc in scales)
    else:
        kp, vp = (jnp.where(owned[:, None, None], pool, jnp.nan)
                  for pool in (kp, vp))
    out = np.asarray(flash_decode_attention(
        q, kp, vp, lengths, block_tables=tables, block_scales=scales,
        interpret=True), np.float32)
    assert np.isfinite(out).all()
    assert np.abs(out - ref).max() <= _PAGED_TOL[kind]
