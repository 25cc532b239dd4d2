"""MiniCPM-SALA on the serving path, at tiny size on the CPU rig: the
engine against the plain reference (``chipbench/reference/
minicpm_sala.py``: every query's selection as the rule is written, the
lightning layers as the token recurrence), the selection sets exactly,
both sparse kernels against their ``jax.numpy`` twins, the compressed-key
leaf as prefill chunks and decode steps write it, lightning through the
Mamba-2 kernel and chunked form, and the typed refusals.

The tiny preset cuts the sparse sizes (kernel 4, stride 2, block 8, top-k
4, window 16, dense_len 32) so that ~100-token contexts select. Float32
throughout: logits are compared RELATIVE to the largest reference logit
at 1e-4 (float32 round-off reads ~3e-7; a wrong block in one query's
selection reads ~1e-3).
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench.reference import minicpm_sala as ref
from nezha_tpu import obs
from nezha_tpu.models.minicpm_sala import (lightning_log_decay,
                                           minicpm_sala)
from nezha_tpu.ops.pallas import sparse_attention as sp
from nezha_tpu.ops.pallas import ssd_chunked, ssd_recurrent, ssm_decode
from nezha_tpu.serve import Engine, ServeConfig, SpeculativeConfig
from nezha_tpu.serve.slots import PagedSlotPool

REL_TOL = 1e-4


def ref_cfg(c) -> dict:
    """The reference's view of a config, as the configuration file
    spells it (the sparse sizes under ``assumed_sizes``)."""
    out = {f.name: getattr(c, f.name) for f in dataclasses.fields(c)}
    out["mixer_types"] = list(c.mixer_types)
    out["assumed_sizes"] = {"sparse_config": {
        "kernel_size": c.sparse_kernel_size,
        "kernel_stride": c.sparse_kernel_stride,
        "block_size": c.sparse_block_size, "topk": c.sparse_topk,
        "init_blocks": c.sparse_init_blocks,
        "window_size": c.sparse_window_size,
        "dense_len": c.sparse_dense_len}}
    return out


@pytest.fixture(scope="module")
def tiny():
    model = minicpm_sala("tiny")
    return model, model.init(jax.random.PRNGKey(0))


def _engine(model, variables, **kw):
    kw = {"max_batch_size": 3, "max_len": 128, "max_prefill_len": 32,
          "prefill_buckets": (16, 32), "kv_block_size": 8,
          "cache_dtype": jnp.float32, "prefix_cache": False, **kw}
    return Engine(model, variables, ServeConfig(**kw))


def _rel(got, want):
    return float(np.abs(np.asarray(got) - want).max() / np.abs(want).max())


def test_cacheless_forward_matches_reference(tiny):
    """The forward pass with no cache (every query's selection over the
    sequence's own complete windows; lightning through the chunked form)
    is the reference's, at 100 tokens (past dense_len, 13 blocks)."""
    model, variables = tiny
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 100), 0, 512)
    got, _ = model.apply(variables, toks)
    want = np.asarray(ref.logits_at(variables["params"], toks,
                                    jnp.arange(100)[None],
                                    ref_cfg(model.cfg)))
    assert got.dtype == jnp.float32 and got.shape == (1, 100, 512)
    assert _rel(got, want) < REL_TOL


# Through the program's own stack, three greedy requests side by side:
# 61 tokens (two programs, 32 + 29; past dense_len, so every decode query
# selects top-4 of 4-8 candidate blocks beside the window), 2 tokens
# (no complete window), 31 (crosses dense_len while decoding).
@pytest.mark.parametrize("impl", ["xla", "kernel"])
def test_scheduler_prefill_and_decode_match_reference(tiny, impl):
    from nezha_tpu.cli import serve as cli

    model, variables = tiny
    sched, _, _ = cli._build_stack(cli.build_parser().parse_args([
        "--model", "minicpm_sala", "--random-init", "--model-preset", "tiny",
        "--max-len", "128", "--max-batch-size", "3", "--max-prefill-len",
        "32", "--prefill-buckets", "16,32", "--kv-block-size", "8",
        "--cache-dtype", "f32", "--prefix-cache", "off", "--decode-impl",
        impl, "--seed", "0", "--platform", "cpu"]))
    eng = sched.engine
    rows, prompts, out = {}, {}, {}
    prefill0, step0 = eng.prefill, eng.step

    def prefill(slot, tokens, **kw):
        prefill0(slot, tokens, **kw)
        rows[slot] = [np.asarray(eng.last_logits[slot])]
        prompts[slot] = list(tokens)

    def step(active):
        calls = eng.step_calls
        res = step0(active)
        if eng.step_calls > calls:
            for slot in np.flatnonzero(active):
                rows[slot].append(np.asarray(eng.last_logits[slot]))
        return res

    eng.prefill, eng.step = prefill, step
    sched.on_finish = lambda r: out.__setitem__(r.request_id, r.tokens)
    rng = np.random.default_rng(3)
    lengths = (61, 2, 31)
    from nezha_tpu.serve import Request
    for i, n in enumerate(lengths):
        sched.submit(Request(prompt=rng.integers(0, 512, n).tolist(),
                             max_new_tokens=12, temperature=0.0,
                             request_id=f"r{i}"))
    sched.run_until_idle()
    assert sorted(out) == ["r0", "r1", "r2"]
    worst = 0.0
    for slot, prompt in prompts.items():
        seq = prompt + out[f"r{lengths.index(len(prompt))}"]
        got = np.stack(rows[slot][:12])
        pos = jnp.arange(len(prompt) - 1, len(seq) - 1)[None]
        want = np.asarray(ref.logits_at(
            variables["params"], jnp.asarray([seq[:-1]], jnp.int32), pos,
            ref_cfg(model.cfg)))[0]
        worst = max(worst, _rel(got, want))
    assert worst < REL_TOL
    eng.pool.leak_check()


def test_selection_sets_are_the_references_exactly():
    """Window scores with exact ties (the lower block id wins) at every
    visible length from 1 to 96: the program's rule (strided maxima,
    ``lax.top_k``) selects exactly the reference's blocks (an overlap
    matrix, a rank count)."""
    z = sp.SparseSizes(4, 2, 8, 4, 1, 16, 32)
    m = 12
    nc = m * z.per_block
    rng = np.random.default_rng(7)
    s = rng.integers(0, 6, (2, 96, nc)).astype(np.float32) / 8  # ties
    lengths = jnp.arange(1, 97)
    n_c = sp.n_complete(lengths, z)
    got = sp.select_mask(sp.block_scores(jnp.asarray(s), n_c[None], z),
                         lengths[None], z)
    cfg = {"block_size": 8, "kernel_stride": 2, "kernel_size": 4,
           "topk": 4, "init_blocks": 1, "window_size": 16, "dense_len": 32}
    masked = jnp.where(jnp.arange(nc) < n_c[:, None], s, -jnp.inf)
    want = ref._selected_blocks(masked, lengths, cfg, False)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # past dense_len: the init block, the window's 2-3 blocks and the
    # top-4, or every block held where that is fewer
    L = np.arange(33, 97)
    held, first = -(-L // 8), (L - 16) // 8
    want_count = np.minimum(held, held - first + np.minimum(1, first) + 4)
    assert (np.asarray(got).sum(-1)[:, 32:] == want_count).all()
    # the list: ascending, the row's current block last, and as many
    # keys as the selected blocks' visible tokens
    ids, keys = sp.select_list(got[0][:, None], lengths, z, z.list_len(m))
    count = np.asarray(got[0]).sum(-1)
    ids, keys = np.asarray(ids)[:, 0], np.asarray(keys)[:, 0]
    np.testing.assert_array_equal(ids[np.arange(96), count - 1],
                                  (np.arange(1, 97) - 1) // 8)
    np.testing.assert_array_equal(
        keys, (count - 1) * 8 + (np.arange(96) % 8) + 1)


@pytest.mark.parametrize("m, k", [(7, 64), (300, 64), (1024, 4)])
def test_top_k_mask_marks_what_lax_top_k_returns(m, k):
    """Bisection over the scores' bits, ties lowest index first: exactly
    ``lax.top_k``'s indices (no candidate: ``-inf``; fewer than ``k``)."""
    rng = np.random.default_rng(m)
    x = rng.integers(0, 7, (16, m)).astype(np.float32) / 8 * 0.37
    x = np.where(rng.random((16, m)) < 0.3, -np.inf, x)
    got = np.asarray(sp.top_k_mask(jnp.asarray(x), k))
    vals, idx = jax.lax.top_k(jnp.asarray(x), min(k, m))
    want = np.zeros_like(got)
    rows = np.repeat(np.arange(16), idx.shape[1])
    keep = np.asarray(vals).ravel() > -np.inf
    want[rows[keep], np.asarray(idx).ravel()[keep]] = True
    np.testing.assert_array_equal(got, want)


def _pools(rng, n_blocks, bs, kvh, d, r):
    k = rng.standard_normal((n_blocks, bs, kvh * d)).astype(np.float32)
    v = rng.standard_normal((n_blocks, bs, kvh * d)).astype(np.float32)
    ck = rng.standard_normal((n_blocks, r * kvh, d)).astype(np.float32)
    return jnp.asarray(k), jnp.asarray(v), jnp.asarray(ck)


# lengths: inactive (0), below a window, around dense_len (32), a window
# that overlaps the top-k's range (40), deep (95, 96)
LENGTHS = [0, 3, 4, 31, 32, 33, 40, 95, 96]


@pytest.mark.parametrize("kernel", ["score", "decode"])
def test_sparse_kernels_match_their_twins(kernel):
    z = sp.SparseSizes(4, 2, 8, 4, 1, 16, 32)
    b, h, kvh, d, m = len(LENGTHS), 4, 2, 16, 12
    rng = np.random.default_rng(11)
    k, v, ck = _pools(rng, 1 + b * m, 8, kvh, d, z.per_block)
    tab = jnp.asarray(1 + rng.permutation(b * m).reshape(b, m), jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
    lengths = jnp.asarray(LENGTHS, jnp.int32)
    if kernel == "score":
        got = sp.sparse_score(q, ck, k, tab, lengths, 0.25, z,
                              interpret=True)
        want = sp.sparse_score_reference(q, ck, k, tab, lengths, 0.25, z)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-5, atol=1e-6)
        return
    s, _ = sp.sparse_score_reference(q, ck, k, tab, lengths, 0.25, z)
    n_c = sp.n_complete(lengths, z)
    mask = sp.select_mask(sp.block_scores(s.transpose(0, 2, 1),
                                          n_c[:, None], z),
                          lengths[:, None], z)
    ids, keys = sp.select_list(mask, lengths, z, z.list_len(m))
    got = sp.sparse_decode_paged(q, k, v, tab, ids, keys, 0.25,
                                 interpret=True)
    want = sp.sparse_decode_reference(q, k, v, tab, ids, keys, 0.25)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    assert not np.asarray(got)[0].any()         # an inactive row: zeros


def test_compressed_key_leaf_is_written_across_chunks_and_blocks(tiny):
    """A 61-token prompt in programs of 32 and 29 (a window that crosses
    the program boundary and windows that cross block edges), then 14
    decode steps: the slot's ``ck`` entries hold the mean of every
    complete window's keys, as the reference computes them; and the
    engine's ``serve.sparse.*`` counters add the rule's blocks at each
    step's visible length."""
    model, variables = tiny
    c = model.cfg
    eng = _engine(model, variables, max_batch_size=1)
    rng = np.random.default_rng(6)
    prompt = rng.integers(0, 512, 61).tolist()
    slot = eng.pool.alloc()
    eng.prefill(slot, prompt, max_new_tokens=20, temperature=0.0)
    toks = []
    eng.overlap_blocks(False)
    counters = [obs.counter(f"serve.sparse.blocks_{k}_total")
                for k in ("read", "resident")]
    was_on = obs.enabled()
    obs.enable()
    before = [c.value for c in counters]
    try:
        for _ in range(14):
            tok, _ = eng.step(np.ones(1, bool))
            toks.append(int(np.asarray(tok).reshape(-1)[0]))
    finally:
        if not was_on:
            obs.disable()
    # the engine's counters: the rule at each step's visible length
    want = np.sum([model.sparse_blocks(np.asarray([n]))
                   for n in range(62, 76)], axis=0)
    assert [c.value - b for c, b in zip(counters, before)] == list(want)
    seq = prompt + toks                 # the last token is not yet written
    n = len(seq) - 1
    _, kept = ref.logits_at(variables["params"],
                            jnp.asarray([seq[:n]], jnp.int32),
                            jnp.asarray([[n - 1]]), ref_cfg(c),
                            state_at=jnp.asarray([n - 1]))
    z = c.sparse_sizes
    n_c = int(sp.n_complete(n, z))
    want = np.asarray(kept[0])[0, :n_c]                   # [n_c, KVH, D]
    ck = np.asarray(eng.pool.caches[0]["ck"])
    tab = eng.pool.tables_host[slot]
    got = np.stack([ck[tab[j // z.per_block]].reshape(
        z.per_block, c.num_key_value_heads, -1)[j % z.per_block]
        for j in range(n_c)])
    assert n_c == 36
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("form", ["chunked", "decode"])
def test_lightning_through_the_ssm_forms_is_the_recurrence(tiny, form):
    """``S_t = g_h S + k^T v``, ``o = q S`` with the layer's decays: the
    SSD chunked form (dt = 1, a = log g, B = k, C = q, x = v, a group a
    head) and the decode kernel step by step, against the recurrence."""
    model, _ = tiny
    c = model.cfg
    t, h, d = 40, c.lightning_nh, c.lightning_head_dim
    rng = np.random.default_rng(4)
    q, k, v = (jnp.asarray(rng.standard_normal((t, h, d)) * 0.3,
                           jnp.float32) for _ in range(3))
    a = lightning_log_decay(c, 1)
    g = jnp.exp(a)

    def step(s, x):
        q_t, k_t, v_t = x
        s = s * g[:, None, None] + k_t[:, :, None] * v_t[:, None, :]
        return s, jnp.einsum("hk,hkv->hv", q_t, s)

    s_want, y_want = jax.lax.scan(step, jnp.zeros((h, d, d)), (q, k, v))
    ones = jnp.ones((t, h))
    if form == "chunked":
        y, s = ssd_chunked(v, ones, a, k, q, jnp.zeros((h, d, d)), chunk=8)
    else:
        pool = jnp.zeros((2, h, d, d))
        ys = []
        for i in range(t):
            yi, pool = ssm_decode(pool, jnp.asarray([1]), v[i:i + 1],
                                  ones[:1], a, k[i:i + 1], q[i:i + 1],
                                  interpret=True)
            ys.append(yi[0])
        y, s = jnp.stack(ys), pool[1]
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_want),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_want),
                               rtol=1e-4, atol=1e-5)
    y_rec, _ = ssd_recurrent(v, ones, a, k, q, jnp.zeros((h, d, d)))
    np.testing.assert_allclose(np.asarray(y_rec), np.asarray(y_want),
                               rtol=1e-4, atol=1e-5)


def test_the_pool_holds_three_sparse_leaves_and_a_state_a_lightning_layer(
        tiny):
    model, _ = tiny
    c = model.cfg
    pool = PagedSlotPool(model, 3, 64, jnp.float32, block_size=8,
                         prefix_cache=False)
    assert pool.layer_groups == ("global", "state", "state", "state")
    assert sorted(pool.caches[0]) == ["ck", "k", "v"]
    assert pool.caches[0]["ck"].shape == (1 + 3 * 8, 4 * 2, 16)
    assert pool.caches[0]["ck"].dtype == jnp.float32
    assert pool.caches[1]["s"].shape == (4, c.lightning_nh, 16, 16)
    assert pool.bytes_per_block == 2 * 8 * 32 * 4 + 8 * 16 * 4


def test_sparse_block_counts_follow_the_rule(tiny):
    """``serve.sparse.*`` from the lengths alone: a row at or below
    dense_len reads every block it holds; past it the init block, the
    window's blocks and the top-k; summed over 2 K/V heads, 1 layer."""
    model, _ = tiny
    read, held = model.sparse_blocks(np.asarray([0, 5, 32, 33, 96, 100]))
    # held 1, 4, 5, 12, 13 blocks; read all of the first three, then the
    # init block + the window's 2 (3 at 100) + the top-4
    assert held == 2 * (1 + 4 + 5 + 12 + 13)
    assert read == 2 * (1 + 4 + 5 + 7 + 8)


@pytest.mark.parametrize("what", ["prefix_cache", "int8", "speculative",
                                  "host_tier", "mesh", "block_size"])
def test_typed_refusals_fire_at_start_up(tiny, what):
    model, variables = tiny
    if what == "prefix_cache":
        with pytest.raises(ValueError, match="prefix_cache with state"):
            _engine(model, variables, prefix_cache=True)
    elif what == "int8":
        with pytest.raises(ValueError, match="int8"):
            _engine(model, variables, kv_dtype="int8")
    elif what == "speculative":
        with pytest.raises(ValueError, match="speculative decoding not "
                                             "supported"):
            _engine(model, variables,
                    speculative=SpeculativeConfig(draft_k=2))
    elif what == "host_tier":
        with pytest.raises(ValueError, match="host_blocks requires"):
            PagedSlotPool(model, 2, 32, jnp.float32, block_size=8,
                          prefix_cache=False, host_blocks=4)
    elif what == "block_size":
        with pytest.raises(ValueError, match="selects blocks of 8"):
            _engine(model, variables, kv_block_size=4)
    else:
        from nezha_tpu.serve.sharded import ShardedEngine
        with pytest.raises(ValueError, match="a device mesh"):
            ShardedEngine(model, variables, ServeConfig(
                max_batch_size=2, max_len=32, kv_block_size=8,
                cache_dtype=jnp.float32, prefix_cache=False), mesh_devices=2)
