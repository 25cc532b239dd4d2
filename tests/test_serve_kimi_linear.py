"""Kimi-Linear on the serving path, at tiny size on the CPU rig: the model
against the plain reference (``chipbench/reference/kimi_linear.py``: the
token recurrence, not the chunked form), the pool's state group beside the
growing table, the share test, the typed refusals, and a request preempted
and resumed by re-prefill.

Seeded random weights and logits throughout, never sampled tokens (the one
greedy stream compared is compared token for token). The tiny preset
computes in float32, so every tolerance below is float32 round-off with a
wide margin (1e-4 absolute on logits of order 1; observed 2e-6): a program
that computed in bf16, whose rounding alone is 4e-3 of a value, fails each
of them by an order of magnitude or more.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench.reference import kimi_linear as ref
from nezha_tpu.models import mistral4
from nezha_tpu.models.kimi_linear import KimiLinearConfig, kimi_linear
from nezha_tpu.models.mistral4 import MLAttention
from nezha_tpu.parallel.expert import DroplessMoE, DroplessMoEConfig
from nezha_tpu.serve import (Engine, Request, Scheduler, ServeConfig,
                             SpeculativeConfig)
from nezha_tpu.serve.slots import PagedSlotPool

F32_TOL = 1e-4


def ref_cfg(c: KimiLinearConfig) -> dict:
    """The reference's view of a config: the published keys, as the
    configuration file spells them."""
    return {
        "num_attention_heads": c.num_attention_heads,
        "kv_lora_rank": c.kv_lora_rank,
        "qk_nope_head_dim": c.qk_nope_head_dim,
        "qk_rope_head_dim": c.qk_rope_head_dim, "v_head_dim": c.v_head_dim,
        "rms_norm_eps": c.rms_norm_eps,
        "linear_attn_config": {
            "kda_layers": list(c.kda_layers),
            "full_attn_layers": list(c.full_attn_layers),
            "num_heads": c.kda_num_heads, "head_dim": c.kda_head_dim,
            "short_conv_kernel_size": c.short_conv_kernel_size},
        "num_experts_per_token": c.num_experts_per_token,
        "moe_renormalize": c.moe_renormalize,
        "routed_scaling_factor": c.routed_scaling_factor,
        "experts_held": list(c.experts_held)}


@pytest.fixture(scope="module")
def tiny():
    model = kimi_linear("tiny")
    return model, model.init(jax.random.PRNGKey(0))


@pytest.fixture(autouse=True)
def folded_tables(monkeypatch):
    """A prefill chunk folds a table of over 4,096 keys a key block at a
    time, as the deployment's 16,384-key table is; the engines here have
    tables of 96 keys, so the limit comes down to 0 and the key block to
    16, and every prefill below takes the deployment's path."""
    monkeypatch.setattr(mistral4, "GATHERED_KEYS_MAX", 0)
    monkeypatch.setattr(mistral4, "PREFILL_KEY_BLOCK", 16)


def _engine(model, variables, **kw):
    # KDA chunks of 8 inside buckets of 8 / 16; latent blocks of 4
    kw = {"max_batch_size": 3, "max_len": 96, "max_prefill_len": 16,
          "prefill_buckets": (8, 16), "kv_block_size": 4,
          "cache_dtype": jnp.float32, "prefix_cache": False, **kw}
    return Engine(model, variables, ServeConfig(**kw))


def _ref_row(variables, c, seq):
    """The reference's logits for the last token of ``seq``."""
    return ref.logits_at(variables["params"], jnp.asarray([seq], jnp.int32),
                         jnp.asarray([[len(seq) - 1]]), ref_cfg(c))[0, 0]


def test_the_tiny_preset_has_every_kind_of_layer(tiny):
    model, variables = tiny
    c = model.cfg
    assert [c.is_kda(i) for i in range(5)] == [True, True, True, False, True]
    assert "mlp" in variables["params"]["h0"]
    assert "moe" in variables["params"]["h1"]
    # the MLA layer's direct query projection: no low-rank pair
    assert sorted(variables["params"]["h3"]["attn"]) == [
        "kv_a", "kv_a_norm", "kv_b", "o", "q"]
    # decays spread over (0, 1), as drawn
    a = np.exp(np.asarray(variables["params"]["h0"]["attn"]["a_log"]))
    assert 1.0 <= a.min() and a.max() <= 16.0


# (a) the cache-less forward: 40 tokens = five KDA chunks of 8
def test_cacheless_forward_matches_reference(tiny):
    model, variables = tiny
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0, 512)
    got, _ = model.apply(variables, toks)
    want = ref.logits_at(variables["params"], toks,
                         jnp.tile(jnp.arange(40)[None], (2, 1)),
                         ref_cfg(model.cfg))
    assert got.dtype == jnp.float32 and got.shape == (2, 40, 512)
    assert float(jnp.abs(want).max()) > 0.3
    assert float(jnp.abs(got - want).max()) < F32_TOL


# (a) prefill in chunks, then decode through both groups, rows of
# different lengths in one batch. Prompt 37 = chunks 16 + 16 + 5 (in the 8
# bucket): the chunk boundaries at 16 and 32 fall inside the convolution's
# reach, the tail chunk is mostly pad (3 of 8); prompt 2 is shorter than
# the convolution's kernel; prompt 17 = 16 + 1: a last chunk of one real
# token, whose tail is two rows of the chunk before it.
@pytest.mark.parametrize("impl", ["xla", "kernel"])
def test_engine_prefill_and_decode_match_reference(tiny, impl):
    model, variables = tiny
    eng = _engine(model, variables, decode_impl=impl)
    assert eng.pool.state_entries == 1 and eng.pool.window is None
    rng = np.random.default_rng(3)
    seqs = [rng.integers(0, 512, n).tolist() for n in (37, 2, 17)]
    for seq in seqs:
        eng.prefill(eng.pool.alloc(), seq, max_new_tokens=40)
    active = np.ones(3, bool)
    worst = 0.0
    for _ in range(12):
        for r, seq in enumerate(seqs):
            worst = max(worst, float(jnp.abs(
                eng.last_logits[r] - _ref_row(variables, model.cfg, seq)).max()))
        tok, emitted = eng.step(active)
        assert emitted.tolist() == [1, 1, 1]
        for r, seq in enumerate(seqs):
            seq.append(int(tok[r, 0]))
    assert worst < F32_TOL
    assert eng.last_expert_load.shape == (4, 4)     # sparse layers x held
    # one state entry a slot; the growing group holds what was written
    assert eng.pool.state_slots_bound == 3
    assert eng.pool.blocks_used == sum(-(-len(s) // 4) for s in seqs)
    for slot in range(3):
        eng.pool.free(slot)
    eng.pool.leak_check()
    assert eng.pool.state_slots_bound == 0 and eng.pool.blocks_used == 0


def test_a_row_that_stops_leaves_its_state_and_the_others_exact(tiny):
    """An inactive row updates the scratch entry, not its own, and attends
    nothing; when it goes on, its logits are the uninterrupted ones."""
    model, variables = tiny
    eng = _engine(model, variables)
    rng = np.random.default_rng(4)
    seqs = [rng.integers(0, 512, n).tolist() for n in (9, 14)]
    for seq in seqs:
        eng.prefill(eng.pool.alloc(), seq, max_new_tokens=30)
    active = np.array([True, True, False])
    for i in range(12):
        active[0] = not 4 <= i < 8      # row 0 sits out four steps
        tok, _ = eng.step(active)
        for r in np.flatnonzero(active[:2]):
            seqs[r].append(int(tok[r, 0]))
    assert len(seqs[0]) == 9 + 8 and len(seqs[1]) == 14 + 12
    for r in (0, 1):
        assert float(jnp.abs(eng.last_logits[r] - _ref_row(
            variables, model.cfg, seqs[r])).max()) < F32_TOL


# (a), (f) a slot reused after free: the second request must not see the
# first one's state, whatever the entry holds
def test_a_reused_slot_starts_from_zero(tiny):
    model, variables = tiny
    eng = _engine(model, variables, max_batch_size=1)
    rng = np.random.default_rng(5)
    first = rng.integers(0, 512, 23).tolist()
    slot = eng.pool.alloc()
    entry = int(eng.pool.state_tables_host[slot, 0])
    eng.prefill(slot, first, max_new_tokens=4)
    eng.step(np.ones(1, bool))
    eng.pool.free(slot)
    assert eng.pool.state_tables_host[slot, 0] == 0
    # what a stale entry could hold at worst: not even finite
    eng.pool.caches = [
        {k: v.at[entry].set(jnp.nan) if k in ("s", "conv") else v
         for k, v in layer.items()} for layer in eng.pool.caches]
    second = rng.integers(0, 512, 19).tolist()
    slot = eng.pool.alloc()
    assert int(eng.pool.state_tables_host[slot, 0]) == entry
    eng.prefill(slot, second, max_new_tokens=4)
    for _ in range(3):
        assert float(jnp.abs(eng.last_logits[0] - _ref_row(
            variables, model.cfg, second)).max()) < F32_TOL
        tok, _ = eng.step(np.ones(1, bool))
        second.append(int(tok[0, 0]))


# (f) the pool: one state entry a slot from alloc to free, leak_check over
# both groups, bytes of each group
def test_pool_keeps_one_state_entry_a_slot(tiny):
    model, _ = tiny
    pool = PagedSlotPool(model, 3, 32, jnp.float32, block_size=4,
                         prefix_cache=False)
    assert pool.layer_groups == ("state", "state", "state", "global", "state")
    assert pool.state_entries == 1 and pool.window_entries == 0
    # the state leaves are per slot (+ the scratch entry), whatever max_len
    assert pool.caches[0]["s"].shape == (4, 4, 16, 16)
    assert pool.caches[0]["s"].dtype == jnp.float32
    assert pool.caches[0]["conv"].shape == (4, 3, 3 * 4 * 16)
    assert pool.caches[3]["latent"].shape == (1 + 3 * 8, 4, 128)
    per_entry = 4 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    assert pool.state_bytes_per_entry == per_entry
    a, b = pool.alloc(), pool.alloc()
    assert pool.state_slots_bound == 2
    assert pool.state_bytes_resident == 2 * per_entry
    assert set(pool.device_tables()) == {"global", "state"}
    assert pool.device_tables()["state"].shape == (3, 1)
    entries = {int(pool.state_tables_host[s, 0]) for s in (a, b)}
    assert len(entries) == 2 and 0 not in entries
    pool.prepare_write(a, 0, 9)
    assert pool.blocks_used == 3 and pool.state_slots_bound == 2
    pool.leak_check()
    pool.free(a)
    assert pool.state_slots_bound == 1 and pool.blocks_used == 0
    pool.leak_check()
    # the books catch an entry that leaks
    pool.state_tables_host[b, 0] = 0
    with pytest.raises(AssertionError, match="state entry"):
        pool.leak_check()


# (f) each typed refusal
def test_typed_refusals(tiny):
    model, variables = tiny
    with pytest.raises(ValueError, match="prefix_cache with state layers"):
        _engine(model, variables, prefix_cache=True)
    with pytest.raises(ValueError, match="int8"):
        _engine(model, variables, kv_dtype="int8")
    with pytest.raises(ValueError, match="speculative decoding not "
                                         "supported"):
        _engine(model, variables, speculative=SpeculativeConfig(draft_k=2))
    pool = _engine(model, variables).pool
    assert not pool.kv_wire
    with pytest.raises(ValueError, match="no migration wire format"):
        pool.export_block_payload(0, 1)
    with pytest.raises(ValueError, match="host_blocks requires"):
        PagedSlotPool(model, 2, 32, jnp.float32, block_size=4,
                      prefix_cache=False, host_blocks=4)
    from nezha_tpu.serve.sharded import ShardedEngine
    with pytest.raises(ValueError, match="a device mesh"):
        ShardedEngine(model, variables, ServeConfig(
            max_batch_size=2, max_len=32, kv_block_size=4,
            cache_dtype=jnp.float32, prefix_cache=False), mesh_devices=2)


def test_cli_refuses_what_the_state_group_cannot_do():
    from nezha_tpu.cli import serve as cli
    base = ["--model", "kimi_linear", "--random-init", "--model-preset",
            "tiny", "--max-len", "32", "--kv-block-size", "4",
            "--platform", "cpu"]
    for flags, what in ((["--kv-dtype", "int8"], "--kv-dtype int8"),
                        (["--mesh", "2"], "--mesh"),
                        (["--speculative"], "--speculative")):
        with pytest.raises(SystemExit, match=what):
            cli._build_stack(cli.build_parser().parse_args(
                base + ["--prefix-cache", "off"] + flags))
    with pytest.raises(SystemExit, match="prefix_cache with state layers"):
        cli._build_stack(cli.build_parser().parse_args(
            base + ["--prefix-cache", "on"]))


# (f) a request preempted and resumed by re-prefill against its
# uninterrupted stream (greedy)
def test_preempted_request_resumes_to_the_same_stream(tiny):
    model, variables = tiny

    def serve(preempt):
        eng = _engine(model, variables, max_batch_size=1,
                      preemption=preempt, queue_capacity=8)
        sched = Scheduler(eng)
        rng = np.random.default_rng(6)
        out = {}
        sched.on_finish = lambda r: out.__setitem__(r.request_id, r.tokens)
        sched.submit(Request(prompt=rng.integers(0, 512, 21).tolist(),
                             max_new_tokens=24, temperature=0.0,
                             request_id="low", priority="background"))
        for _ in range(9):
            sched.step()
        if preempt:
            sched.submit(Request(prompt=rng.integers(0, 512, 7).tolist(),
                                 max_new_tokens=3, temperature=0.0,
                                 request_id="high",
                                 priority="interactive"))
        sched.run_until_idle()
        eng.pool.leak_check()
        return out, sched

    plain, _ = serve(False)
    mixed, sched = serve(True)
    assert sched.preemptions >= 1 and len(mixed["high"]) == 3
    assert len(plain["low"]) == 24
    assert mixed["low"] == plain["low"]


# (e) the share test: four chips' routed parts plus the shared expert
# counted once add up to the uncut layer
def test_four_shares_add_up_to_the_whole_layer(tiny):
    model, variables = tiny
    c = model.cfg
    h = c.hidden_size
    whole_cfg = DroplessMoEConfig(
        d_model=h, d_ff=c.moe_intermediate_size, num_experts=c.num_experts,
        top_k=c.num_experts_per_token, experts_held=(0, c.num_experts),
        norm_topk_prob=True, routed_scaling_factor=c.routed_scaling_factor,
        score_func="sigmoid")
    whole = DroplessMoE(whole_cfg)
    wv = whole.init(jax.random.PRNGKey(8))
    x = jax.random.normal(jax.random.PRNGKey(9), (24, h))
    want, _ = whole.apply(wv, x)
    total = jnp.zeros_like(want)
    for share in range(4):
        first = share * 4
        part = DroplessMoE(dataclasses.replace(whole_cfg,
                                               experts_held=(first, 4)))
        pv = {"params": {
            "router": wv["params"]["router"],
            **{n: wv["params"][n][first:first + 4]
               for n in ("w_gate", "w_up", "w_down")}}, "state": {}}
        y, st = part.apply(pv, x)
        total = total + y
        assert int(st["load"].sum()) > 0
    assert float(jnp.abs(want).max()) > 1e-3
    assert float(jnp.abs(total - want).max()) < 1e-5


# (f) MLAttention with q_lora_rank=None and no rotation against the
# reference: position-free, so a shifted copy of a sequence gives the
# shifted outputs
def test_mla_without_low_rank_query_or_rotation(tiny):
    model, variables = tiny
    attn = model.h[3].attn
    assert isinstance(attn, MLAttention) and not attn.rotates
    p = {"params": variables["params"]["h3"]["attn"], "state": {}}
    x = jax.random.normal(jax.random.PRNGKey(11), (2, 12, 64))
    got, _ = attn.apply(p, x)
    with jax.default_matmul_precision("highest"):
        want = ref._mla(p["params"], x, ref_cfg(model.cfg), lambda a: a)
    assert float(jnp.abs(want).max()) > 1e-3
    assert float(jnp.abs(got - want).max()) < 1e-5
    # no position enters: positions offset by 1,000 change nothing
    q0, r0, lat0 = attn.project(p, x, jnp.arange(12)[None])
    q1, r1, lat1 = attn.project(p, x, 1000 + jnp.arange(12)[None])
    assert all(bool(jnp.array_equal(a, b))
               for a, b in ((q0, q1), (r0, r1), (lat0, lat1)))
    # the cached row: [c_kv | k_pe | zeros] in whole 128-lane tiles
    assert lat0.shape[-1] == 128
    assert not np.asarray(lat0[..., 32:]).any()
