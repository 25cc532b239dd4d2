"""Mistral-Small-4 on the serving path, at tiny size on the CPU rig.

Seeded random weights and logits throughout, never sampled tokens. The
tiny preset computes in float32, so every tolerance below is float32
round-off over a few dozen 64-wide contractions with a wide margin
(1e-4 absolute on logits of order 1; observed 2e-7 to 3e-6): a program
that computed in bf16, whose rounding alone is 4e-3 of a value, fails
each of them by an order of magnitude or more.
"""

import dataclasses
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench.reference import mistral4 as ref
from nezha_tpu.models import mistral4
from nezha_tpu.models.gpt2 import GPT2, GPT2Config
from nezha_tpu.models.mistral4 import (TINY_KW, Mistral4, Mistral4Config,
                                       mistral_small4)
from nezha_tpu.ops import rotary
from nezha_tpu.parallel.expert import DroplessMoE, DroplessMoEConfig
from nezha_tpu.serve import Engine, ServeConfig
from nezha_tpu.serve.slots import PagedSlotPool

F32_TOL = 1e-4


def ref_cfg(c: Mistral4Config) -> dict:
    """The reference's view of a config: the published keys, as the
    configuration file spells them."""
    return {
        "num_attention_heads": c.num_attention_heads,
        "qk_nope_head_dim": c.qk_nope_head_dim,
        "qk_rope_head_dim": c.qk_rope_head_dim,
        "v_head_dim": c.v_head_dim, "kv_lora_rank": c.kv_lora_rank,
        "rms_norm_eps": c.rms_norm_eps,
        "num_experts_per_tok": c.num_experts_per_tok,
        "norm_topk_prob": c.norm_topk_prob,
        "routed_scaling_factor": c.routed_scaling_factor,
        "experts_held": list(c.experts_held),
        "rope_parameters": {
            "rope_theta": c.rope_theta, "factor": c.rope_factor,
            "original_max_position_embeddings": c.rope_original_max,
            "beta_fast": c.rope_beta_fast, "beta_slow": c.rope_beta_slow,
            "mscale_all_dim": c.rope_mscale_all_dim,
            "llama_4_scaling_beta": c.llama_4_scaling_beta}}


@pytest.fixture(scope="module")
def tiny():
    model = mistral_small4("tiny")
    return model, model.init(jax.random.PRNGKey(0))


def _engine(model, variables, **kw):
    # block 8, chunks of 16 with buckets 8/16: prompts below straddle
    # both a block and a chunk boundary
    cfg = ServeConfig(max_batch_size=3, max_len=96, max_prefill_len=16,
                      prefill_buckets=(8, 16), kv_block_size=8,
                      cache_dtype=jnp.float32, **kw)
    return Engine(model, variables, cfg)


def _reference_rows(model, variables, seq, first, count):
    """Reference logits at positions first-1 .. first+count-2 of seq."""
    pos = np.arange(first - 1, first - 1 + count)[None, :]
    return np.asarray(ref.logits_at(
        variables["params"], jnp.asarray([seq], jnp.int32),
        jnp.asarray(pos), ref_cfg(model.cfg)))[0]


# (a) program vs reference through the real Engine and paged latent pool:
# the composed decode view (what the default, "auto", resolves to off a
# TPU) with the gathered prefill view of a table of up to 4,096 keys (as
# this model's chunks are served), and the paged kernel's latent form in
# interpret mode (as its decode steps are served on a TPU) with a table
# folded a key block at a time (Kimi-Linear's prefill through the same
# class)
@pytest.mark.parametrize("paths", ["composed-gathered", "kernel-folded"])
@pytest.mark.parametrize("n_prompt", [13, 21, 37])
def test_engine_prefill_and_decode_match_reference(tiny, n_prompt, paths,
                                                   monkeypatch):
    model, variables = tiny
    kw = {}
    if paths == "kernel-folded":
        monkeypatch.setattr(mistral4, "GATHERED_KEYS_MAX", 0)
        monkeypatch.setattr(mistral4, "PREFILL_KEY_BLOCK", 16)
        kw["decode_impl"] = "kernel"
    eng = _engine(model, variables, **kw)
    assert eng.model.cfg.decode_impl == kw.get("decode_impl", "auto")
    rng = np.random.default_rng(n_prompt)
    prompt = rng.integers(0, model.cfg.vocab_held, n_prompt).tolist()
    slot = eng.pool.alloc()
    eng.prefill(slot, prompt, max_new_tokens=12)
    got = [np.asarray(eng.last_logits[slot])]
    active = np.zeros((3,), bool)
    active[slot] = True
    toks = []
    for _ in range(9):
        tok, emitted = eng.step(active)
        assert emitted[slot] == 1
        toks.append(int(tok[slot, 0]))
        got.append(np.asarray(eng.last_logits[slot]))
    want = _reference_rows(model, variables, prompt + toks, n_prompt, 10)
    assert np.abs(np.stack(got) - want).max() < F32_TOL
    # the counter: every active row's pairs land on held experts or not;
    # one row, top-4, so at most 4 a layer, and shaped [layers, held]
    load = eng.last_expert_load
    assert load.shape == (model.cfg.num_layers, model.cfg.experts_held[1])
    assert load.dtype == np.int32 and (load.sum(axis=1) <= 4).all()
    eng.pool.free(slot)
    eng.pool.leak_check()


# (b) absorbed vs expanded attention: the same function
def test_absorbed_attention_equals_expanded(tiny):
    model, variables = tiny
    attn = model.h[0].attn
    av = {"params": variables["params"]["h0"]["attn"], "state": {}}
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 40, model.cfg.hidden_size))
    pos = jnp.arange(40)[None, :]
    q_nope, q_rope, latent = attn.project(av, x, pos)
    causal = jnp.tril(jnp.ones((40, 40), bool))[None]
    full = attn.expanded(av, q_nope, q_rope, latent, causal)
    last = attn.absorbed(av, q_nope[:, -1:], q_rope[:, -1:], latent,
                         jnp.ones((2, 40), bool))
    assert jnp.abs(full[:, -1:] - last).max() < 1e-5


# (c) the share test: four shares + the shared expert once = the whole layer
def test_four_shares_add_up_to_the_uncut_layer(tiny):
    model, variables = tiny
    c = model.cfg
    whole = dataclasses.replace(c, experts_held=(0, c.n_routed_experts))
    blk = Mistral4(whole).init(jax.random.PRNGKey(5))["params"]["h0"]
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 50, c.hidden_size))
    want, _ = ref._moe(blk, x, ref_cfg(whole))          # the uncut layer
    shared = ref._gated(blk["shared"]["gate"]["w"], blk["shared"]["up"]["w"],
                        blk["shared"]["down"]["w"], x)
    total = shared                                      # counted once
    for first in range(0, c.n_routed_experts, 4):
        layer = DroplessMoE(DroplessMoEConfig(
            c.hidden_size, c.moe_intermediate_size, c.n_routed_experts,
            c.num_experts_per_tok, (first, 4)))
        share = {"router": blk["moe"]["router"],
                 **{k: blk["moe"][k][first:first + 4]
                    for k in ("w_gate", "w_up", "w_down")}}
        y, st = layer.apply({"params": share, "state": {}}, x[0])
        total = total + y[None]
        # and the program's share equals the reference's share
        got_ref, _ = ref._moe({**blk, "moe": share}, x,
                              {**ref_cfg(whole), "experts_held": [first, 4]})
        assert jnp.abs(shared + y[None] - got_ref).max() < 1e-5
    assert jnp.abs(total - want).max() < 1e-5


# (d) dropless: every token picks the same experts and none is lost
def test_dropless_when_every_token_picks_the_same_expert():
    cfg = DroplessMoEConfig(32, 16, 8, 2, (0, 4))
    layer = DroplessMoE(cfg)
    params = layer.init(jax.random.PRNGKey(0))["params"]
    # a router that sends every token to experts 1 and 2, whatever x is
    params["router"]["w"] = jnp.zeros((32, 8)).at[:, 1].set(1.0).at[:, 2].set(0.9)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (64, 32))) + 0.1
    y, st = layer.apply({"params": params, "state": {}}, x)
    assert st["load"].tolist() == [0, 64, 64, 0]
    w = jax.nn.softmax(x @ params["router"]["w"], -1)[:, 1:3]
    w = w / w.sum(-1, keepdims=True)
    want = sum(w[:, j:j + 1] * ref._gated(params["w_gate"][e], params["w_up"][e],
                                          params["w_down"][e], x)
               for j, e in enumerate((1, 2)))
    assert jnp.abs(y - want).max() < 1e-5
    assert float(jnp.abs(y).min(axis=1).max()) > 0      # no zeroed token


# (e) GPT-2's pool from its declaration: lane-dense K/V rows (PR 27; the
# same bytes a block as the per-head tiles it replaced), scales per head
@pytest.mark.parametrize("quantized", [False, True])
def test_gpt2_pool_leaves_unchanged(quantized):
    model = GPT2(GPT2Config(vocab_size=64, max_positions=64, num_layers=3,
                            num_heads=4, hidden_size=32))
    pool = PagedSlotPool(model, 2, 64, jnp.bfloat16, block_size=16,
                         quantized=quantized)
    n, h, bs, d = pool.num_blocks, 4, 16, 8
    assert n == 1 + 2 * 4 and len(pool.caches) == 3
    want = {"k": ((n, bs, h * d), jnp.int8 if quantized else jnp.bfloat16),
            "v": ((n, bs, h * d), jnp.int8 if quantized else jnp.bfloat16)}
    if quantized:
        want.update(k_scale=((n, h), jnp.float32), v_scale=((n, h), jnp.float32))
    for layer in pool.caches:
        assert list(layer) == list(want)
        for name, (shape, dt) in want.items():
            assert layer[name].shape == shape and layer[name].dtype == dt
    kv_bytes = h * bs * d * (1 if quantized else 2)
    assert pool.bytes_per_block == 2 * 3 * (kv_bytes + (h * 4 if quantized else 0))
    assert pool.kv_wire and pool.wire_block_shape == (h, bs, d)


def test_latent_pool_leaves_and_bytes(tiny):
    model, _ = tiny
    pool = PagedSlotPool(model, 2, 64, jnp.bfloat16, block_size=16)
    w = model.cfg.latent_row_width
    assert (model.cfg.latent_width, w) == (32, 128)
    assert Mistral4Config().latent_row_width == 384
    assert [list(layer) for layer in pool.caches] == [["latent"]] * 2
    assert pool.caches[0]["latent"].shape == (pool.num_blocks, 16, w)
    assert pool.bytes_per_block == 2 * 16 * w * 2 and not pool.kv_wire
    with pytest.raises(ValueError, match="no migration wire format"):
        pool.export_block_payload(0, 1)


# (f) a prefix-trie hit on the latent pool gives a cold prefill's logits
def test_prefix_hit_on_latent_pool_matches_cold_prefill(tiny):
    model, variables = tiny
    rng = np.random.default_rng(7)
    shared = rng.integers(0, 512, 24).tolist()          # three whole blocks
    first, second = shared + [5, 6, 7], shared + [9, 8, 7, 6, 5]
    eng = _engine(model, variables)
    a = eng.pool.alloc()
    eng.prefill(a, first)
    b = eng.pool.alloc()
    eng.prefill(b, second)
    assert eng.pool.prefix_hits == 1 and eng.last_prefill_tokens < len(second)
    hit = np.asarray(eng.last_logits[b])
    cold_eng = _engine(model, variables, prefix_cache=False)
    s = cold_eng.pool.alloc()
    cold_eng.prefill(s, second)
    cold = np.asarray(cold_eng.last_logits[s])
    assert np.abs(hit - cold).max() < 1e-5
    assert np.abs(hit - _reference_rows(model, variables, second,
                                        len(second), 1)[0]).max() < F32_TOL


# (g) yarn frequencies and the interleave against an independent formula
def test_yarn_frequencies_and_interleave():
    dim, theta, factor, n0, fast, slow = 64, 10000.0, 128.0, 8192, 32.0, 1.0
    got = np.asarray(rotary.yarn_inv_freq(dim, theta, factor, n0, fast, slow))
    want = []
    for i in range(dim // 2):
        f = theta ** (-2 * i / dim)
        turns = n0 * f / (2 * math.pi)          # turns within the original context
        # the pair index at which a frequency makes r turns, inverted
        lo = math.floor(dim * math.log(n0 / (fast * 2 * math.pi)) / (2 * math.log(theta)))
        hi = math.ceil(dim * math.log(n0 / (slow * 2 * math.pi)) / (2 * math.log(theta)))
        t = min(max((i - lo) / (hi - lo), 0.0), 1.0)
        want.append(f * (1 - t) + f / factor * t)
        if turns > fast * 1.5:
            assert got[i] == pytest.approx(f, rel=1e-6)         # kept
        if turns < slow / 1.5:
            assert got[i] == pytest.approx(f / factor, rel=1e-6)    # slowed
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # pairs are ADJACENT: (x0, x1) turns by pos * f0, (x2, x3) by pos * f1
    x = jnp.asarray([[1.0, 0.0, 0.0, 2.0]])
    f = jnp.asarray([0.5, 0.25])
    out = np.asarray(rotary.apply_interleaved(x, jnp.asarray([3.0]), f))[0]
    np.testing.assert_allclose(
        out, [math.cos(1.5), math.sin(1.5), -2 * math.sin(0.75),
              2 * math.cos(0.75)], rtol=1e-6)
    assert Mistral4Config().softmax_scale == pytest.approx(
        128 ** -0.5 * (0.1 * math.log(128) + 1) ** 2)


# refusals: typed, at start-up
@pytest.mark.parametrize("kw, match", [
    ({"kv_dtype": "int8"}, "no block quantizer"),
    ({"speculative": "on"}, "speculative"),
])
def test_typed_refusals(tiny, kw, match):
    model, variables = tiny
    if "speculative" in kw:
        from nezha_tpu.serve.engine import SpeculativeConfig
        kw = {"speculative": SpeculativeConfig(draft_k=2, draft_layers=1)}
    with pytest.raises(ValueError, match=match):
        _engine(model, variables, **kw)


def test_latent_cache_without_tables_is_refused(tiny):
    """The model's own guard: its attention takes the paged cache (a
    latent pool and the block tables) and nothing else."""
    model, variables = tiny
    (name, (shape, dtype)), = model.cache_leaves(8, jnp.float32)[0][2].items()
    rows = [{name: jnp.zeros((3,) + tuple(shape), dtype)}
            for _ in range(model.cfg.num_layers)]
    with pytest.raises(ValueError, match="block-paged only"):
        model.apply(variables, jnp.zeros((1, 4), jnp.int32),
                    cache=rows, pos=jnp.zeros((), jnp.int32))


def test_cli_builds_the_stack_and_refuses_what_it_cannot_serve():
    from nezha_tpu.cli import serve as cli
    base = ["--model", "mistral_small4", "--random-init", "--model-preset",
            "tiny", "--max-len", "64", "--max-batch-size", "2",
            "--max-prefill-len", "8", "--kv-block-size", "8",
            "--cache-dtype", "f32"]
    sched, _, _ = cli._build_stack(cli.build_parser().parse_args(base))
    assert type(sched.engine.model).__name__ == "Mistral4"
    assert sched.engine.vocab == TINY_KW["vocab_held"]
    for extra, match in ((["--mesh", "2"], "--mesh"),
                         (["--kv-dtype", "int8"], "int8"),
                         (["--speculative"], "--speculative"),
                         (["--kv-host-blocks", "4"], "--kv-host-blocks"),
                         (["--role", "prefill"], "KV migration")):
        with pytest.raises(SystemExit, match=match):
            cli._build_stack(cli.build_parser().parse_args(base + extra))
    with pytest.raises(SystemExit, match="--random-init only"):
        cli._build_stack(cli.build_parser().parse_args(
            ["--model", "mistral_small4", "--ckpt-dir", "/nonexistent"]))


def test_reference_router_margin_sees_a_held_expert_two_places_from_the_edge():
    """Experts 0-1 held of 8, two a token. Logits 1.000 (held), 0.999 and
    0.998 (absent): the last chosen and the first left out are both
    absent, yet 0.002 of noise costs the held expert its place. Then a
    held expert far inside and none near the edge: no finite margin."""
    cfg = {"num_experts_per_tok": 2, "experts_held": [0, 2],
           "norm_topk_prob": True, "routed_scaling_factor": 1.0}
    zeros = {"w": jnp.zeros((4, 4))}
    blk = {"shared": {"gate": zeros, "up": zeros, "down": zeros},
           "moe": {"router": {"w": jnp.zeros((4, 8))},
                   "w_gate": jnp.zeros((2, 4, 4)), "w_up": jnp.zeros((2, 4, 4)),
                   "w_down": jnp.zeros((2, 4, 4))}}
    x = jnp.asarray([[[1.0, 0.0, 0.0, 0.0]]])
    near = jnp.full((8,), -5.0).at[0].set(1.0).at[5].set(0.999).at[6].set(0.998)
    blk["moe"]["router"]["w"] = jnp.zeros((4, 8)).at[0].set(near)
    _, margin = ref._moe(blk, x, cfg)
    assert float(margin[0, 0]) == pytest.approx(0.002 / 2.0 ** -8, rel=1e-3)
    far = near.at[0].set(3.0).at[1].set(-5.0)
    blk["moe"]["router"]["w"] = jnp.zeros((4, 8)).at[0].set(far)
    _, margin = ref._moe(blk, x, cfg)
    # held 0 is 2.002 above the first left out, held 1 is 5.999 under the
    # last chosen: 2.002 in ulps of the last chosen logit (max(1, 0.999))
    assert float(margin[0, 0]) == pytest.approx(2.002 / 2.0 ** -8, rel=1e-3)
