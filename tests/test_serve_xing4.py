"""Xing4.0 on the serving path, at tiny size on the CPU rig: the model against
the plain reference (``chipbench/reference/xing4.py``), prefill in chunks and
then decode through the paged latent table, through ``Scheduler`` + ``Engine``
as ``_build_stack`` builds them, the three departures that must be caught, the
scalar the serve programs return, and the registry of served models.

Seeded random weights and logits throughout. The tiny preset computes in
float32, so the tight tolerance is float32 round-off with a wide margin (1e-4
absolute on logits of order 1; observed 3e-7); under the bf16 policy the
stated tolerance is 16 bf16 ulps of the largest reference logit (observed
3-5), the limit ``chipbench/drivers/serve_lm.py`` holds Mistral-Small-4 to.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench.reference import xing4 as ref
from test_hyper_connections import bf16_maps
from nezha_tpu.cli import common, serve as cli
from nezha_tpu.models import mistral4
from nezha_tpu.models.xing4 import Xing4, Xing4Config, xing4
from nezha_tpu.nn import hyper_connections
from nezha_tpu.serve import Engine, Request, ServeConfig
from nezha_tpu.tensor.policy import Policy

F32_TOL = 1e-4
BF16_TOL_ULPS = 16


def ref_cfg(c: Xing4Config) -> dict:
    """The reference's view of a config: the published keys, as the
    configuration file spells them."""
    keys = ("num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "rms_norm_eps", "rope_theta",
            "hc_mult", "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
            "mhc_h_res_clamp_max", "num_experts_per_tok", "norm_topk_prob",
            "routed_scaling_factor", "first_k_dense_replace")
    return {**{k: getattr(c, k) for k in keys},
            "experts_held": list(c.experts_held),
            "rope_scaling": {
                "type": "yarn", "factor": c.rope_factor,
                "original_max_position_embeddings": c.rope_original_max,
                "beta_fast": c.rope_beta_fast, "beta_slow": c.rope_beta_slow,
                "mscale": c.rope_mscale,
                "mscale_all_dim": c.rope_mscale_all_dim}}


@pytest.fixture(scope="module")
def tiny():
    model = xing4("tiny")
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
    kw = {"max_batch_size": 3, "max_len": 96, "max_prefill_len": 16,
          "prefill_buckets": (8, 16), "kv_block_size": 4,
          "cache_dtype": jnp.float32, **kw}
    return Engine(model, variables, ServeConfig(**kw))


def _ref_rows(variables, c, seq, first: int):
    """The reference's logits for the tokens of ``seq`` from index
    ``first`` on. One padded length for every call (the mask is causal, so
    what follows a token does not reach it): one compiled layer."""
    padded = np.zeros((1, 96), np.int32)
    padded[0, :len(seq)] = seq
    return ref.logits_at(variables["params"], jnp.asarray(padded),
                         jnp.arange(first, len(seq))[None], ref_cfg(c))[0]


def _worst_against_reference(model, variables, steps: int = 6,
                             published=None, **kw):
    """Prefill three prompts in chunks (37 = 16 + 16 + 5 in the 8 bucket; 2,
    shorter than a block; 17 = 16 + 1, a last chunk of one real token), then
    ``steps`` decode steps of the three rows together; -> (the largest logit
    difference from the reference's full forward pass over every compared
    row, the largest reference logit, the engine). ``published``: the
    config the reference runs, where ``model`` departs from it."""
    published = published or model.cfg
    eng = _engine(model, variables, **kw)
    rng = np.random.default_rng(3)
    seqs = [rng.integers(0, 512, n).tolist() for n in (37, 2, 17)]
    for seq in seqs:
        eng.prefill(eng.pool.alloc(), seq, max_new_tokens=40)
    active = np.ones(3, bool)
    worst = top = 0.0
    for _ in range(steps):
        for r, seq in enumerate(seqs):
            want = _ref_rows(variables, published, seq, len(seq) - 1)[0]
            worst = max(worst, float(jnp.abs(eng.last_logits[r] - want).max()))
            top = max(top, float(jnp.abs(want).max()))
        tok, emitted = eng.step(active)
        assert emitted.tolist() == [1, 1, 1]
        for r, seq in enumerate(seqs):
            seq.append(int(tok[r, 0]))
    return worst, top, eng


def test_the_tiny_preset_has_both_kinds_of_layer_and_four_streams(tiny):
    model, variables = tiny
    p = variables["params"]
    assert (model.cfg.hc_mult, model.cfg.hc_sinkhorn_iters) == (4, 20)
    assert "mlp" in p["h0"] and "mlp" in p["h1"]
    assert "moe" in p["h2"] and "shared" in p["h3"]
    assert p["h2"]["moe"]["router"]["bias"].dtype == jnp.float32
    for name in ("hc_attn", "hc_mlp"):
        hc = p["h2"][name]
        assert hc["phi"].shape == (24, 4 * 64) and hc["b"].shape == (24,)
        assert {a.dtype for a in hc.values()} == {jnp.dtype(jnp.float32)}
        # x~ phi spreads by 2.4 at any width: the maps depend on their token
        assert float(hc["phi"].std()) * 256 ** 0.5 == pytest.approx(2.4, rel=.1)
    assert model.mhc_sublayers == 8


def test_cacheless_forward_matches_reference(tiny):
    model, variables = tiny
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0, 512)
    got, states = model.apply(variables, toks)
    want = ref.logits_at(variables["params"], toks,
                         jnp.tile(jnp.arange(40)[None], (2, 1)),
                         ref_cfg(model.cfg))
    assert got.dtype == jnp.float32 and got.shape == (2, 40, 512)
    assert float(jnp.abs(want).max()) > 0.3
    assert float(jnp.abs(got - want).max()) < F32_TOL
    assert 0.0 < float(model.mhc_residual(states)) < 0.5


# prefill in chunks, then decode through the paged latent table, rows of
# different lengths in one batch; the composed path and the kernels (the
# paged decode kernel's latent form and both nezha_mhc kernels, interpreted)
@pytest.mark.parametrize("impl", ["xla", "kernel"])
def test_engine_prefill_and_decode_match_reference(tiny, impl):
    model, variables = tiny
    worst, _, eng = _worst_against_reference(model, variables, steps=8,
                                             decode_impl=impl)
    assert worst < F32_TOL
    assert eng.last_expert_load.shape == (2, 8)     # sparse layers x held
    # every chunk's and every step's residual has been read
    assert not eng._mhc_pending
    assert 0.0 < eng.mhc_residual_max < 0.5


def test_a_block_of_steps_adds_the_counts_and_keeps_the_worst_residual(tiny):
    """``decode_horizon`` 4: one program scans four steps; its expert
    counts are the four steps' sums, its residual the worst of the four
    (each reduced by NAME in the step program, not by dtype), and the
    tokens are the single steps' tokens."""
    model, variables = tiny
    runs = {}
    for horizon in (1, 4):
        eng = _engine(model, variables, decode_horizon=horizon)
        eng.prefill(eng.pool.alloc(), list(range(3, 24)), max_new_tokens=40)
        eng.mhc_residual_max = 0.0      # the steps' alone, from here on
        active = np.array([True, False, False])
        toks, load = [], 0
        for _ in range(4 // horizon):
            tok, emitted = eng.step(active)
            assert emitted[0] == horizon
            toks += tok[0, :horizon].tolist()
            load = load + eng.last_expert_load
        runs[horizon] = (toks, load, eng.mhc_residual_max)
    assert runs[1][0] == runs[4][0]
    assert np.array_equal(runs[1][1], runs[4][1]) and runs[4][1].sum() == 16
    assert runs[4][2] == pytest.approx(runs[1][2], rel=1e-5) and runs[4][2] > 0


def test_engine_under_the_bf16_policy_is_within_its_stated_tolerance():
    model = xing4("tiny", policy=Policy(jnp.bfloat16, jnp.bfloat16))
    variables = model.init(jax.random.PRNGKey(0))
    hc = variables["params"]["h0"]["hc_attn"]
    assert {a.dtype for a in hc.values()} == {jnp.dtype(jnp.float32)}
    worst, top, _ = _worst_against_reference(
        model, variables, steps=4, cache_dtype=jnp.bfloat16)
    assert worst < BF16_TOL_ULPS * 2.0 ** -8 * max(1.0, top)
    assert worst > F32_TOL      # and it is bf16 that was run


# What the comparison must catch: each departure run through the same
# prefill and decode, against the reference as published.
@pytest.mark.parametrize("departure", ["two_rounds", "no_clamp", "bf16_maps"])
def test_departures_from_the_equations_fail_the_comparison(tiny, departure,
                                                          monkeypatch):
    model, variables = tiny
    published = model.cfg
    if departure == "two_rounds":
        model = Xing4(dataclasses.replace(model.cfg, hc_sinkhorn_iters=2))
    elif departure == "no_clamp":
        # a row of H~res with two entries past the clamp (at the drawn
        # weights none comes near it): clamped they weigh alike
        variables = jax.tree_util.tree_map(lambda a: a, variables)
        b = variables["params"]["h1"]["hc_mlp"]["b"]
        variables["params"]["h1"]["hc_mlp"]["b"] = b.at[8].set(40.0).at[
            9].set(35.0)
        sound, _, _ = _worst_against_reference(model, variables, steps=2)
        assert sound < F32_TOL
        model = Xing4(dataclasses.replace(
            model.cfg, mhc_h_res_clamp_min=-1e9, mhc_h_res_clamp_max=1e9))
    else:
        monkeypatch.setattr(hyper_connections, "mhc_pre_composed", bf16_maps)
    worst, _, _ = _worst_against_reference(model, variables, steps=2,
                                           published=published)
    assert worst > 5 * F32_TOL     # the comparison holds F32_TOL


_TINY_ARGV = ("--model xing4 --random-init --model-preset tiny --max-len 96 "
              "--max-batch-size 3 --max-prefill-len 16 --prefill-buckets 8,16 "
              "--kv-block-size 4 --cache-dtype f32 --prefix-cache on "
              "--platform cpu").split()


def test_scheduler_and_engine_from_build_stack_follow_the_reference():
    """``nezha-serve --model xing4`` as ``_build_stack`` builds it: greedy
    requests of three lengths through the scheduler; every emitted token is
    the reference's own choice at that point of the stream (teacher-forced;
    a token whose top-2 margin in the reference is inside the tolerance is
    set aside: none is, here), and a prompt sent again takes its prefix
    from the trie."""
    sched, _, _ = cli._build_stack(cli.build_parser().parse_args(_TINY_ARGV))
    eng = sched.engine
    assert type(eng.model).__name__ == "Xing4" and eng.pool.prefix_cache_enabled
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 512, n).tolist() for n in (5, 33, 70)]
    done = {}
    sched.on_finish = lambda res: done.__setitem__(res.request_id, res)
    for i, prompt in enumerate(prompts + [prompts[2]]):
        sched.submit(Request(prompt=prompt, max_new_tokens=5, temperature=0.0,
                             request_id=f"r{i}"))
        if i == 2:
            sched.run_until_idle()
    sched.run_until_idle()
    assert eng.pool.prefix_hits == 1
    assert done["r3"].tokens == done["r2"].tokens
    checked = 0
    for i, prompt in enumerate(prompts):
        toks = done[f"r{i}"].tokens
        assert done[f"r{i}"].finish_reason == "length" and len(toks) == 5
        seq = prompt + toks
        rows = _ref_rows(eng.variables, eng.model.cfg, seq[:-1],
                         len(prompt) - 1)
        for tok, row in zip(toks, np.asarray(rows)):
            top2 = np.sort(row)[-2:]
            if top2[1] - top2[0] > 2 * F32_TOL:
                assert tok == int(row.argmax())
                checked += 1
    assert checked == 15
    assert 0.0 < eng.mhc_residual_max < 0.5


@pytest.mark.parametrize("flag", [("--kv-dtype", "int8"), ("--mesh", "2"),
                                  ("--speculative",)])
def test_what_is_written_for_per_head_kv_refuses_typed(flag):
    args = cli.build_parser().parse_args(_TINY_ARGV + list(flag))
    with pytest.raises(SystemExit, match="--model xing4: not supported"):
        cli._build_stack(args)


def test_the_served_models_are_one_table():
    """``--model``'s choices, its help and the loader read one table; a
    model outside it is refused by the parser, and all but gpt2 take
    ``--random-init`` only."""
    parser = cli.build_parser()
    action = next(a for a in parser._actions if a.dest == "model")
    assert tuple(action.choices) == common.SERVED_MODELS
    assert common.SERVED_MODELS == ("gpt2", *common.RANDOM_INIT_MODELS)
    for name, (module, builder, what) in common.RANDOM_INIT_MODELS.items():
        assert f"{name} ({what})" in action.help
        mod = __import__(f"nezha_tpu.models.{module}", fromlist=[builder])
        assert callable(getattr(mod, builder))
    with pytest.raises(SystemExit):
        parser.parse_args(["--random-init", "--model", "no_such_model"])
    args = parser.parse_args(["--ckpt-dir", "/nowhere", "--model", "xing4"])
    with pytest.raises(SystemExit, match="takes --random-init only"):
        common.load_model_for_inference(args)
