"""GSPMD dp×tp tests: spec rules hit the right leaves, the sharded step
matches single-device numerics, params actually land sharded."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from nezha_tpu import optim, parallel
from nezha_tpu.models.gpt2 import GPT2, GPT2Config, lm_loss
from nezha_tpu.train.loop import init_train_state, make_train_step


def tiny_gpt2():
    return GPT2(GPT2Config(vocab_size=128, max_positions=32, num_layers=2,
                           num_heads=4, hidden_size=32))


def test_param_specs_rules():
    model = tiny_gpt2()
    params = model.init(jax.random.PRNGKey(0))["params"]
    specs = parallel.param_specs_from_rules(params, parallel.GPT2_TP_RULES)
    assert specs["h0"]["attn"]["qkv"]["w"] == P(None, "tp")
    assert specs["h0"]["attn"]["proj"]["w"] == P("tp", None)
    assert specs["h1"]["mlp"]["fc"]["b"] == P("tp")
    assert specs["wte"]["embedding"] == P("tp", None)
    assert specs["ln_f"]["scale"] == P()
    assert specs["wpe"]["embedding"] == P()


def test_strict_rules_cover_gpt2_and_bert():
    # The shipped tables fully enumerate their models (incl. the
    # deliberately-replicated tail), so strict mode passes.
    from nezha_tpu.models.bert import Bert, BertConfig
    gpt2 = tiny_gpt2().init(jax.random.PRNGKey(0))["params"]
    parallel.param_specs_from_rules(gpt2, parallel.GPT2_TP_RULES, strict=True)
    bert = Bert(BertConfig(vocab_size=128, max_positions=32, num_layers=1,
                           num_heads=2, hidden_size=32)).init(
        jax.random.PRNGKey(0))["params"]
    parallel.param_specs_from_rules(bert, parallel.BERT_TP_RULES, strict=True)


def test_auto_partitioner_flag_set_during_gspmd_trace(devices8):
    """Models consult under_auto_partitioner() to avoid auto-choosing
    Pallas kernels inside jit-with-shardings (Mosaic custom calls cannot
    be SPMD-auto-partitioned)."""
    from nezha_tpu.parallel.gspmd import under_auto_partitioner

    seen = []

    class Probe:
        def init(self, rng):
            return {"params": {"w": jnp.ones((4, 4))}, "state": {}}

        def apply(self, variables, batch, training=False, rng=None):
            seen.append(under_auto_partitioner())
            return batch["x"] @ variables["params"]["w"], {}

    mesh = parallel.make_mesh({"dp": 2, "tp": 4})
    model = Probe()
    opt = optim.sgd(1e-2)
    state = {"variables": model.init(None), "opt_state": opt.init(
        model.init(None)["params"]), "rng": jax.random.PRNGKey(0)}
    specs = {"w": P(None, "tp")}
    state = parallel.shard_train_state(state, mesh, specs)
    step = parallel.make_gspmd_train_step(
        model, opt, lambda out, b: (out ** 2).mean(), mesh, specs,
        donate=False)
    assert under_auto_partitioner() is False
    step(state, parallel.gspmd.shard_batch_gspmd(
        mesh, {"x": jnp.ones((2, 4))}))
    assert seen == [True]  # set during trace, only there
    assert under_auto_partitioner() is False


def test_strict_rules_fail_loudly():
    import pytest
    params = tiny_gpt2().init(jax.random.PRNGKey(0))["params"]
    # A renamed layer (rule no longer matches anything + param uncovered).
    params["h0"]["attn"]["qkv_renamed"] = params["h0"]["attn"].pop("qkv")
    with pytest.raises(ValueError, match="qkv_renamed"):
        parallel.param_specs_from_rules(params, parallel.GPT2_TP_RULES,
                                        strict=True)
    # An obsolete rule matching nothing also fails.
    with pytest.raises(ValueError, match="matching no parameter"):
        parallel.param_specs_from_rules(
            {"w": jnp.zeros((2, 2))},
            [(r"^w$", P(None, "tp")), (r"^gone$", P("tp"))], strict=True)


def test_gspmd_step_matches_single_device(devices8):
    mesh = parallel.make_mesh({"dp": 2, "tp": 4})
    model = tiny_gpt2()
    opt = optim.adamw(1e-3, weight_decay=0.0)

    state0 = init_train_state(model, opt, jax.random.PRNGKey(0))
    batch = {"tokens": jnp.asarray(
        np.random.RandomState(0).randint(0, 128, (8, 17)), jnp.int32)}

    # Single device reference.
    ref_step = make_train_step(model, opt, lm_loss, donate=False)
    ref_state, ref_m = ref_step(jax.tree_util.tree_map(jnp.copy, state0), batch)

    # dp=2 x tp=4 GSPMD.
    specs = parallel.param_specs_from_rules(
        state0["variables"]["params"], parallel.GPT2_TP_RULES)
    sharded = parallel.shard_train_state(state0, mesh, specs)
    step = parallel.make_gspmd_train_step(model, opt, lm_loss, mesh, specs,
                                          donate=False)
    new_state, m = step(sharded, parallel.gspmd.shard_batch_gspmd(mesh, batch))

    np.testing.assert_allclose(float(ref_m["loss"]), float(m["loss"]),
                               rtol=1e-4)
    for a, b in zip(jax.tree_util.tree_leaves(ref_state["variables"]["params"]),
                    jax.tree_util.tree_leaves(new_state["variables"]["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4,
                                   atol=5e-5)


def test_gspmd_params_are_physically_sharded(devices8):
    mesh = parallel.make_mesh({"dp": 2, "tp": 4})
    model = tiny_gpt2()
    opt = optim.adamw(1e-3)
    state = init_train_state(model, opt, jax.random.PRNGKey(0))
    specs = parallel.param_specs_from_rules(
        state["variables"]["params"], parallel.GPT2_TP_RULES)
    sharded = parallel.shard_train_state(state, mesh, specs)
    qkv_w = sharded["variables"]["params"]["h0"]["attn"]["qkv"]["w"]
    # (32, 96) sharded over tp=4 on dim 1 -> local (32, 24) per device.
    shapes = {s.data.shape for s in qkv_w.addressable_shards}
    assert shapes == {(32, 24)}
    # Optimizer stats follow the param layout (mu of qkv/w also sharded).
    mu = sharded["opt_state"]["mu"]["h0"]["attn"]["qkv"]["w"]
    assert {s.data.shape for s in mu.addressable_shards} == {(32, 24)}


def test_opt_state_specs_recurse_into_wrapped_optimizers(devices8):
    """accumulate_gradients nests the inner optimizer's state under
    "inner"; its mu/nu must inherit the param specs (sharded), not fall to
    a replicate-everything branch (found via --grad-accum x pp review)."""
    from jax.sharding import PartitionSpec as P

    from nezha_tpu import optim
    from nezha_tpu.parallel.gspmd import opt_state_specs

    params = {"w": jnp.zeros((8, 4)), "b": jnp.zeros((4,))}
    param_specs = {"w": P("dp", None), "b": P()}
    opt = optim.accumulate_gradients(optim.adamw(1e-3), 4)
    specs = opt_state_specs(opt.init(params), param_specs)
    assert specs["acc"] == param_specs
    assert specs["count"] == P()
    assert specs["inner"]["mu"] == param_specs  # sharded, not replicated
    assert specs["inner"]["nu"] == param_specs
    assert specs["inner"]["step"] == P()


def test_gspmd_tp_flash_shmap_matches_single(devices8):
    """attn_impl='flash_shmap': the flash kernel runs device-locally over
    tp-sharded heads via a NESTED shard_map inside the gspmd jit (the
    auto-partitioner never sees the Mosaic call) — step-for-step parity
    with single-device composed attention. On TPU, 'auto' selects this
    automatically when tp divides the heads."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from nezha_tpu import optim, parallel
    from nezha_tpu.models.gpt2 import GPT2, GPT2Config, lm_loss
    from nezha_tpu.parallel.gspmd import shard_batch_gspmd
    from nezha_tpu.train.loop import init_train_state, make_train_step

    kw = dict(vocab_size=128, max_positions=32, num_layers=2, num_heads=4,
              hidden_size=32, fused_loss_chunk=-1)
    toks = np.random.RandomState(0).randint(0, 128, (8, 17)).astype(np.int32)
    batch = {"tokens": jnp.asarray(toks)}

    m0 = GPT2(GPT2Config(attn_impl="xla", **kw))
    opt = optim.adamw(1e-2, weight_decay=0.0)
    s0 = init_train_state(m0, opt, jax.random.PRNGKey(0))
    step0 = make_train_step(m0, opt, lm_loss)
    l0 = []
    for _ in range(3):
        s0, met = step0(s0, batch)
        l0.append(float(met["loss"]))

    mesh = parallel.make_mesh({"dp": 2, "tp": 4})
    m1 = GPT2(GPT2Config(attn_impl="flash_shmap", **kw))
    s1 = init_train_state(m1, opt, jax.random.PRNGKey(0))
    specs = parallel.param_specs_from_rules(
        s1["variables"]["params"], parallel.GPT2_TP_RULES, strict=True)
    s1 = parallel.shard_train_state(s1, mesh, specs)
    step1 = parallel.make_gspmd_train_step(m1, opt, lm_loss, mesh, specs)
    b1 = shard_batch_gspmd(mesh, batch)
    l1 = []
    for _ in range(3):
        s1, met = step1(s1, b1)
        l1.append(float(met["loss"]))
    np.testing.assert_allclose(l1, l0, rtol=1e-3)


def test_gspmd_bert_tp_flash_shmap_varlen_matches_single(devices8):
    """BERT's bidirectional flash kernel under GSPMD TP via the nested
    shard_map — INCLUDING dp-sharded kv_lengths right-padding — matches
    single-device composed attention step-for-step."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from nezha_tpu import optim, parallel
    from nezha_tpu.models.bert import Bert, BertConfig, mlm_loss
    from nezha_tpu.parallel.gspmd import shard_batch_gspmd
    from nezha_tpu.train.loop import init_train_state, make_train_step

    kw = dict(vocab_size=128, max_positions=32, num_layers=2, num_heads=4,
              hidden_size=32, fused_loss_chunk=-1)
    rs = np.random.RandomState(0)
    batch = {"tokens": jnp.asarray(rs.randint(0, 128, (8, 16)), jnp.int32),
             "labels": jnp.asarray(
                 np.where(rs.rand(8, 16) < 0.3,
                          rs.randint(0, 128, (8, 16)), -100), jnp.int32),
             "kv_lengths": jnp.asarray([16, 12, 16, 9, 16, 16, 5, 16],
                                       jnp.int32)}

    m0 = Bert(BertConfig(attn_impl="xla", **kw))
    opt = optim.adamw(1e-2, weight_decay=0.0)
    s0 = init_train_state(m0, opt, jax.random.PRNGKey(0))
    step0 = make_train_step(m0, opt, mlm_loss)
    l0 = []
    for _ in range(3):
        s0, met = step0(s0, batch)
        l0.append(float(met["loss"]))

    mesh = parallel.make_mesh({"dp": 2, "tp": 4})
    m1 = Bert(BertConfig(attn_impl="flash_shmap", **kw))
    s1 = init_train_state(m1, opt, jax.random.PRNGKey(0))
    specs = parallel.param_specs_from_rules(
        s1["variables"]["params"], parallel.BERT_TP_RULES, strict=True)
    s1 = parallel.shard_train_state(s1, mesh, specs)
    step1 = parallel.make_gspmd_train_step(m1, opt, mlm_loss, mesh, specs)
    b1 = shard_batch_gspmd(mesh, batch)
    l1 = []
    for _ in range(3):
        s1, met = step1(s1, b1)
        l1.append(float(met["loss"]))
    np.testing.assert_allclose(l1, l0, rtol=1e-3)


def test_gspmd_pallas_ln_nested_shmap_matches_xla(devices8, monkeypatch):
    """Under the auto-partitioner with a mesh, the fused Pallas LN runs
    device-locally via a nested shard_map (the backend probe is patched
    so the kernel runs through the interpreter off-TPU) — numerics match
    the composed LN."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    from nezha_tpu import nn, parallel
    from nezha_tpu.nn import layers
    from nezha_tpu.parallel.gspmd import auto_partitioner_scope

    monkeypatch.setattr(layers, "_ln_kernel_backend", lambda: True)
    mesh = parallel.make_mesh({"dp": 2, "tp": 4})
    ln_p = nn.LayerNorm(32, impl="pallas")
    ln_x = nn.LayerNorm(32, impl="xla")
    v = ln_x.init(jax.random.PRNGKey(0))
    v["params"]["scale"] = jnp.asarray(
        np.random.RandomState(1).rand(32).astype(np.float32))
    x = jnp.asarray(np.random.RandomState(0).randn(8, 16, 32)
                    .astype(np.float32))

    with auto_partitioner_scope(mesh):
        y_p, _ = ln_p.apply(v, x)
    y_x, _ = ln_x.apply(v, x)
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_x),
                               rtol=2e-5, atol=2e-6)
