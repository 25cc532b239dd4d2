"""Pallas kernel tests (interpret mode on CPU — same kernel code that
compiles on TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nezha_tpu import ops
from nezha_tpu.ops.pallas import flash_attention, fused_layer_norm


def _qkv(b=2, h=3, s=64, d=32, seed=0):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(kq, (b, h, s, d)),
            jax.random.normal(kk, (b, h, s, d)),
            jax.random.normal(kv, (b, h, s, d)))


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_reference(causal):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal, None, 16, 16)
    mask = ops.causal_mask(64, 64) if causal else None
    ref = ops.dot_product_attention(q, k, v, mask=mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_flash_attention_odd_blocks():
    # S not divisible by the requested block -> divisor fallback.
    q, k, v = _qkv(s=48)
    out = flash_attention(q, k, v, True, None, 32, 32)
    ref = ops.dot_product_attention(q, k, v, mask=ops.causal_mask(48, 48))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_flash_attention_grad_matches_reference():
    q, k, v = _qkv(b=1, h=2, s=32, d=16)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, None, 16, 16) ** 2)

    def loss_ref(q, k, v):
        out = ops.dot_product_attention(q, k, v, mask=ops.causal_mask(32, 32))
        return jnp.sum(out ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5)


def test_flash_attention_bf16():
    q, k, v = (t.astype(jnp.bfloat16) for t in _qkv())
    out = flash_attention(q, k, v, True, None, 16, 16)
    assert out.dtype == jnp.bfloat16
    ref = ops.dot_product_attention(q, k, v, mask=ops.causal_mask(64, 64))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=5e-2,
                               atol=5e-2)


def test_fused_layer_norm_matches_layernorm():
    from nezha_tpu import nn
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 8, 96)) * 3 + 1
    scale = jax.random.normal(jax.random.PRNGKey(1), (96,)) + 1
    bias = jax.random.normal(jax.random.PRNGKey(2), (96,))
    out = fused_layer_norm(x, scale, bias)
    ln = nn.LayerNorm(96)
    ref, _ = ln.apply({"params": {"scale": scale, "bias": bias}, "state": {}}, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_bwd_multiblock_noncausal():
    """Fused backward across multiple q AND k blocks, non-causal."""
    rng = np.random.RandomState(3)
    q, k, v = [jnp.asarray(rng.randn(2, 3, 64, 32), jnp.float32)
               for _ in range(3)]

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, False, None, 16, 16) ** 2)

    def loss_ref(q, k, v):
        from nezha_tpu import ops
        return jnp.sum(ops.dot_product_attention(q, k, v) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_flash_bwd_causal_multiblock():
    rng = np.random.RandomState(4)
    q, k, v = [jnp.asarray(rng.randn(1, 2, 96, 16), jnp.float32)
               for _ in range(3)]

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, None, 32, 32) ** 2)

    def loss_ref(q, k, v):
        from nezha_tpu import ops
        mask = ops.causal_mask(96, 96)
        return jnp.sum(ops.dot_product_attention(q, k, v, mask=mask) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_flash_bwd_bf16_grads_match_reference():
    rng = np.random.RandomState(5)
    q, k, v = [jnp.asarray(rng.randn(1, 2, 64, 32), jnp.bfloat16)
               for _ in range(3)]

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, True, None, 32, 32)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    def loss_ref(q, k, v):
        from nezha_tpu import ops
        mask = ops.causal_mask(64, 64)
        out = ops.dot_product_attention(q, k, v, mask=mask)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=0.15, rtol=0.1)  # bf16 grain


def test_fused_layer_norm_grads_match_xla():
    """The fused backward kernel's dx/dscale/dbias vs autodiff through the
    composed XLA layer norm."""
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(6, 128, 64), jnp.float32)
    scale = jnp.asarray(rng.rand(64) + 0.5, jnp.float32)
    bias = jnp.asarray(rng.randn(64), jnp.float32)
    w = jnp.asarray(rng.randn(*x.shape), jnp.float32)

    def ref_ln(x, scale, bias):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-5) * scale + bias

    def loss(fn):
        return lambda x, s, b: jnp.sum(fn(x, s, b) * w)

    g_fused = jax.grad(loss(lambda x, s, b: fused_layer_norm(x, s, b)),
                       argnums=(0, 1, 2))(x, scale, bias)
    g_ref = jax.grad(loss(ref_ln), argnums=(0, 1, 2))(x, scale, bias)
    for a, b, name in zip(g_fused, g_ref, ("dx", "dscale", "dbias")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5, err_msg=name)


def test_fused_layer_norm_bf16_grads_finite():
    x = jnp.asarray(np.random.RandomState(1).randn(4, 64, 32), jnp.bfloat16)
    scale = jnp.ones((32,), jnp.float32)
    bias = jnp.zeros((32,), jnp.float32)
    g = jax.grad(lambda x: jnp.sum(
        fused_layer_norm(x, scale, bias).astype(jnp.float32) ** 2))(x)
    assert np.isfinite(np.asarray(g, np.float32)).all()


def test_fused_layer_norm_mixed_param_dtypes_grad():
    """scale f32 + bias bf16: cotangent dtypes must match each primal."""
    x = jnp.asarray(np.random.RandomState(2).randn(4, 32, 16), jnp.float32)
    scale = jnp.ones((16,), jnp.float32)
    bias = jnp.zeros((16,), jnp.bfloat16)
    g = jax.grad(lambda s, b: jnp.sum(fused_layer_norm(x, s, b)),
                 argnums=(0, 1))(scale, bias)
    assert g[0].dtype == jnp.float32 and g[1].dtype == jnp.bfloat16


def _varlen_setup(s=32, lengths=(20, 32)):
    q, k, v = _qkv(b=len(lengths), h=2, s=s, d=16)
    lens = jnp.asarray(lengths, jnp.int32)
    # Additive mask equivalent to the kernel's right-padding contract:
    # key positions >= length get -inf for every query row.
    kpos = jnp.arange(s)[None, None, None, :]
    mask = jnp.where(kpos < lens[:, None, None, None], 0.0, -jnp.inf)
    # Valid-row selector [B, 1, S, 1] for comparisons/losses: padded QUERY
    # rows are unspecified in the kernel contract.
    valid_q = (jnp.arange(s)[None, :] < lens[:, None])[:, None, :, None]
    return q, k, v, lens, mask, valid_q


def test_flash_varlen_matches_masked_xla():
    """kv_lengths == additive prefix mask on the valid query rows (fwd),
    multi-block so the length boundary crosses block edges."""
    q, k, v, lens, mask, valid_q = _varlen_setup(s=32, lengths=(20, 32))
    out_f = flash_attention(q, k, v, causal=False, block_q=8, block_k=8,
                            kv_lengths=lens)
    out_r = ops.dot_product_attention(q, k, v, mask=mask)
    np.testing.assert_allclose(np.asarray(jnp.where(valid_q, out_f, 0.0)),
                               np.asarray(jnp.where(valid_q, out_r, 0.0)),
                               rtol=5e-4, atol=5e-5)


def test_flash_varlen_grads_match_masked_xla():
    """Gradients through the varlen custom VJP match the masked composed
    path on valid rows; padded keys/values get exactly zero gradient."""
    q, k, v, lens, mask, valid_q = _varlen_setup(s=32, lengths=(20, 32))

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, causal=False, block_q=8, block_k=8,
                              kv_lengths=lens)
        return jnp.sum(jnp.where(valid_q, out, 0.0) ** 2)

    def loss_ref(q, k, v):
        out = ops.dot_product_attention(q, k, v, mask=mask)
        return jnp.sum(jnp.where(valid_q, out, 0.0) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5)
    # Padded key/value positions (row 0: length 20 of 32) carry no grad.
    dk, dv = np.asarray(g1[1]), np.asarray(g1[2])
    assert np.all(dk[0, :, 20:, :] == 0.0)
    assert np.all(dv[0, :, 20:, :] == 0.0)
    assert np.any(dk[0, :, :20, :] != 0.0)


def test_flash_varlen_jits_and_batches_lengths():
    """kv_lengths is a traced operand: one compiled program serves
    different length values (no per-batch recompilation)."""
    q, k, v, _, _, _ = _varlen_setup(s=32, lengths=(20, 32))
    f = jax.jit(lambda q, k, v, l: flash_attention(
        q, k, v, causal=False, kv_lengths=l))
    o1 = f(q, k, v, jnp.asarray([20, 32], jnp.int32))
    o2 = f(q, k, v, jnp.asarray([32, 8], jnp.int32))
    assert o1.shape == o2.shape == q.shape
    assert not np.allclose(np.asarray(o1), np.asarray(o2))


def test_flash_varlen_zero_length_clamps_to_one():
    """Length 0 is clamped to 1 (fully-padded row attends to position 0
    only) — finite output, identical to an explicit length-1 call, and no
    silent uniform-attention over padding (ADVICE r4)."""
    q, k, v, _, _, _ = _varlen_setup(s=32, lengths=(20, 32))
    out0 = flash_attention(q, k, v, causal=False, block_q=8, block_k=8,
                           kv_lengths=jnp.asarray([0, 32], jnp.int32))
    out1 = flash_attention(q, k, v, causal=False, block_q=8, block_k=8,
                           kv_lengths=jnp.asarray([1, 32], jnp.int32))
    assert np.all(np.isfinite(np.asarray(out0)))
    np.testing.assert_allclose(np.asarray(out0), np.asarray(out1),
                               rtol=1e-6, atol=1e-7)
    # NOT uniform attention over all positions (the pre-clamp failure
    # mode): row 0 must equal attention restricted to key position 0.
    only_pos0 = jnp.broadcast_to(v[0, :, :1, :], q[0].shape)
    np.testing.assert_allclose(np.asarray(out0[0]), np.asarray(only_pos0),
                               rtol=5e-4, atol=5e-5)


def _causal_reference(q, k, v, lens=None):
    s = q.shape[2]
    mask = ops.causal_mask(s, s)
    if lens is not None:
        kpos = jnp.arange(s)[None, None, None, :]
        mask = jnp.where(kpos < lens[:, None, None, None], mask, -jnp.inf)
    return ops.dot_product_attention(q, k, v, mask=mask)


@pytest.mark.parametrize("lengths", [None, (77, 200)],
                         ids=["dense", "kv_lengths"])
@pytest.mark.parametrize("s,block_q,block_k", [
    (128, None, None), (256, None, 128), (512, None, 128), (512, 256, 128),
    (1024, None, None)])
def test_flash_causal_sub_tiles_match_reference(s, block_q, block_k,
                                                lengths):
    """A causal q block several key blocks tall walks its row strips from
    the diagonal square down and skips those above: two strips at
    S = 1024's default blocks, four of 128 at S = 512, two at S = 256, and
    two q blocks of two strips each at S = 512 with 256-row q blocks;
    S = 128 is one strip, which keeps the whole-block body. Forward and all
    three gradients match composed causal attention, with lengths that end
    inside a strip."""
    from nezha_tpu.ops.pallas.flash_attention import _auto_blocks, _sub_tile
    auto_q, auto_k = (min(s, b) for b in _auto_blocks(s, s))
    assert (_sub_tile(block_q or auto_q, block_k or auto_k) is None) == (
        s == 128)
    q, k, v = _qkv(b=2, h=1, s=s, d=64, seed=s)
    lens = None if lengths is None else jnp.asarray(lengths, jnp.int32)
    w = jax.random.normal(jax.random.PRNGKey(1), q.shape)

    def flash(q, k, v):
        return flash_attention(q, k, v, True, block_q=block_q,
                               block_k=block_k, kv_lengths=lens)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * w)

    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(_causal_reference(q, k, v, lens)),
                               rtol=2e-4, atol=2e-5)
    g1 = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss(lambda q, k, v: _causal_reference(q, k, v, lens)),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g1, g2, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5, err_msg=name)


def test_flash_block_fwd_bwd_match_reference():
    """The ring's raw building blocks at a whole-row causal shape with two
    row strips: the forward's output and row logsumexp, and the backward's
    three gradients given the global lse and output."""
    from nezha_tpu.ops.pallas.flash_attention import (flash_block_bwd,
                                                      flash_block_fwd)
    q, k, v = _qkv(b=1, h=2, s=1024, d=64, seed=7)
    do = jax.random.normal(jax.random.PRNGKey(8), q.shape)
    out, lse = flash_block_fwd(q, k, v, True)
    ref, vjp = jax.vjp(_causal_reference, q, k, v)
    scores = (jnp.einsum("bhqd,bhkd->bhqk", q, k) / 8.0
              + ops.causal_mask(1024, 1024))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(jax.nn.logsumexp(scores, axis=-1)),
        rtol=2e-5, atol=2e-5)
    got = flash_block_bwd(q, k, v, out, lse, do, True)
    for a, b, name in zip(got, vjp(do), ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5, err_msg=name)


@pytest.mark.parametrize("s,share", [(128, 1.0), (256, 1.0), (512, 1.0),
                                     (1024, 0.75), (2048, 0.625)])
def test_flash_causal_tile_share_gauge(s, share):
    """Tracing a causal call sets ``kernel.flash.causal_tile_share`` to the
    share of the S x S scores it computes: three of four 512-row strips at
    S = 1024, 1.0 where the block is one strip, the skipped grid blocks
    beyond 1024."""
    from nezha_tpu import obs
    x = jax.ShapeDtypeStruct((1, 1, s, 64), jnp.float32)
    jax.clear_caches()      # the call is jitted: trace it anew
    obs.enable()
    try:
        obs.gauge("kernel.flash.causal_tile_share").set(0.0)
        jax.eval_shape(lambda q, k, v: flash_attention(q, k, v, True),
                       x, x, x)
        assert obs.gauge("kernel.flash.causal_tile_share").value == share
    finally:
        obs.disable()
