"""Flash-decode kernel: interpret-mode numerics at the edge rows the
serving engine actually produces (length 1, length == L_max, inactive
rows, mixed skews), greedy-decode parity between the kernel and the
composed masked path through the full model, and the microbenchmark's
tier-1 smoke. The kernel is the serving hot path — parity here is what
licenses `attn_impl="auto"` to route production decode through it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nezha_tpu import ops
from nezha_tpu.ops.pallas import flash_decode_attention


def _qkv(b, L, h=4, d=16, seed=0, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(kq, (b, h, 1, d), dtype),
            jax.random.normal(kk, (b, h, L, d), dtype),
            jax.random.normal(kv, (b, h, L, d), dtype))


def _composed(q, k, v, lengths):
    """The engine's pre-kernel decode path: dense attention under a
    [B, 1, 1, L] additive -inf mask."""
    L = k.shape[2]
    mask = jnp.where(jnp.arange(L)[None, :] < lengths[:, None],
                     0.0, -jnp.inf).astype(jnp.float32)
    return ops.dot_product_attention(q, k.astype(q.dtype),
                                     v.astype(q.dtype),
                                     mask=mask[:, None, None, :])


@pytest.mark.parametrize("lengths", [
    [1, 1, 1, 1],            # every row at minimum depth
    [48, 48, 48, 48],        # every row at full capacity
    [1, 48, 7, 23],          # mixed skew
    [5, 48, 1, 17],
])
def test_decode_kernel_matches_composed(lengths):
    q, k, v = _qkv(b=4, L=48)
    lengths = jnp.asarray(lengths, jnp.int32)
    out = flash_decode_attention(q, k, v, lengths, block_k=16)
    ref = _composed(q, k, v, lengths)
    assert np.abs(np.asarray(out) - np.asarray(ref)).max() <= 1e-5


def test_decode_kernel_inactive_rows_zero():
    """length == 0 marks an inactive slot: every KV block is skipped and
    the output row is exactly zero (the composed path would compute a
    uniform softmax over garbage there)."""
    q, k, v = _qkv(b=3, L=32)
    out = flash_decode_attention(
        q, k, v, jnp.asarray([0, 32, 0], jnp.int32), block_k=16)
    out = np.asarray(out)
    assert (out[0] == 0).all() and (out[2] == 0).all()
    ref = _composed(q, k, v, jnp.asarray([32, 32, 32], jnp.int32))
    assert np.abs(out[1] - np.asarray(ref[1])).max() <= 1e-5


def test_decode_kernel_bf16_cache_fp32_accum():
    """bf16 q/K/V with fp32 accumulation: close to the fp32 composed
    reference at bf16-level tolerance, and the output keeps q's dtype."""
    q, k, v = _qkv(b=2, L=64, dtype=jnp.bfloat16)
    lengths = jnp.asarray([9, 64], jnp.int32)
    out = flash_decode_attention(q, k, v, lengths, block_k=32)
    assert out.dtype == jnp.bfloat16
    ref = _composed(*(t.astype(jnp.float32) for t in (q, k, v)), lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), rtol=2e-2, atol=2e-2)


def test_decode_kernel_under_jit_traced_lengths():
    q, k, v = _qkv(b=2, L=32)
    f = jax.jit(lambda q_, k_, v_, l_: flash_decode_attention(
        q_, k_, v_, l_, block_k=16))
    lengths = jnp.asarray([3, 30], jnp.int32)
    np.testing.assert_allclose(
        np.asarray(f(q, k, v, lengths)),
        np.asarray(_composed(q, k, v, lengths)), rtol=1e-5, atol=1e-5)


def test_decode_kernel_rejects_multi_token_query():
    q, k, v = _qkv(b=1, L=16)
    q2 = jnp.concatenate([q, q], axis=2)                     # s_q == 2
    with pytest.raises(ValueError, match="single-token"):
        flash_decode_attention(q2, k, v, jnp.asarray([4], jnp.int32))


# --------------------------------------------------- model-level parity
def test_generate_greedy_parity_kernel_vs_composed():
    """The satellite contract: one-shot generate() routed through the
    flash-decode kernel (decode_impl='kernel', interpret mode on CPU) is
    BIT-IDENTICAL to the composed masked path for greedy decoding."""
    from nezha_tpu.models.generate import generate
    from nezha_tpu.models.gpt2 import GPT2, GPT2Config

    kw = dict(vocab_size=97, max_positions=64, num_layers=2, num_heads=4,
              hidden_size=64)
    composed = GPT2(GPT2Config(**kw, decode_impl="xla"))
    kernel = GPT2(GPT2Config(**kw, decode_impl="kernel"))
    variables = composed.init(jax.random.PRNGKey(0))
    prompt = np.asarray([[5, 17, 3, 42], [9, 1, 1, 7]], np.int32)
    a = np.asarray(generate(composed, variables, prompt, max_new_tokens=8,
                            cache_dtype=jnp.float32))
    b = np.asarray(generate(kernel, variables, prompt, max_new_tokens=8,
                            cache_dtype=jnp.float32))
    assert (a == b).all()


def test_decode_impl_resolution():
    """``decode_impl`` alone decides: "kernel" forces the kernel,
    "xla" is the one way to the composed path."""
    from nezha_tpu.models.gpt2 import GPT2Config, _decode_flash_ok

    assert _decode_flash_ok(GPT2Config(decode_impl="kernel"))
    assert not _decode_flash_ok(GPT2Config(decode_impl="xla"))
    # auto follows the shared attn_impl resolution: composed on CPU.
    assert not _decode_flash_ok(GPT2Config(decode_impl="auto"))


@pytest.mark.parametrize("impl, backend, partitioned, want", [
    ("kernel", "cpu", False, True),     # forced: interpreted off a TPU
    ("xla", "tpu", False, False),       # forced: composed on a TPU
    ("auto", "cpu", False, False),
    ("auto", "tpu", False, True),
    ("auto", "tpu", True, False),       # never under the auto-partitioner
    ("flash", "tpu", False, ValueError),
])
def test_use_kernels_is_the_one_kernel_policy(monkeypatch, impl, backend,
                                              partitioned, want):
    """``use_kernels`` is every model's ``*_impl`` rule: ``auto`` means
    a TPU backend outside the GSPMD auto-partitioner, as GPT-2's
    ``attn_impl="auto"`` always has; a name it does not know raises."""
    import contextlib

    from nezha_tpu.ops.pallas.common import use_kernels
    from nezha_tpu.parallel.gspmd import auto_partitioner_scope

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    with (auto_partitioner_scope() if partitioned
          else contextlib.nullcontext()):
        if want is ValueError:
            with pytest.raises(ValueError, match="flash"):
                use_kernels(impl)
        else:
            assert use_kernels(impl) is want


# -------------------------------------------------------- benchmark CLI
def test_decode_attention_benchmark_cli(tmp_path):
    """benchmarks/decode_attention.py runs at tier-1 shapes (interpret
    mode) and writes schema-valid run-dir artifacts."""
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "benchmarks"))
    import decode_attention as bench

    run_dir = str(tmp_path / "bench")
    rec = bench.run(bench.build_parser().parse_args(
        ["--batch-sizes", "2", "--max-lens", "32", "--num-heads", "2",
         "--head-dim", "8", "--skews", "full,mixed,one_active",
         "--dtype", "f32", "--iters", "2", "--warmup", "1",
         "--run-dir", run_dir]))
    assert rec["interpreted"] is True
    assert len(rec["configs"]) == 3
    assert all(c["kernel_ms"] > 0 and c["composed_ms"] > 0
               for c in rec["configs"])
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    from check_telemetry_schema import check_run_dir
    assert check_run_dir(run_dir) == []
