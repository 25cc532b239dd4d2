"""Core layers.

TPU-first conventions baked in:

- Convs are NHWC (feature-minor) — the layout XLA:TPU tiles best onto the
  MXU; weights are HWIO.
- Every layer takes a dtype ``Policy`` (fp32 master params, bf16 compute by
  default for the big models) so the MXU runs at full bf16 throughput while
  normalization statistics stay fp32.
- All shapes static; no data-dependent control flow, so everything fuses
  under one jit.

Reference parity: the op set nezha's graph needs for its five benchmark
workloads (SURVEY.md §2: matmul, conv, norms, embedding, dropout, pooling).
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

from nezha_tpu.nn import initializers as init_lib
from nezha_tpu.nn.module import Module, Variables, make_variables
from nezha_tpu.tensor.policy import DEFAULT_POLICY, Policy


class Linear(Module):
    """y = x @ W + b, weights stored (in, out)."""

    def __init__(self, in_features: int, out_features: int, use_bias: bool = True,
                 kernel_init=None, bias_init=init_lib.zeros,
                 policy: Policy = DEFAULT_POLICY, name: str = "linear"):
        self.in_features = in_features
        self.out_features = out_features
        self.use_bias = use_bias
        self.kernel_init = kernel_init or init_lib.lecun_normal()
        self.bias_init = bias_init
        self.policy = policy
        self.name = name

    def init(self, rng: jax.Array) -> Variables:
        kw, kb = jax.random.split(rng)
        p = {"w": self.kernel_init(kw, (self.in_features, self.out_features),
                                   self.policy.param_dtype)}
        if self.use_bias:
            p["b"] = self.bias_init(kb, (self.out_features,), self.policy.param_dtype)
        return make_variables(p)

    def apply(self, variables: Variables, x, training: bool = False, rng=None):
        del training, rng
        p = variables["params"]
        w = self.policy.cast_to_compute(p["w"])
        x = self.policy.cast_to_compute(x)
        y = x @ w
        if self.use_bias:
            y = y + self.policy.cast_to_compute(p["b"])
        return self.policy.cast_output(y), {}


class Conv2d(Module):
    """NHWC conv, HWIO weights, optional groups — lowers to XLA conv on MXU."""

    def __init__(self, in_channels: int, out_channels: int,
                 kernel_size: Union[int, Tuple[int, int]],
                 stride: Union[int, Tuple[int, int]] = 1,
                 padding: Union[str, int, Tuple[int, int]] = "SAME",
                 groups: int = 1, use_bias: bool = True,
                 kernel_init=None, policy: Policy = DEFAULT_POLICY):
        self.in_channels = in_channels
        self.out_channels = out_channels
        self.kernel_size = (kernel_size, kernel_size) if isinstance(kernel_size, int) else tuple(kernel_size)
        self.stride = (stride, stride) if isinstance(stride, int) else tuple(stride)
        if isinstance(padding, int):
            padding = ((padding, padding), (padding, padding))
        elif isinstance(padding, tuple):
            padding = tuple((p, p) if isinstance(p, int) else p for p in padding)
        self.padding = padding
        self.groups = groups
        self.use_bias = use_bias
        self.kernel_init = kernel_init or init_lib.he_normal()
        self.policy = policy

    def init(self, rng: jax.Array) -> Variables:
        kw, kb = jax.random.split(rng)
        kh, kwd = self.kernel_size
        p = {"w": self.kernel_init(
            kw, (kh, kwd, self.in_channels // self.groups, self.out_channels),
            self.policy.param_dtype)}
        if self.use_bias:
            p["b"] = init_lib.zeros(kb, (self.out_channels,), self.policy.param_dtype)
        return make_variables(p)

    def apply(self, variables: Variables, x, training: bool = False, rng=None):
        del training, rng
        p = variables["params"]
        w = self.policy.cast_to_compute(p["w"])
        x = self.policy.cast_to_compute(x)
        y = lax.conv_general_dilated(
            x, w,
            window_strides=self.stride,
            padding=self.padding,
            feature_group_count=self.groups,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        if self.use_bias:
            y = y + self.policy.cast_to_compute(p["b"])
        return self.policy.cast_output(y), {}


class BatchNorm(Module):
    """Batch norm over N,H,W (axis −1 features) with running stats in fp32.

    Running stats are framework ``state`` — updated functionally: apply in
    training mode returns the new stats, the train step threads them.
    Batch statistics themselves are per-replica under data parallelism (no
    cross-replica batch-stat sync inside the layer); the DP/ZeRO-1 train
    steps pmean the *running* stats each step (they're tiny), and
    ``nezha_tpu.parallel.sync_batch_stats`` averages pmap-style stacked
    per-replica stats for custom steps that let them drift until eval.
    """

    def __init__(self, num_features: int, momentum: float = 0.9, eps: float = 1e-5,
                 policy: Policy = DEFAULT_POLICY):
        self.num_features = num_features
        self.momentum = momentum
        self.eps = eps
        self.policy = policy

    def init(self, rng: jax.Array) -> Variables:
        del rng
        f = self.num_features
        params = {"scale": jnp.ones((f,), self.policy.param_dtype),
                  "bias": jnp.zeros((f,), self.policy.param_dtype)}
        state = {"mean": jnp.zeros((f,), jnp.float32),
                 "var": jnp.ones((f,), jnp.float32)}
        return make_variables(params, state)

    def apply(self, variables: Variables, x, training: bool = False, rng=None):
        del rng
        p, s = variables["params"], variables["state"]
        reduce_axes = tuple(range(x.ndim - 1))
        xf = jnp.asarray(x, jnp.float32)  # stats in fp32 always
        if training:
            mean = jnp.mean(xf, axis=reduce_axes)
            var = jnp.var(xf, axis=reduce_axes)
            m = self.momentum
            new_state = {"mean": m * s["mean"] + (1 - m) * mean,
                         "var": m * s["var"] + (1 - m) * var}
        else:
            mean, var = s["mean"], s["var"]
            new_state = {}
        inv = lax.rsqrt(var + self.eps)
        scale = jnp.asarray(p["scale"], jnp.float32) * inv
        shift = jnp.asarray(p["bias"], jnp.float32) - mean * scale
        # Normalize in the input's compute dtype: stats stay fp32 (above),
        # but applying them to the fp32-upcast activation would make the
        # residual saved for backward an fp32 copy of every conv output —
        # 2x the HBM traffic of the bf16 policy it runs under. scale/shift
        # are per-channel, so the bf16 multiply loses no batch statistics.
        y = x * jnp.asarray(scale, x.dtype) + jnp.asarray(shift, x.dtype)
        return self.policy.cast_output(y), new_state


def _ln_kernel_backend() -> bool:
    """Whether ``impl="pallas"`` layer norms run the fused kernel: TPU
    backends only (tests patch this to drive the kernel through the
    Pallas interpreter on CPU)."""
    return jax.default_backend() == "tpu"


class LayerNorm(Module):
    """Layer norm over the last axis; statistics in fp32.

    ``impl="pallas"`` opts into the fused Pallas kernel (fwd + custom-VJP
    bwd, `ops.pallas.fused_layer_norm`) on TPU backends; requires both
    scale and bias. Under the GSPMD auto-partitioner (which cannot
    partition Mosaic calls) the kernel still runs device-locally via a
    nested shard_map when the trace carries its mesh (rows independent,
    batch over dp); composed XLA otherwise and on non-TPU backends."""

    def __init__(self, dim: int, eps: float = 1e-5, use_bias: bool = True,
                 use_scale: bool = True, policy: Policy = DEFAULT_POLICY,
                 impl: str = "xla"):
        self.dim = dim
        self.eps = eps
        self.use_bias = use_bias
        self.use_scale = use_scale
        self.policy = policy
        if impl not in ("xla", "pallas"):
            raise ValueError(f"unknown LayerNorm impl {impl!r}")
        if impl == "pallas" and not (use_bias and use_scale):
            raise ValueError("impl='pallas' needs use_scale and use_bias")
        self.impl = impl

    def init(self, rng: jax.Array) -> Variables:
        del rng
        p = {}
        if self.use_scale:
            p["scale"] = jnp.ones((self.dim,), self.policy.param_dtype)
        if self.use_bias:
            p["bias"] = jnp.zeros((self.dim,), self.policy.param_dtype)
        return make_variables(p)

    def apply(self, variables: Variables, x, training: bool = False, rng=None):
        del training, rng
        p = variables["params"]
        if self.impl == "pallas" and _ln_kernel_backend():
            from nezha_tpu.parallel.gspmd import (auto_partitioner_mesh,
                                                  under_auto_partitioner)
            if not under_auto_partitioner():
                from nezha_tpu.ops.pallas import fused_layer_norm
                y = fused_layer_norm(
                    self.policy.cast_to_compute(x),
                    jnp.asarray(p["scale"], jnp.float32),
                    jnp.asarray(p["bias"], jnp.float32), eps=self.eps)
                return self.policy.cast_output(y), {}
            mesh = auto_partitioner_mesh()
            if mesh is not None and "dp" in mesh.axis_names and x.ndim >= 2:
                # Under the GSPMD auto-partitioner (which cannot partition
                # a Mosaic call) the kernel still runs device-locally via
                # a nested shard_map: rows are independent, activations
                # between blocks are tp-replicated, batch shards over dp
                # (same pattern as models.gpt2._tp_sharded_flash).
                from jax.sharding import PartitionSpec as P

                from nezha_tpu.ops.pallas import fused_layer_norm
                from nezha_tpu.parallel._compat import shard_map
                spec = P(*(("dp",) + (None,) * (x.ndim - 1)))
                f = shard_map(
                    lambda x_, s_, b_: fused_layer_norm(x_, s_, b_,
                                                        eps=self.eps),
                    mesh=mesh, in_specs=(spec, P(), P()), out_specs=spec)
                y = f(self.policy.cast_to_compute(x),
                      jnp.asarray(p["scale"], jnp.float32),
                      jnp.asarray(p["bias"], jnp.float32))
                return self.policy.cast_output(y), {}
        xf = jnp.asarray(x, jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mean) * lax.rsqrt(var + self.eps)
        if self.use_scale:
            y = y * jnp.asarray(p["scale"], jnp.float32)
        if self.use_bias:
            y = y + jnp.asarray(p["bias"], jnp.float32)
        return self.policy.cast_output(y), {}


class RMSNorm(Module):
    """Root-mean-square norm over the last axis (no mean subtraction, no
    bias): ``x * rsqrt(mean(x^2) + eps) * scale``; statistics in fp32."""

    def __init__(self, dim: int, eps: float = 1e-6,
                 policy: Policy = DEFAULT_POLICY):
        self.dim = dim
        self.eps = eps
        self.policy = policy

    def init(self, rng: jax.Array) -> Variables:
        del rng
        return make_variables(
            {"scale": jnp.ones((self.dim,), self.policy.param_dtype)})

    def apply(self, variables: Variables, x, training: bool = False, rng=None):
        del training, rng
        xf = jnp.asarray(x, jnp.float32)
        y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                           + self.eps)
        y = y * jnp.asarray(variables["params"]["scale"], jnp.float32)
        return self.policy.cast_output(y), {}


class Embedding(Module):
    """Token embedding table; lookup stays a gather (fast path on TPU)."""

    def __init__(self, num_embeddings: int, features: int,
                 embedding_init=None, policy: Policy = DEFAULT_POLICY):
        self.num_embeddings = num_embeddings
        self.features = features
        self.embedding_init = embedding_init or init_lib.normal(0.02)
        self.policy = policy

    def init(self, rng: jax.Array) -> Variables:
        return make_variables({
            "embedding": self.embedding_init(
                rng, (self.num_embeddings, self.features), self.policy.param_dtype)
        })

    def apply(self, variables: Variables, ids, training: bool = False, rng=None):
        del training, rng
        table = self.policy.cast_to_compute(variables["params"]["embedding"])
        return jnp.take(table, ids, axis=0), {}

    def attend(self, variables: Variables, x):
        """Tied-softmax logits: x @ E^T (GPT-2/BERT output head)."""
        table = self.policy.cast_to_compute(variables["params"]["embedding"])
        return self.policy.cast_to_compute(x) @ table.T


class Dropout(Module):
    def __init__(self, rate: float):
        self.rate = rate

    def init(self, rng: jax.Array) -> Variables:
        del rng
        return make_variables()

    def apply(self, variables: Variables, x, training: bool = False, rng=None):
        del variables
        if not training or self.rate == 0.0:
            return x, {}
        if rng is None:
            raise ValueError("Dropout in training mode needs an rng")
        keep = 1.0 - self.rate
        mask = jax.random.bernoulli(rng, keep, x.shape)
        return jnp.where(mask, x / keep, jnp.zeros_like(x)), {}


def max_pool(x, window: int, stride: int, padding: str = "SAME"):
    """NHWC max pool via reduce_window."""
    return lax.reduce_window(
        x, -jnp.inf, lax.max,
        (1, window, window, 1), (1, stride, stride, 1), padding)


def avg_pool(x, window: int, stride: int, padding: str = "VALID"):
    dims = (1, window, window, 1)
    strides = (1, stride, stride, 1)
    summed = lax.reduce_window(x, 0.0, lax.add, dims, strides, padding)
    if padding == "VALID":
        return summed / (window * window)
    # SAME: edge windows overlap padding — divide by the true element count.
    counts = lax.reduce_window(jnp.ones_like(x), 0.0, lax.add, dims, strides,
                               padding)
    return summed / counts


def global_avg_pool(x):
    """NHWC -> NC."""
    return jnp.mean(x, axis=(1, 2))
