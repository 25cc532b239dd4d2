"""ResNet family: ResNet-50 and Wide-ResNet-101-2.

Benchmark configs 2 and 5 (SURVEY.md §0: "ResNet-50 / ImageNet
data-parallel, all-reduce" and "Wide-ResNet-101, large-batch mixed
bf16/fp32"). TPU-first choices: NHWC layout throughout (XLA:TPU's native
conv layout), BatchNorm stats in fp32 under the bf16 policy, zero-init of
each block's last BN scale (standard large-batch trick), and a single
residual topology XLA fuses aggressively.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from nezha_tpu import nn
from nezha_tpu.nn import initializers as init_lib
from nezha_tpu.nn.module import Module, Variables, child_vars, run_child
from nezha_tpu.tensor.policy import DEFAULT_POLICY, Policy


class Bottleneck(Module):
    """1x1 -> 3x3 (stride) -> 1x1 with projection shortcut when needed."""

    def __init__(self, in_ch: int, width: int, out_ch: int, stride: int,
                 policy: Policy = DEFAULT_POLICY):
        self.conv1 = nn.Conv2d(in_ch, width, 1, use_bias=False, policy=policy)
        self.bn1 = nn.BatchNorm(width, policy=policy)
        self.conv2 = nn.Conv2d(width, width, 3, stride=stride, use_bias=False,
                               policy=policy)
        self.bn2 = nn.BatchNorm(width, policy=policy)
        self.conv3 = nn.Conv2d(width, out_ch, 1, use_bias=False, policy=policy)
        self.bn3 = nn.BatchNorm(out_ch, policy=policy)
        self.needs_proj = (in_ch != out_ch) or (stride != 1)
        if self.needs_proj:
            self.proj = nn.Conv2d(in_ch, out_ch, 1, stride=stride,
                                  use_bias=False, policy=policy)
            self.proj_bn = nn.BatchNorm(out_ch, policy=policy)

    def init(self, rng: jax.Array) -> Variables:
        v = super().init(rng)
        # Zero-init the last BN scale so each block starts as identity —
        # improves large-batch trainability (used by the WRN-101 config).
        v["params"]["bn3"]["scale"] = jnp.zeros_like(v["params"]["bn3"]["scale"])
        return v

    def apply(self, variables: Variables, x, training: bool = False, rng=None):
        states: dict = {}
        y = run_child(self.conv1, "conv1", variables, states, x, training=training)
        y = run_child(self.bn1, "bn1", variables, states, y, training=training)
        y = jnp.maximum(y, 0)
        y = run_child(self.conv2, "conv2", variables, states, y, training=training)
        y = run_child(self.bn2, "bn2", variables, states, y, training=training)
        y = jnp.maximum(y, 0)
        y = run_child(self.conv3, "conv3", variables, states, y, training=training)
        y = run_child(self.bn3, "bn3", variables, states, y, training=training)
        if self.needs_proj:
            sc = run_child(self.proj, "proj", variables, states, x, training=training)
            sc = run_child(self.proj_bn, "proj_bn", variables, states, sc,
                           training=training)
        else:
            sc = x
        return jnp.maximum(y + sc, 0), states


def _space_to_depth_stem(x: jax.Array, w: jax.Array) -> jax.Array:
    """The 7x7/stride-2 stem conv, re-expressed MXU-first.

    A 7x7 conv over 3-channel images runs the systolic array at ~9% (the
    contraction dim is 7*7*3=147 elements of which only 3 land per lane and
    the strided window defeats tiling). Space-to-depth by 2 turns the same
    arithmetic into a 4x4 stride-1 conv over 12 channels: x[2i+a-2] with
    a-2 = 2*alpha + u becomes X[i+alpha, (u,v,c)], so

        y[i,j] = sum_{alpha,beta,u,v,c} X[i+alpha, j+beta, (u,v,c)]
                                        * w_pad[2*alpha+u, 2*beta+v, c]

    with w zero-padded from 7x7 to 8x8 (index 7 is the pad row/col) and
    padding (1,2) replacing SAME's (2,3). Bit-for-bit the same dot products
    as the original conv, in a layout the MXU can actually tile. The
    parameter stays [7,7,Cin,64] so checkpoints and HF interchange are
    unchanged; the pad+reshape is traced into the graph (a no-FLOP
    relayout). Requires even H,W — callers fall back to the plain conv
    otherwise.
    """
    b, h, wd, c = x.shape
    xs = x.reshape(b, h // 2, 2, wd // 2, 2, c)
    xs = xs.transpose(0, 1, 3, 2, 4, 5).reshape(b, h // 2, wd // 2, 4 * c)
    wp = jnp.pad(w, ((0, 1), (0, 1), (0, 0), (0, 0)))
    out_ch = w.shape[-1]
    ws = wp.reshape(4, 2, 4, 2, c, out_ch)
    ws = ws.transpose(0, 2, 1, 3, 4, 5).reshape(4, 4, 4 * c, out_ch)
    return jax.lax.conv_general_dilated(
        xs, ws, window_strides=(1, 1), padding=((1, 2), (1, 2)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


class ResNet(Module):
    """Generic bottleneck ResNet over NHWC images.

    ``width_factor=2`` gives the Wide-ResNet variants (inner bottleneck
    width doubled, output channels unchanged). ``stem="s2d"`` routes the
    7x7/s2 stem through :func:`_space_to_depth_stem` (same parameters,
    same math, MXU-tileable layout): measured worth ~+3% e2e over
    ``"conv7"`` on RN50 (2,212 vs 2,141 img/s, r4 — different windows,
    so inside the noise; the ~3x stem-in-isolation figure from the r3
    probe arithmetic did NOT materialize e2e, the step is
    bandwidth-bound elsewhere). ``"conv7"`` keeps the plain conv.
    """

    def __init__(self, stage_sizes: Sequence[int], num_classes: int = 1000,
                 width_factor: int = 1, in_channels: int = 3,
                 stem: str = "conv7", remat: bool = False,
                 policy: Policy = DEFAULT_POLICY):
        if stem not in ("conv7", "s2d"):
            raise ValueError(f"unknown stem {stem!r}")
        self.stage_sizes = tuple(stage_sizes)
        self.stem = stem
        # Per-bottleneck jax.checkpoint: backward recomputes each block
        # from its input instead of reading saved intermediates — the
        # big-batch memory knob, and an A/B lever for the bandwidth-bound
        # step (saved-activation reads traded for recompute FLOPs;
        # rn50_probe --variants remat measures the sign on chip).
        self.remat = remat
        self.policy = policy
        self.stem_conv = nn.Conv2d(in_channels, 64, 7, stride=2,
                                   use_bias=False, policy=policy)
        self.stem_bn = nn.BatchNorm(64, policy=policy)

        self.blocks = []
        in_ch = 64
        for stage, n_blocks in enumerate(self.stage_sizes):
            base = 64 * (2 ** stage)
            width = base * width_factor
            out_ch = base * 4
            for b in range(n_blocks):
                stride = 2 if (stage > 0 and b == 0) else 1
                self.blocks.append(
                    Bottleneck(in_ch, width, out_ch, stride, policy=policy))
                in_ch = out_ch
        self.head = nn.Linear(in_ch, num_classes,
                              kernel_init=init_lib.zeros, policy=policy)

    def apply(self, variables: Variables, batch, training: bool = False, rng=None):
        x = batch["image"] if isinstance(batch, dict) else batch
        states: dict = {}
        if self.stem == "s2d" and x.shape[1] % 2 == 0 and x.shape[2] % 2 == 0:
            pol = self.stem_conv.policy
            x = _space_to_depth_stem(
                pol.cast_to_compute(x),
                pol.cast_to_compute(variables["params"]["stem_conv"]["w"]))
        else:
            x = run_child(self.stem_conv, "stem_conv", variables, states, x,
                          training=training)
        x = run_child(self.stem_bn, "stem_bn", variables, states, x,
                      training=training)
        x = jnp.maximum(x, 0)
        x = nn.max_pool(x, 3, 2, "SAME")
        remat = self.remat and training
        for i, block in enumerate(self.blocks):
            if remat:
                # Save only each bottleneck's input; recompute its convs/
                # BNs in backward (running-stat state updates come from
                # the forward pass as usual).
                name = f"blocks{i}"

                def block_fn(bvars, xx, block=block):
                    return block.apply(bvars, xx, training=True)

                x, st = jax.checkpoint(block_fn)(
                    child_vars(variables, name), x)
                if st:
                    states[name] = st
            else:
                x = run_child(block, f"blocks{i}", variables, states, x,
                              training=training)
        x = nn.global_avg_pool(x)
        logits = run_child(self.head, "head", variables, states, x,
                           training=training)
        return jnp.asarray(logits, jnp.float32), states


def resnet50(num_classes: int = 1000, stem: str = "conv7",
             remat: bool = False,
             policy: Policy = DEFAULT_POLICY) -> ResNet:
    return ResNet((3, 4, 6, 3), num_classes=num_classes, stem=stem,
                  remat=remat, policy=policy)


def wide_resnet101(num_classes: int = 1000, stem: str = "conv7",
                   remat: bool = False,
                   policy: Policy = DEFAULT_POLICY) -> ResNet:
    """Wide-ResNet-101-2 (bottleneck width x2) — benchmark config 5."""
    return ResNet((3, 4, 23, 3), num_classes=num_classes, width_factor=2,
                  stem=stem, remat=remat, policy=policy)
