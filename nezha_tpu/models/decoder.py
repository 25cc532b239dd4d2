"""The shell the served decoders share, and the small blocks they build
their layers from.

A served decoder (Mistral-Small-4, K-EXAONE, Kimi-Linear, Xing4.0,
Falcon-H1, MiniCPM-SALA) is an embedding of the ``vocab_held`` rows it holds, one
``Block(cfg, layer, policy)`` a layer, a final RMSNorm and an untied head:
:class:`DecoderLM`. A model file supplies its config, its presets, its
``Block`` and mixers, and ``cache_leaves``; the shell keeps the rest of
the contract ``serve.Engine`` calls, with the defaults the models share.
The residual stream between the blocks is float32: a bf16 residual rounds
a value of the whole stream's magnitude at every add, the largest
rounding of the forward pass.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from nezha_tpu import nn
from nezha_tpu.nn import initializers as init_lib
from nezha_tpu.nn.module import Module, Variables, child_vars, run_child
from nezha_tpu.tensor.policy import DEFAULT_POLICY, Policy


def linear(n_in: int, n_out: int, policy: Policy) -> nn.Linear:
    return nn.Linear(n_in, n_out, use_bias=False,
                     kernel_init=init_lib.normal(0.02), policy=policy)


def project_f32(linear: nn.Linear, variables: Variables, name: str, x):
    """``x @ W`` of a bias-free child linear layer with compute-dtype
    operands and the float32 accumulator as the result: a product that
    feeds a nonlinearity, the float32 residual stream or the sampler is
    not rounded to bf16 on the way."""
    cdt = linear.policy.compute_dtype
    w = child_vars(variables, name)["params"]["w"]
    return jnp.dot(x.astype(cdt), w.astype(cdt),
                   preferred_element_type=jnp.float32)


class GatedMLP(Module):
    """``(silu(x W_gate) * (x W_up)) W_down``: the shared expert.
    Returns float32."""

    def __init__(self, d_model: int, d_ff: int, policy: Policy):
        self.gate = linear(d_model, d_ff, policy)
        self.up = linear(d_model, d_ff, policy)
        self.down = linear(d_ff, d_model, policy)

    def apply(self, variables: Variables, x, training: bool = False, rng=None):
        g = project_f32(self.gate, variables, "gate", x)
        u = project_f32(self.up, variables, "up", x)
        return project_f32(self.down, variables, "down",
                           jax.nn.silu(g) * u), {}


def conv_taps(rows, w):
    """``y_t = sum_i w_i x_{t-K+1+i}`` over ``rows`` ``[..., K - 1 + T,
    C]`` (the tail, then the chunk) -> ``[..., T, C]`` float32: a plain
    sum of ``K`` shifted slices."""
    k = w.shape[0]
    t = rows.shape[-2] - k + 1
    rows, w = rows.astype(jnp.float32), w.astype(jnp.float32)
    return sum(rows[..., i:i + t, :] * w[i] for i in range(k))


class DecoderConfig:
    """What serve.Engine and the pools read of any served decoder's
    config, in the published keys' names (a model's config dataclass
    derives from this)."""

    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def num_heads(self) -> int:
        return self.num_attention_heads

    @property
    def max_positions(self) -> int:
        return self.max_position_embeddings


class DecoderLM(Module):
    """Returns logits [B, S, vocab_held] (float32); untied head.

    A subclass sets ``Block`` and may override :meth:`enter` (the
    embedding's output -> the first block's input) and :meth:`read_out`
    (the last block's output -> the logits)."""

    Block: type
    # What serve.Engine reads of a model beside its methods: layers
    # whose recurrent state a decode step updates (``serve.ssm.*``), and
    # hyper-connection maps a token passes (``serve.mhc.*``; such a
    # model also has ``mhc_residual(states)``), and layers whose decode
    # query selects blocks (``serve.sparse.*``; such a model also has
    # ``sparse_blocks(lengths)``).
    ssm_layers = 0
    mhc_sublayers = 0
    sparse_attention_layers = 0

    def __init__(self, cfg, policy: Policy = DEFAULT_POLICY):
        if not 1 <= cfg.vocab_held <= cfg.vocab_size:
            raise ValueError(f"vocab_held {cfg.vocab_held} outside the "
                             f"vocabulary of {cfg.vocab_size}")
        self.cfg = cfg
        self.policy = policy
        self.embed = nn.Embedding(cfg.vocab_held, cfg.hidden_size,
                                  policy=policy)
        self.h = [self.Block(cfg, i, policy)
                  for i in range(cfg.num_hidden_layers)]
        self.norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps, policy)
        self.lm_head = linear(cfg.hidden_size, cfg.vocab_held, policy)

    def enter(self, x):
        return x

    def read_out(self, variables: Variables, states: dict, x):
        x = run_child(self.norm, "norm", variables, states, x)
        return project_f32(self.lm_head, variables, "lm_head", x)

    def apply(self, variables: Variables, batch, training: bool = False,
              rng=None, cache=None, pos=None, prefill: bool = False,
              active=None):
        tokens = batch["tokens"][:, :-1] if isinstance(batch, dict) else batch
        states: dict = {}
        x = self.enter(run_child(self.embed, "embed", variables, states,
                                 tokens))
        for i, block in enumerate(self.h):
            x = run_child(block, f"h{i}", variables, states, x,
                          cache=None if cache is None else cache[i],
                          pos=pos, prefill=prefill, active=active)
        return self.read_out(variables, states, x), states

    # ------------------------------------- what serve.Engine asks a model
    def caches_from_states(self, states: dict, prev: list) -> list:
        return [states.get(f"h{i}", {}).get("attn", {}).get("cache", prev[i])
                for i in range(self.cfg.num_hidden_layers)]

    def expert_load(self, states: dict) -> Optional[jax.Array]:
        """[sparse layers, experts held] int32: pairs computed per held
        expert in this forward pass; None for a model with no expert
        layer (nothing is added to its programs)."""
        if not self.cfg.sparse_layers:
            return None
        return jnp.stack([states[f"h{i}"]["moe"]["load"]
                          for i in self.cfg.sparse_layers])

    def expert_visits(self, states: dict):
        """[sparse layers, 2] int32: the (row tile, expert) visits the
        experts' kernel made in this forward pass and the held experts
        it touched (``ops/pallas/moe_experts.py::visit_plan``)."""
        return jnp.stack([states[f"h{i}"]["moe"]["visits"]
                          for i in self.cfg.sparse_layers])

    def paged_prefill_uses_kernel(self) -> bool:
        return False


def build_preset(model_cls, cfg_cls, full_kw: dict, tiny_kw: dict,
                 preset: str, policy: Optional[Policy], overrides: dict):
    """``full``: one chip's share at the published widths (``full_kw``),
    bf16 parameters and compute. ``tiny``: float32, for CPU tests."""
    if preset == "full":
        kw = dict(full_kw)
        policy = policy or Policy(jnp.bfloat16, jnp.bfloat16)
    elif preset == "tiny":
        kw = dict(tiny_kw)
        policy = policy or DEFAULT_POLICY
    else:
        raise ValueError(f"unknown preset {preset!r}")
    kw.update(overrides)
    return model_cls(cfg_cls(**kw), policy=policy)
