"""Xing4.0 (``model_type: xing4_0``): the DeepSeek-V3 line's block (latent
attention with a low-rank query and yarn rotary; leading dense layers, then
sigmoid-routed dropless experts with a selection bias and a shared expert)
on a residual path of ``hc_mult`` streams mixed by manifold-constrained
hyper-connections (``nn/hyper_connections.py``: one map a SUBLAYER, its
``H_res`` made doubly stochastic by ``hc_sinkhorn_iters`` Sinkhorn rounds).

A token carries ``X`` in ``R^{n x C}`` (float32, stored flat ``[n*C]``).
``X_0`` is the token's embedding in every stream; per layer, with ``HC_a`` /
``HC_m`` the attention and MLP sublayers' maps::

    u  = H_pre^a X;    X <- H_res^a X + H_post^a^T Attn(RMSNorm(u))
    u  = H_pre^m X;    X <- H_res^m X + H_post^m^T MLP(RMSNorm(u))
    MLP = GatedMLP(intermediate_size)                l < first_k_dense_replace
    MLP = Shared(v) + sum_{e in top-k, held} w_e E_e(v)             otherwise

and after the last layer ``logits = RMSNorm(sum_i X_i) W_head`` (untied).
``Attn`` is ``mistral4.MLAttention`` as Mistral-Small-4 runs it (absorbed at
decode through the paged kernel's latent form, yarn's ``m^2`` in the softmax
scale, interleaved rotary pairs, no position-dependent query scaling); the
router is ``parallel.expert.route_top_k`` with sigmoid scores, one group.

Nothing of the serving contract changes: ``apply(variables, tokens,
cache=rows, pos=..., active=...)``, one paged latent row a token a layer
(:meth:`Xing4.cache_leaves`); the streams live only inside a forward pass.
The model also DECLARES :meth:`Xing4.mhc_residual`: how far the pass's
worst ``H_res`` lies from doubly stochastic, which the serve programs return
beside the expert load. **The chip's share**, as in ``mistral4.py``:
``experts_held``, ``vocab_held``; the ``full`` preset is the cut that
``chipbench/configs/xing4.0-29b.json`` states (every expert, the whole
vocabulary, the first six layers).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp

from nezha_tpu import nn
from nezha_tpu.models.mistral4 import (GatedMLP, MLAttention, _linear,
                                       _project_f32)
from nezha_tpu.nn.hyper_connections import HyperConnection, sinkhorn_residual
from nezha_tpu.nn.module import Module, Variables, child_vars, run_child
from nezha_tpu.ops import rotary
from nezha_tpu.parallel.expert import DroplessMoE, DroplessMoEConfig
from nezha_tpu.tensor.policy import DEFAULT_POLICY, Policy


@dataclasses.dataclass(frozen=True)
class Xing4Config:
    # The published keys (XingChen-AGI/Xing4.0-29B-A4B config.json;
    # ``rope_scaling`` flattened to ``rope_*``).
    vocab_size: int = 131072
    hidden_size: int = 3584
    intermediate_size: int = 9216
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    first_k_dense_replace: int = 2
    n_routed_experts: int = 64
    num_experts_per_tok: int = 4
    n_shared_experts: int = 1
    moe_intermediate_size: int = 1024
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.0
    scoring_func: str = "sigmoid"
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262144
    rope_theta: float = 10000.0
    rope_factor: float = 64.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    # The chip's share (the defaults are the whole model).
    experts_held: Tuple[int, int] = (0, 64)
    vocab_held: int = 131072
    # "auto": on a TPU the paged kernel's latent form at decode and the
    # two ``nezha_mhc`` kernels on every path; their ``jax.numpy`` twins
    # elsewhere. "kernel" / "xla" force one (ServeConfig.decode_impl).
    decode_impl: str = "auto"

    # What serve.Engine and the pools read of any model's config.
    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def num_heads(self) -> int:
        return self.num_attention_heads

    @property
    def max_positions(self) -> int:
        return self.max_position_embeddings

    # What mistral4.MLAttention reads beside the published keys.
    llama_4_scaling_beta = 0.0      # the query is not scaled by position

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_row_width(self) -> int:
        """The cached row in whole 128-lane tiles (576 -> 640)."""
        return -(-self.latent_width // 128) * 128

    @property
    def softmax_scale(self) -> float:
        m = rotary.yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m

    @property
    def sparse_layers(self) -> Tuple[int, ...]:
        return tuple(range(self.first_k_dense_replace,
                           self.num_hidden_layers))


# One chip's share of the stated deployment: the first of eight pipeline
# stages (both dense layers and four sparse ones, every layer whole).
FULL_KW = dict(num_hidden_layers=6)
# CPU tests: every mechanism at widths a test can afford.
TINY_KW = dict(
    vocab_size=512, vocab_held=512, hidden_size=64, intermediate_size=128,
    num_hidden_layers=4, num_attention_heads=4, q_lora_rank=32,
    kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=16, v_head_dim=16,
    first_k_dense_replace=2, n_routed_experts=8, num_experts_per_tok=2,
    experts_held=(0, 8), moe_intermediate_size=32,
    max_position_embeddings=4096, rope_factor=8.0, rope_original_max=64,
    rope_beta_fast=4.0, rope_beta_slow=1.0)


class Block(Module):
    def __init__(self, cfg: Xing4Config, layer: int, policy: Policy):
        h = cfg.hidden_size
        hc = lambda: HyperConnection(       # noqa: E731
            h, cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps,
            (cfg.mhc_h_res_clamp_min, cfg.mhc_h_res_clamp_max),
            cfg.rms_norm_eps, impl=cfg.decode_impl)
        self.n = cfg.hc_mult
        self.hc_attn, self.hc_mlp = hc(), hc()
        self.attn_norm = nn.RMSNorm(h, cfg.rms_norm_eps, policy)
        # the decode kernel under the name the benchmark's
        # ``kernel.mla_decode_*`` patterns carry (as Mistral-Small-4's)
        self.attn = MLAttention(cfg, policy,
                                decode_kernel_name="nezha_mla_decode_paged")
        self.mlp_norm = nn.RMSNorm(h, cfg.rms_norm_eps, policy)
        self.sparse = layer >= cfg.first_k_dense_replace
        if not self.sparse:
            self.mlp = GatedMLP(h, cfg.intermediate_size, policy)
            return
        self.shared = GatedMLP(
            h, cfg.moe_intermediate_size * cfg.n_shared_experts, policy)
        self.moe = DroplessMoE(DroplessMoEConfig(
            d_model=h, d_ff=cfg.moe_intermediate_size,
            num_experts=cfg.n_routed_experts, top_k=cfg.num_experts_per_tok,
            experts_held=cfg.experts_held, norm_topk_prob=cfg.norm_topk_prob,
            routed_scaling_factor=cfg.routed_scaling_factor,
            score_func=cfg.scoring_func), policy)

    def apply(self, variables: Variables, x, training: bool = False, rng=None,
              cache=None, pos=None, prefill: bool = False, active=None):
        """``x`` [B, S, n*C] float32 streams -> the new streams."""
        states: dict = {}
        b, s, _ = x.shape
        u, maps_a = self.hc_attn.pre(child_vars(variables, "hc_attn"), x)
        a = run_child(self.attn, "attn", variables, states,
                      run_child(self.attn_norm, "attn_norm", variables,
                                states, u),
                      cache=cache, pos=pos, prefill=prefill, active=active)
        x = self.hc_attn.post(x, a, maps_a)
        u, maps_m = self.hc_mlp.pre(child_vars(variables, "hc_mlp"), x)
        y = run_child(self.mlp_norm, "mlp_norm", variables, states, u)
        if not self.sparse:
            f = run_child(self.mlp, "mlp", variables, states, y)
        else:
            # one token a row in a decode step: its rows' ``active`` mask
            # decides which pairs the expert-load counter counts
            f = run_child(self.shared, "shared", variables, states, y) \
                + run_child(
                    self.moe, "moe", variables, states,
                    y.reshape(b * s, -1),
                    active=active if (active is not None and s == 1)
                    else None).reshape(b, s, -1)
        states["mhc"] = jnp.maximum(sinkhorn_residual(maps_a, self.n),
                                    sinkhorn_residual(maps_m, self.n))
        return self.hc_mlp.post(x, f, maps_m), states


class Xing4(Module):
    """Returns logits [B, S, vocab_held] (float32); untied head. The
    streams between the blocks are float32."""

    def __init__(self, cfg: Xing4Config = Xing4Config(),
                 policy: Policy = DEFAULT_POLICY):
        if not 1 <= cfg.vocab_held <= cfg.vocab_size:
            raise ValueError(f"vocab_held {cfg.vocab_held} outside the "
                             f"vocabulary of {cfg.vocab_size}")
        self.cfg = cfg
        self.policy = policy
        self.embed = nn.Embedding(cfg.vocab_held, cfg.hidden_size,
                                  policy=policy)
        self.h = [Block(cfg, i, policy)
                  for i in range(cfg.num_hidden_layers)]
        self.norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps, policy)
        self.lm_head = _linear(cfg.hidden_size, cfg.vocab_held, policy)

    def apply(self, variables: Variables, batch, training: bool = False,
              rng=None, cache=None, pos=None, prefill: bool = False,
              active=None):
        tokens = batch["tokens"][:, :-1] if isinstance(batch, dict) else batch
        states: dict = {}
        n = self.cfg.hc_mult
        e = run_child(self.embed, "embed", variables, states,
                      tokens).astype(jnp.float32)
        x = jnp.concatenate([e] * n, axis=-1)       # every stream starts as e
        for i, block in enumerate(self.h):
            x = run_child(block, f"h{i}", variables, states, x,
                          cache=None if cache is None else cache[i],
                          pos=pos, prefill=prefill, active=active)
        x = x.reshape(x.shape[:-1] + (n, -1)).sum(axis=-2)  # the read-out
        x = run_child(self.norm, "norm", variables, states, x)
        return _project_f32(self.lm_head, variables, "lm_head", x), states

    # ------------------------------------- what serve.Engine asks a model
    def cache_leaves(self, block_size: int, dtype, quantized: bool = False
                     ) -> list:
        """One entry a layer, ``(group, window, leaves)``: one latent row a
        token in the growing group, as ``Mistral4``."""
        if quantized:
            raise ValueError(
                "kv_dtype='int8': the latent cache has no block quantizer "
                "(its rows are a normed latent and a rotated key, not "
                "per-head K/V)")
        leaves = {"latent": ((block_size, self.cfg.latent_row_width), dtype)}
        return [("global", None, leaves)] * self.cfg.num_hidden_layers

    def caches_from_states(self, states: dict, prev: list) -> list:
        return [states.get(f"h{i}", {}).get("attn", {}).get("cache", prev[i])
                for i in range(self.cfg.num_hidden_layers)]

    def expert_load(self, states: dict):
        """[sparse layers, experts held] int32: pairs computed per held
        expert in this forward pass."""
        return jnp.stack([states[f"h{i}"]["moe"]["load"]
                          for i in self.cfg.sparse_layers])

    def expert_visits(self, states: dict):
        """[sparse layers, 2] int32: the experts' kernel's (row tile,
        expert) visits and the held experts it touched."""
        return jnp.stack([states[f"h{i}"]["moe"]["visits"]
                          for i in self.cfg.sparse_layers])

    def mhc_residual(self, states: dict):
        """The largest ``|row sum - 1|`` or ``|column sum - 1|`` of any
        ``H_res`` of this forward pass (every token, both sublayers of
        every layer): one float32."""
        return jnp.stack([states[f"h{i}"]["mhc"]
                          for i in range(self.cfg.num_hidden_layers)]).max()

    @property
    def mhc_sublayers(self) -> int:
        """Maps computed a token a forward pass."""
        return 2 * self.cfg.num_hidden_layers

    def paged_prefill_uses_kernel(self) -> bool:
        return False


def xing4(preset: str = "full", policy: Optional[Policy] = None,
          **overrides) -> Xing4:
    """``full``: the first pipeline stage at the published widths
    (``FULL_KW``), bf16 parameters and compute. ``tiny``: float32, for CPU
    tests."""
    if preset == "full":
        kw = dict(FULL_KW)
        policy = policy or Policy(jnp.bfloat16, jnp.bfloat16)
    elif preset == "tiny":
        kw = dict(TINY_KW)
        policy = policy or DEFAULT_POLICY
    else:
        raise ValueError(f"unknown preset {preset!r}")
    kw.update(overrides)
    return Xing4(Xing4Config(**kw), policy=policy)
