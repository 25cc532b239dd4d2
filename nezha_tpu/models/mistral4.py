"""Mistral-Small-4 (``model_type: mistral4``): latent attention (MLA), yarn
rotary positions, every layer a dropless expert layer with a shared expert.

Per layer, with ``x`` a token's hidden state::

    h = x + Attn(RMSNorm(x))
    y = h + Shared(RMSNorm(h)) + sum_{e in top-k, held} w_e E_e(RMSNorm(h))

**Attention.** ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb`` -> per head
``[q_nope | q_rope]``. ``[c_kv | k_r] = x W_kva``; ``c_kv = RMSNorm(c_kv)``;
``k_r = RoPE(k_r)`` is ONE rotary key shared by all heads. ``[k_nope | v]_h
= c_kv W_kvb,h``. What is cached is the row ``[c_kv | k_r]`` after norm and
rotation (``kv_lora_rank + qk_rope_head_dim`` values a token a layer, no
head axis). Two forms of the same function:

- *expanded* (prefill chunks, and the cache-less forward): keys and values
  re-expanded from the latent rows, ``s = (q_nope . k_nope + q_rope . k_r)
  * scale``;
- *absorbed* (single-token decode): ``q~_h = q_nope,h W_uk,h^T`` lives in
  the latent space, ``s = (q~_h . c_kv + q_rope,h . k_r) * scale``,
  ``o_h = (sum_t p_t c_kv,t) W_uv,h``, with ``W_uk,h`` / ``W_uv,h`` the
  two slices of ``W_kvb,h``: the cache is read once, as it lies.

**The chip's share.** ``experts_held`` (first, count) names the routed
experts whose weights live here, ``vocab_held`` the rows of the embedding
and the head. The router keeps its published width; what absent experts
would add is left out (``parallel.expert.DroplessMoE``). The ``full`` preset
is one chip's share of the deployment ``chipbench/configs/
mistral-small-4.json`` states; the text path only (no vision tower).

The model keeps the contract ``serve.Engine`` uses for GPT-2:
``apply(variables, tokens, cache=rows, pos=..., active=...)`` with a
prefill chunk at a traced scalar offset or one token a row at per-row
positions, block tables in each layer's cache dict; and it DECLARES its
per-layer cache leaves (:meth:`Mistral4.cache_leaves`), from which
``PagedSlotPool`` allocates.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp

from nezha_tpu import nn
from nezha_tpu.models.attention import MLAttention
from nezha_tpu.models.decoder import (DecoderConfig, DecoderLM, GatedMLP,
                                      build_preset)
from nezha_tpu.nn.module import Module, Variables, run_child
from nezha_tpu.ops import rotary
from nezha_tpu.parallel.expert import DroplessMoE, DroplessMoEConfig
from nezha_tpu.tensor.policy import Policy


@dataclasses.dataclass(frozen=True)
class Mistral4Config(DecoderConfig):
    # The published keys (mistralai/Mistral-Small-4-119B-2603 config.json;
    # ``rope_parameters`` flattened to ``rope_*`` / ``llama_4_scaling_beta``).
    vocab_size: int = 131072
    hidden_size: int = 4096
    num_hidden_layers: int = 36
    num_attention_heads: int = 32
    q_lora_rank: int = 1024
    kv_lora_rank: int = 256
    qk_nope_head_dim: int = 64
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 128
    num_experts_per_tok: int = 4
    n_shared_experts: int = 1
    moe_intermediate_size: int = 2048
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 1048576
    rope_theta: float = 10000.0
    rope_factor: float = 128.0
    rope_original_max: int = 8192
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    llama_4_scaling_beta: float = 0.1
    # The chip's share: routed experts (first, count) and vocabulary rows
    # whose weights are here. The defaults are the whole model.
    experts_held: Tuple[int, int] = (0, 128)
    vocab_held: int = 131072
    # How :class:`MLAttention` reads the paged latent cache at decode:
    # "kernel" is the paged decode kernel's latent form, which walks each
    # row's own live table entries once; "xla" gathers each row's whole
    # table as one view and attends it composed; "auto" is the kernel on
    # a TPU backend (what this model's cell runs) and the composed view
    # elsewhere (ServeConfig.decode_impl overrides it, as for GPT-2).
    decode_impl: str = "auto"

    @property
    def latent_width(self) -> int:
        """Values cached a token a layer: ``[c_kv | k_r]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_row_width(self) -> int:
        """The cached row as it is stored: ``latent_width`` padded with
        zeros to whole 128-lane tiles (320 -> 384). A minor dimension
        that is not a multiple of 128 makes the TPU choose a device
        layout with the BLOCK axis minor, and every program then
        re-lays-out the whole pool twice a layer (PERF.md section 5)."""
        return -(-self.latent_width // 128) * 128

    @property
    def softmax_scale(self) -> float:
        m = rotary.yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m

    @property
    def sparse_layers(self) -> Tuple[int, ...]:
        """Every layer is an expert layer."""
        return tuple(range(self.num_hidden_layers))


# One chip's share of the stated deployment: six of the 36 layers (one
# pipeline stage), 32 of the 128 routed experts and a quarter of the
# vocabulary (one of the four chips that share each layer).
FULL_KW = dict(num_hidden_layers=6, experts_held=(0, 32), vocab_held=32768)
# CPU tests: every mechanism at widths a test can afford. The yarn ramp
# lies inside the 8 rotary pairs and the 4,096 positions.
TINY_KW = dict(
    vocab_size=512, vocab_held=512, hidden_size=64, num_hidden_layers=2,
    num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=16, v_head_dim=16,
    n_routed_experts=16, num_experts_per_tok=4, experts_held=(0, 4),
    moe_intermediate_size=32, max_position_embeddings=4096,
    rope_factor=8.0, rope_original_max=64, rope_beta_fast=4.0,
    rope_beta_slow=1.0)


class Block(Module):
    def __init__(self, cfg: Mistral4Config, layer: int, policy: Policy):
        del layer       # every layer is alike
        h = cfg.hidden_size
        self.attn_norm = nn.RMSNorm(h, cfg.rms_norm_eps, policy)
        # the decode kernel's name in this model's traces: the one the
        # benchmark's ``kernel.mla_decode_*`` patterns were written for
        self.attn = MLAttention(cfg, policy,
                                decode_kernel_name="nezha_mla_decode_paged")
        self.mlp_norm = nn.RMSNorm(h, cfg.rms_norm_eps, policy)
        self.shared = GatedMLP(
            h, cfg.moe_intermediate_size * cfg.n_shared_experts, policy)
        self.moe = DroplessMoE(DroplessMoEConfig(
            d_model=h, d_ff=cfg.moe_intermediate_size,
            num_experts=cfg.n_routed_experts, top_k=cfg.num_experts_per_tok,
            experts_held=cfg.experts_held, norm_topk_prob=cfg.norm_topk_prob,
            routed_scaling_factor=cfg.routed_scaling_factor), policy)

    def apply(self, variables: Variables, x, training: bool = False, rng=None,
              cache=None, pos=None, prefill: bool = False, active=None):
        states: dict = {}
        b, s, h = x.shape
        a = run_child(self.attn, "attn", variables, states,
                      run_child(self.attn_norm, "attn_norm", variables,
                                states, x),
                      cache=cache, pos=pos, prefill=prefill, active=active)
        # The residual stream accumulates in float32 (the sublayers read
        # its norm in the compute dtype): a bf16 residual rounds a value of
        # the whole stream's magnitude at every add, the largest rounding
        # of the forward pass, for 16 KB a token of activation traffic.
        x = x.astype(jnp.float32) + a
        y = run_child(self.mlp_norm, "mlp_norm", variables, states, x)
        shared = run_child(self.shared, "shared", variables, states, y)
        # One token a row in a decode step: its rows' ``active`` mask
        # decides which pairs the expert-load counter counts.
        routed = run_child(
            self.moe, "moe", variables, states, y.reshape(b * s, h),
            active=active if (active is not None and s == 1) else None)
        return x + shared + routed.reshape(b, s, h), states


class Mistral4(DecoderLM):
    Block = Block

    # ------------------------------------- what serve.Engine asks a model
    def cache_leaves(self, block_size: int, dtype, quantized: bool = False
                     ) -> list:
        """One entry a layer, ``(group, window, leaves)``: every layer in
        the growing group, and the leaves of one pool block, name ->
        (trailing shape, dtype). One latent row a token, no head axis,
        the row (in whole 128-lane tiles) as the minor dimension."""
        if quantized:
            raise ValueError(
                "kv_dtype='int8': the latent cache has no block quantizer "
                "(its rows are a normed latent and a rotated key, not "
                "per-head K/V)")
        leaves = {"latent": ((block_size, self.cfg.latent_row_width), dtype)}
        return [("global", None, leaves)] * self.cfg.num_hidden_layers


def mistral_small4(preset: str = "full", policy: Optional[Policy] = None,
                   **overrides) -> Mistral4:
    """``models.decoder.build_preset``'s ``full`` / ``tiny``."""
    return build_preset(Mistral4, Mistral4Config, FULL_KW, TINY_KW, preset,
                        policy, overrides)
