"""K-EXAONE (``model_type: exaone_moe``): grouped-query attention with
window layers beside global layers, QK-norm, a leading dense layer and
then sigmoid-routed dropless experts with a shared expert.

Per layer ``l``, with ``x`` a token's hidden state (the residual stream is
float32, as in ``decoder.py``)::

    h = x + Attn_l(RMSNorm(x))
    y = h + MLP(RMSNorm(h))                          # l < first_k_dense_replace
    y = h + Shared(n) + sum_{e in top-k, held} w_e E_e(n),  n = RMSNorm(h)

**Attention.** ``q = n W_q`` -> ``H`` heads of ``D``; ``k = n W_k``, ``v =
n W_v`` -> ``KVH`` heads of ``D``; no biases. ``q_h`` and ``k_g`` are
RMS-normed over the head dimension (one learned ``D``-vector each a
layer), before rotation. A ``sliding_attention`` layer rotates ``q, k``
over all ``D`` dimensions in the half-split form (``ops.rotary.
apply_half_split``, theta 1e6); a ``full_attention`` layer does NOT rotate
(the model card's "global attention: no rotary positional embedding").
Query head ``h`` reads K/V head ``h // (H / KVH)``. Key ``j`` is visible to
query ``i`` iff ``j <= i`` and, on a sliding layer, ``i - j <
sliding_window``. The cache holds ``k`` after norm and rotation, and ``v``,
as lane-dense rows ``[N, bs, KVH*D]``.

**Two cache groups.** A full layer's blocks live in the growing table
(``max_len / bs`` entries a slot); a sliding layer's in a RING of
``ceil(window / bs) + 1`` entries (``serve/slots.py``): position ``p`` is
entry ``(p // bs) % ring``. Decode is the paged kernel
(``ops/pallas/decode_attention.py``: one body, a full-table call and a
ring call) or, off the TPU, its composed form. A prefill chunk at a traced
offset is blocked over keys in ``jax.numpy``: a full layer writes the
chunk and folds the table's key blocks up to its last query with an
online softmax; a sliding layer reads the window before the chunk out of
the ring, attends ``[that | the chunk]`` in bands, and then writes only
what later queries can see. Neither writes a pad (the engine says how
many of the chunk's tokens are real: ``cache["valid"]``).

**Router.** ``s = sigmoid(n W_r)`` over all experts; the chosen are the
largest of ``s + b`` (``b``: the selection bias), the weights ``s`` of the
chosen over their sum, times ``routed_scaling_factor`` (``parallel.expert.
route_top_k``, ``score_func="sigmoid"``).

**The chip's share**, as in ``mistral4.py``: ``experts_held`` (first,
count), ``vocab_held``; the ``full`` preset is the cut
``chipbench/configs/k-exaone-236b.json`` states. The multi-token-
prediction layer is not built.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp

from nezha_tpu import nn
from nezha_tpu.models.attention import GQAttention
from nezha_tpu.models.decoder import (DecoderConfig, DecoderLM, GatedMLP,
                                      build_preset)
from nezha_tpu.nn.module import Module, Variables, run_child
from nezha_tpu.parallel.expert import DroplessMoE, DroplessMoEConfig
from nezha_tpu.tensor.policy import DEFAULT_POLICY, Policy

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class ExaoneMoeConfig(DecoderConfig):
    # The published keys (LGAI-EXAONE/K-EXAONE-236B-A23B config.json;
    # ``rope_parameters.rope_theta`` flattened).
    vocab_size: int = 153600
    hidden_size: int = 6144
    intermediate_size: int = 18432
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    # layer ``l`` is ``layer_types[l]``: the published pattern, 12 x LLLG
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL) * 12
    sliding_window: int = 128
    first_k_dense_replace: int = 1
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    moe_intermediate_size: int = 2048
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e6
    max_position_embeddings: int = 262144
    # The chip's share (the defaults are the whole model).
    experts_held: Tuple[int, int] = (0, 128)
    vocab_held: int = 153600
    # "auto": the paged decode kernel on a TPU backend, composed
    # elsewhere; "kernel" / "xla" force one (ServeConfig.decode_impl).
    decode_impl: str = "auto"

    def window_of(self, layer: int) -> Optional[int]:
        return (self.sliding_window if self.layer_types[layer] == SLIDING
                else None)

    @property
    def sparse_layers(self) -> Tuple[int, ...]:
        return tuple(range(self.first_k_dense_replace,
                           self.num_hidden_layers))


# One chip's share of the stated deployment: the first five layers (the
# dense layer, then a whole sliding x3 / full period), 16 of the 128
# routed experts and an eighth of the vocabulary.
FULL_KW = dict(num_hidden_layers=5, experts_held=(0, 16), vocab_held=19200)
# CPU tests: every mechanism at widths a test can afford.
TINY_KW = dict(
    vocab_size=512, vocab_held=512, hidden_size=64, intermediate_size=128,
    num_hidden_layers=5, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, sliding_window=8, num_experts=16, num_experts_per_tok=4,
    experts_held=(0, 4), moe_intermediate_size=32,
    max_position_embeddings=4096)


class Block(Module):
    def __init__(self, cfg: ExaoneMoeConfig, layer: int, policy: Policy):
        h = cfg.hidden_size
        self.attn_norm = nn.RMSNorm(h, cfg.rms_norm_eps, policy)
        self.attn = GQAttention(cfg, cfg.window_of(layer), policy)
        self.mlp_norm = nn.RMSNorm(h, cfg.rms_norm_eps, policy)
        self.sparse = layer >= cfg.first_k_dense_replace
        if not self.sparse:
            self.mlp = GatedMLP(h, cfg.intermediate_size, policy)
            return
        self.shared = GatedMLP(
            h, cfg.moe_intermediate_size * cfg.num_shared_experts, policy)
        self.moe = DroplessMoE(DroplessMoEConfig(
            d_model=h, d_ff=cfg.moe_intermediate_size,
            num_experts=cfg.num_experts, top_k=cfg.num_experts_per_tok,
            experts_held=cfg.experts_held, norm_topk_prob=cfg.norm_topk_prob,
            routed_scaling_factor=cfg.routed_scaling_factor,
            score_func=cfg.scoring_func), policy)

    def apply(self, variables: Variables, x, training: bool = False, rng=None,
              cache=None, pos=None, prefill: bool = False, active=None):
        states: dict = {}
        b, s, h = x.shape
        a = run_child(self.attn, "attn", variables, states,
                      run_child(self.attn_norm, "attn_norm", variables,
                                states, x),
                      cache=cache, pos=pos, prefill=prefill, active=active)
        x = x.astype(jnp.float32) + a       # float32 residual (decoder.py)
        y = run_child(self.mlp_norm, "mlp_norm", variables, states, x)
        if not self.sparse:
            return x + run_child(self.mlp, "mlp", variables, states, y), states
        shared = run_child(self.shared, "shared", variables, states, y)
        routed = run_child(
            self.moe, "moe", variables, states, y.reshape(b * s, h),
            active=active if (active is not None and s == 1) else None)
        return x + shared + routed.reshape(b, s, h), states


class ExaoneMoe(DecoderLM):
    Block = Block

    def __init__(self, cfg: ExaoneMoeConfig = ExaoneMoeConfig(),
                 policy: Policy = DEFAULT_POLICY):
        if cfg.num_attention_heads % cfg.num_key_value_heads:
            raise ValueError(
                f"{cfg.num_attention_heads} query heads are not whole "
                f"groups over {cfg.num_key_value_heads} K/V heads")
        if len(cfg.layer_types) < cfg.num_hidden_layers:
            raise ValueError(
                f"layer_types names {len(cfg.layer_types)} layers, "
                f"num_hidden_layers is {cfg.num_hidden_layers}")
        super().__init__(cfg, policy)

    # ------------------------------------- what serve.Engine asks a model
    def cache_leaves(self, block_size: int, dtype, quantized: bool = False
                     ) -> list:
        """One entry a layer, ``(group, window, leaves)``: a full layer
        in the growing group, a sliding layer in the ring; K and V rows
        ``(block_size, KVH*D)`` either way."""
        if quantized:
            raise ValueError(
                "kv_dtype='int8': the int8 pool's scales are per (block, "
                "query head) and its kernels have no grouped-query or "
                "window form")
        c = self.cfg
        kv = (block_size, c.num_key_value_heads * c.head_dim)
        leaves = {"k": (kv, dtype), "v": (kv, dtype)}
        return [("global" if c.window_of(i) is None else "window",
                 c.window_of(i), leaves)
                for i in range(c.num_hidden_layers)]


def k_exaone(preset: str = "full", policy: Optional[Policy] = None,
             **overrides) -> ExaoneMoe:
    """``models.decoder.build_preset``'s ``full`` / ``tiny``."""
    return build_preset(ExaoneMoe, ExaoneMoeConfig, FULL_KW, TINY_KW, preset,
                        policy, overrides)
