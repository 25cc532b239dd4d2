"""BERT-base with an MLM head — benchmark config 4 (SURVEY.md §0:
"BERT-base — grad reduce-scatter + weight all-gather (ZeRO-1-style)").

Bidirectional encoder; padding handled with an additive mask; MLM loss masks
to the 15% corrupted positions via ``ignore_index=-100`` labels. Train with
`nezha_tpu.parallel.make_zero1_train_step` for the ZeRO-1 benchmark path.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from nezha_tpu import nn, ops
from nezha_tpu.nn import initializers as init_lib
from nezha_tpu.nn.module import Module, Variables, child_vars, run_child
from nezha_tpu.tensor.policy import DEFAULT_POLICY, Policy, bf16_policy


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    max_positions: int = 512
    type_vocab_size: int = 2
    num_layers: int = 12
    num_heads: int = 12
    hidden_size: int = 768
    mlp_ratio: int = 4
    dropout: float = 0.0
    # Published BERT checkpoints use 1e-12 (HF layer_norm_eps); kept in the
    # config so converted weights reproduce the torch reference exactly.
    ln_eps: float = 1e-12
    # Same protocol as GPT2Config.fused_loss_chunk: 0 -> dense fp32 logits
    # returned from apply(); -1 -> defer the tied decoder to the loss so the
    # CE keeps bf16 logits with the fp32 upcast fused into logsumexp (never
    # materializes fp32 [B,S,30522] — ~1 GB/step at B=16 S=512); >0 ->
    # sequence-chunked scan. Training-only; eval/convert paths get logits.
    fused_loss_chunk: int = 0
    # "auto": the Pallas flash kernel (causal=False) on TPU backends when a
    # layer sees NO padding mask — full-length batches, the packed-sequence
    # pretraining shape; the kernel has no arbitrary-mask path, so any
    # padding_mask falls back to composed XLA attention. Mirrors
    # GPT2Config.attn_impl (incl. the GSPMD auto-partitioner fallback).
    attn_impl: str = "auto"  # "xla" | "flash" | "auto"
    # Layer-stacked encoder applied via lax.scan — one compiled layer
    # program instead of num_layers inlined copies; params live under
    # "layers_scan" with a leading [num_layers] dim. Mirrors
    # GPT2Config.scan_layers (same parity contract, same converters via
    # nn.module.stack_prefixed_params).
    scan_layers: bool = False
    # "pallas" opts the 2/layer + emb + mlm layer norms into the fused
    # kernel on TPU (mirrors GPT2Config.ln_impl; default flips only on a
    # measured A/B win).
    ln_impl: str = "xla"


class EncoderLayer(Module):
    """Post-LN transformer encoder layer (original BERT topology)."""

    def __init__(self, cfg: BertConfig, policy: Policy):
        h = cfg.hidden_size
        self.cfg = cfg
        self.qkv = nn.Linear(h, 3 * h, kernel_init=init_lib.normal(0.02),
                             policy=policy)
        self.attn_out = nn.Linear(h, h, kernel_init=init_lib.normal(0.02),
                                  policy=policy)
        self.attn_ln = nn.LayerNorm(h, eps=cfg.ln_eps, policy=policy,
                                    impl=cfg.ln_impl)
        self.fc = nn.Linear(h, h * cfg.mlp_ratio,
                            kernel_init=init_lib.normal(0.02), policy=policy)
        self.fc_out = nn.Linear(h * cfg.mlp_ratio, h,
                                kernel_init=init_lib.normal(0.02), policy=policy)
        self.out_ln = nn.LayerNorm(h, eps=cfg.ln_eps, policy=policy,
                                   impl=cfg.ln_impl)
        self.drop = nn.Dropout(cfg.dropout)

    def apply(self, variables: Variables, x, mask=None, training: bool = False,
              rng=None, kv_lengths=None):
        cfg = self.cfg
        b, s, h = x.shape
        d = h // cfg.num_heads
        states: dict = {}
        qkv = run_child(self.qkv, "qkv", variables, states, x, training=training)
        qkv = qkv.reshape(b, s, 3, cfg.num_heads, d).transpose(2, 0, 3, 1, 4)
        impl = cfg.attn_impl
        from nezha_tpu.ops.pallas.common import resolve_interpret, use_kernels
        from nezha_tpu.parallel.gspmd import auto_partitioner_mesh
        # the enclosing gspmd trace's mesh, where its ``tp`` axis divides
        # the heads (the nested shard_map flash path's condition)
        mesh = auto_partitioner_mesh()
        if mesh is not None and ("tp" not in mesh.axis_names
                                 or cfg.num_heads % mesh.shape["tp"]):
            mesh = None
        if impl == "auto":
            # GPT2Config.attn_impl's rule: flash where the kernels run,
            # else the nested shard_map flash under such a mesh on a
            # backend that compiles them, else composed
            impl = ("xla" if mask is not None
                    else "flash" if use_kernels("auto")
                    else "flash_shmap"
                    if mesh is not None and not resolve_interpret(None)
                    else "xla")
        if impl == "flash_shmap":
            if mask is not None:
                raise ValueError("attn_impl='flash_shmap' cannot apply an "
                                 "arbitrary padding mask; use right-padded "
                                 "batches with kv_lengths, or 'xla'")
            from nezha_tpu.models.gpt2 import _tp_sharded_flash
            if mesh is None:
                raise ValueError(
                    f"attn_impl='flash_shmap' needs an enclosing gspmd "
                    f"trace carrying a mesh with a 'tp' axis dividing "
                    f"num_heads={cfg.num_heads}")
            att = _tp_sharded_flash(qkv[0], qkv[1], qkv[2], mesh,
                                    causal=False, kv_lengths=kv_lengths)
        elif impl == "flash":
            if mask is not None:
                raise ValueError("attn_impl='flash' cannot apply an "
                                 "arbitrary padding mask; use right-padded "
                                 "batches with kv_lengths, or 'xla'")
            from nezha_tpu.ops.pallas import flash_attention
            att = flash_attention(qkv[0], qkv[1], qkv[2], causal=False,
                                  kv_lengths=kv_lengths)
        else:
            if kv_lengths is not None and mask is None:
                # Same right-padding contract as the flash path, composed:
                # a prefix mask built from the lengths, clamped to >= 1 so
                # a fully-padded row attends to position 0 instead of
                # NaN-ing the softmax (flash_attention clamps identically).
                import jax.numpy as jnp
                mask = ops.make_attention_mask(
                    jnp.arange(s)[None, :]
                    < jnp.maximum(kv_lengths, 1)[:, None])
            att = ops.dot_product_attention(qkv[0], qkv[1], qkv[2], mask=mask)
        att = att.transpose(0, 2, 1, 3).reshape(b, s, h)
        att = run_child(self.attn_out, "attn_out", variables, states, att,
                        training=training)
        att = run_child(self.drop, "drop", variables, states, att,
                        training=training, rng=rng)
        x = run_child(self.attn_ln, "attn_ln", variables, states, x + att,
                      training=training)
        y = run_child(self.fc, "fc", variables, states, x, training=training)
        y = ops.gelu(y, approximate=False)  # original BERT uses erf GELU
        y = run_child(self.fc_out, "fc_out", variables, states, y,
                      training=training)
        return run_child(self.out_ln, "out_ln", variables, states, x + y,
                         training=training), states


class ScannedEncoder(Module):
    """``num_layers`` homogeneous :class:`EncoderLayer`s with layer-stacked
    params, applied via ``lax.scan`` (one compiled layer program; see
    ``models.gpt2.ScannedBlocks`` for the full rationale). ``mask`` /
    ``kv_lengths`` are layer-invariant broadcast inputs (closures), not
    scan operands; per-layer dropout RNGs pre-split with the SAME
    ``layers{i}`` derivation as the unrolled encoder."""

    _init_with_parent_rng = True  # layer keys derive from Bert's rng

    def __init__(self, cfg: BertConfig, policy: Policy):
        self.cfg = cfg
        self.layer = EncoderLayer(cfg, policy)  # structure template

    def init(self, rng: jax.Array) -> Variables:
        from nezha_tpu.nn.module import scan_stack_init
        return scan_stack_init(self.layer, rng, self.cfg.num_layers,
                               "layers")

    def apply(self, variables: Variables, x, mask=None, training: bool = False,
              rng=None, kv_lengths=None):
        from nezha_tpu.nn.module import scan_stack_apply
        x = scan_stack_apply(self.layer, variables["params"], x,
                             self.cfg.num_layers, "layers", rng=rng,
                             mask=mask, training=training,
                             kv_lengths=kv_lengths)
        return x, {}


class Bert(Module):
    """Returns MLM logits [B, S, vocab] (decoder tied to token embeddings).

    ``batch``: {"tokens": [B,S], "segment_ids": [B,S], "padding_mask": [B,S]
    bool, "labels": [B,S] with -100 at unmasked positions}.
    """

    def __init__(self, cfg: BertConfig = BertConfig(),
                 policy: Policy = DEFAULT_POLICY):
        self.cfg = cfg
        self.policy = policy
        h = cfg.hidden_size
        self.tok_emb = nn.Embedding(cfg.vocab_size, h, policy=policy)
        self.pos_emb = nn.Embedding(cfg.max_positions, h,
                                    embedding_init=init_lib.normal(0.02),
                                    policy=policy)
        self.type_emb = nn.Embedding(cfg.type_vocab_size, h, policy=policy)
        self.emb_ln = nn.LayerNorm(h, eps=cfg.ln_eps, policy=policy,
                                   impl=cfg.ln_impl)
        self.drop = nn.Dropout(cfg.dropout)
        if cfg.scan_layers:
            self.layers_scan = ScannedEncoder(cfg, policy)
            self.layers = []
        else:
            self.layers = [EncoderLayer(cfg, policy)
                           for _ in range(cfg.num_layers)]
        # MLM head: transform + LN, decoder tied to tok_emb with a free bias.
        self.mlm_dense = nn.Linear(h, h, kernel_init=init_lib.normal(0.02),
                                   policy=policy)
        self.mlm_ln = nn.LayerNorm(h, eps=cfg.ln_eps, policy=policy,
                                   impl=cfg.ln_impl)

    def init(self, rng: jax.Array) -> Variables:
        v = super().init(rng)
        v["params"]["mlm_bias"] = jnp.zeros((self.cfg.vocab_size,),
                                            self.policy.param_dtype)
        return v

    def apply(self, variables: Variables, batch, training: bool = False, rng=None):
        tokens = batch["tokens"]
        segment_ids = batch.get("segment_ids")
        padding_mask = batch.get("padding_mask")
        # Right-padded batches: "kv_lengths" ([B] int32, each >= 1) keeps
        # the flash path (the kernel masks key columns >= length); the
        # composed path builds the equivalent prefix mask. Mutually
        # exclusive with an explicit padding_mask.
        kv_lengths = batch.get("kv_lengths") if isinstance(batch, dict) \
            else None
        if kv_lengths is not None and padding_mask is not None:
            raise ValueError("pass either padding_mask or kv_lengths, "
                             "not both")
        states: dict = {}
        s = tokens.shape[1]
        if s > self.cfg.max_positions:
            # Without this, the position-embedding gather silently clamps.
            raise ValueError(
                f"sequence length {s} exceeds max_positions "
                f"{self.cfg.max_positions}")
        pos = jnp.arange(s)[None, :]
        x = run_child(self.tok_emb, "tok_emb", variables, states, tokens,
                      training=training)
        x = x + run_child(self.pos_emb, "pos_emb", variables, states, pos,
                          training=training)
        if segment_ids is not None:
            x = x + run_child(self.type_emb, "type_emb", variables, states,
                              segment_ids, training=training)
        x = run_child(self.emb_ln, "emb_ln", variables, states, x,
                      training=training)
        x = run_child(self.drop, "drop", variables, states, x,
                      training=training, rng=rng)
        mask = (ops.make_attention_mask(padding_mask)
                if padding_mask is not None else None)
        if self.cfg.scan_layers:
            # rng passed RAW: ScannedEncoder derives per-layer layers{i}
            # keys itself, matching the unrolled encoder exactly.
            x, _ = self.layers_scan.apply(
                child_vars(variables, "layers_scan"), x, mask=mask,
                training=training, rng=rng, kv_lengths=kv_lengths)
        for i, layer in enumerate(self.layers):
            x = run_child(layer, f"layers{i}", variables, states, x,
                          mask=mask, training=training, rng=rng,
                          kv_lengths=kv_lengths)
        y = run_child(self.mlm_dense, "mlm_dense", variables, states, x,
                      training=training)
        y = ops.gelu(y, approximate=False)  # original BERT uses erf GELU
        y = run_child(self.mlm_ln, "mlm_ln", variables, states, y,
                      training=training)
        if self.cfg.fused_loss_chunk and training:
            # Defer the tied decoder to the loss (mlm_loss ->
            # ops.lm_ce_from_fused): bf16 logits with the fp32 upcast fused
            # into logsumexp, or a chunked scan — the fp32 [B,S,V] tensor is
            # never written to HBM. Same protocol as GPT-2's fused head.
            wte = child_vars(variables, "tok_emb")["params"]["embedding"]
            return {"hidden": y, "wte": wte,
                    "bias": variables["params"]["mlm_bias"],
                    "chunk": self.cfg.fused_loss_chunk}, states
        logits = self.tok_emb.attend(child_vars(variables, "tok_emb"), y)
        logits = logits + self.policy.cast_to_compute(
            variables["params"]["mlm_bias"])
        return jnp.asarray(logits, jnp.float32), states


def bert_base(policy: Policy | None = None, **overrides) -> Bert:
    cfg = BertConfig(**overrides)
    return Bert(cfg, policy=policy or bf16_policy())


def mlm_loss(out, batch):
    """MLM CE over the 15% corrupted positions (labels == -100 elsewhere).
    Accepts dense logits or the fused-head dict (BertConfig.fused_loss_chunk)."""
    if isinstance(out, dict):
        return ops.lm_ce_from_fused(out, batch["labels"], ignore_index=-100)
    return ops.softmax_cross_entropy_with_integer_labels(
        out, batch["labels"], ignore_index=-100)
