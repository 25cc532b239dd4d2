"""BERT-base forward pass with its MLM head, plainly: ``jax.numpy``,
float32, no kernels.

Follows Devlin et al. 2019 and the ``google-bert/bert-base-uncased``
config: token + position + segment embeddings, an embedding layer norm,
post-LN encoder layers (bidirectional multi-head attention, 4x erf-GELU
MLP), and the MLM head (dense, erf-GELU, layer norm, decoder tied to the
token embedding plus a free bias). Layer-norm epsilon is 1e-12. Every
matrix product runs under ``default_matmul_precision("highest")``.
Full-length sequences only: no padding mask.

It reads the program's parameter tree (``tok_emb``, ``pos_emb``,
``type_emb``, ``emb_ln``, ``layers{i}/{qkv,attn_out,attn_ln,fc,fc_out,
out_ln}``, ``mlm_dense``, ``mlm_ln``, ``mlm_bias``) and nothing else of
the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-12
IGNORE = -100


def _layer_norm(p, x):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _linear(p, x):
    return x @ p["w"] + p["b"]


def _gelu_erf(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def _attention(p, x, num_heads):
    b, s, h = x.shape
    d = h // num_heads
    qkv = _linear(p["qkv"], x).reshape(b, s, 3, num_heads, d)
    q, k, v = (qkv[:, :, i].transpose(0, 2, 1, 3) for i in range(3))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(d)
    out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), v)
    return _linear(p["attn_out"], out.transpose(0, 2, 1, 3).reshape(b, s, h))


def mlm_logits(params, tokens, segment_ids, num_heads):
    """MLM logits [B, S, V] (float32)."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                   params)
        s = tokens.shape[1]
        x = p["tok_emb"]["embedding"][tokens] \
            + p["pos_emb"]["embedding"][:s][None]
        if segment_ids is not None:
            x = x + p["type_emb"]["embedding"][segment_ids]
        x = _layer_norm(p["emb_ln"], x)
        layer = 0
        while f"layers{layer}" in p:
            lp = p[f"layers{layer}"]
            x = _layer_norm(lp["attn_ln"], x + _attention(lp, x, num_heads))
            y = _linear(lp["fc_out"], _gelu_erf(_linear(lp["fc"], x)))
            x = _layer_norm(lp["out_ln"], x + y)
            layer += 1
        y = _layer_norm(p["mlm_ln"], _gelu_erf(_linear(p["mlm_dense"], x)))
        return y @ p["tok_emb"]["embedding"].T + p["mlm_bias"]


def mlm_loss_sum(params, tokens, segment_ids, labels, num_heads):
    """Summed cross-entropy over the positions whose label is not -100,
    and their number: the caller adds blocks and divides."""
    lg = mlm_logits(params, tokens, segment_ids, num_heads)
    keep = labels != IGNORE
    logp = jax.nn.log_softmax(lg, -1)
    safe = jnp.where(keep, labels, 0)
    nll = -jnp.take_along_axis(logp, safe[..., None], -1)[..., 0]
    return jnp.where(keep, nll, 0.0).sum(), keep.sum()
