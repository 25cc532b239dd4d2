"""GPT-2 forward pass, plainly: ``jax.numpy``, float32, no cache, no kernels.

The yardstick the serve and train drivers compare the program with. It
follows the published architecture (Radford et al. 2019; the
``openai-community/gpt2`` config): token + learned position embeddings,
``n_layer`` pre-LN blocks (causal multi-head attention, 4x tanh-GELU MLP),
a final layer norm and a head tied to the token embedding. Layer-norm
epsilon is 1e-5. Every matrix product runs under
``default_matmul_precision("highest")``: on a TPU a float32 product is
otherwise computed in bf16 passes.

It reads the program's parameter tree (weights are data: ``wte``, ``wpe``,
``h{i}/{ln_1,attn/{qkv,proj},ln_2,mlp/{fc,proj}}``, ``ln_f``; linear layers
hold ``w`` [in, out] and ``b``) and nothing else of the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-5


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def _layer_norm(p, x):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _linear(p, x):
    return x @ p["w"] + p["b"]


def _gelu_tanh(x):
    c = math.sqrt(2.0 / math.pi)
    return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x ** 3)))


def _attention(p, x, num_heads):
    b, s, h = x.shape
    d = h // num_heads
    qkv = _linear(p["qkv"], x).reshape(b, s, 3, num_heads, d)
    q, k, v = (qkv[:, :, i].transpose(0, 2, 1, 3) for i in range(3))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), v)
    return _linear(p["proj"], out.transpose(0, 2, 1, 3).reshape(b, s, h))


def hidden(params, tokens, num_heads):
    """Final-layer-norm hidden states [B, S, h] for ``tokens`` [B, S]."""
    with jax.default_matmul_precision("highest"):
        p = _f32(params)
        s = tokens.shape[1]
        x = p["wte"]["embedding"][tokens] + p["wpe"]["embedding"][:s][None]
        layer = 0
        while f"h{layer}" in p:
            blk = p[f"h{layer}"]
            x = x + _attention(blk["attn"], _layer_norm(blk["ln_1"], x),
                               num_heads)
            y = _layer_norm(blk["ln_2"], x)
            x = x + _linear(blk["mlp"]["proj"],
                            _gelu_tanh(_linear(blk["mlp"]["fc"], y)))
            layer += 1
        return _layer_norm(p["ln_f"], x)


def logits(params, tokens, num_heads):
    """Logits [B, S, V] (float32). Memory: B*S*V*4 bytes: small inputs."""
    with jax.default_matmul_precision("highest"):
        h = hidden(params, tokens, num_heads)
        return h @ jnp.asarray(params["wte"]["embedding"], jnp.float32).T


def logits_at(params, tokens, positions, num_heads):
    """Logits [B, K, V] at ``positions`` [B, K] only: the head is applied
    to K rows a sequence, so a 1,024-token sequence never needs its
    S*V logits."""
    with jax.default_matmul_precision("highest"):
        h = hidden(params, tokens, num_heads)
        rows = jnp.take_along_axis(h, positions[..., None], axis=1)
        return rows @ jnp.asarray(params["wte"]["embedding"], jnp.float32).T


def lm_loss_sum(params, tokens, num_heads):
    """Summed next-token cross-entropy of ``tokens`` [B, S+1] and the
    number of targets: the caller adds blocks and divides."""
    lg = logits(params, tokens[:, :-1], num_heads)
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(lg, -1)
    nll = -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]
    return nll.sum(), targets.size
