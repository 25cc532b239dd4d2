"""The operations and bytes Xing4.0's serving path needs, from its sizes: the
numerators of the ``xing4.0-29b`` cell's roofline and utilization shares.
Kept with the benchmark, beside ``kernel_costs.py`` (whose ``min_seconds``
turns a cost into a least time), so that no PR that claims a gain can change
what 'needed' means.

``sizes`` is what the ``serve_mhc`` driver reads from the configuration
file: ``hidden``, ``heads``, ``q_lora``, ``kv_lora``, ``nope``, ``rope``,
``v_head`` (latent attention), ``dense_width``, ``expert_width``,
``experts_routed``, ``experts_held``, ``top_k``, ``vocab_held``, ``layers``,
``dense_layers``, ``sparse_layers``, ``hc_streams`` and ``hc_maps`` (the
residual path: ``n`` streams, ``n*n + 2n`` map entries a sublayer).
Weights, latent rows and activations at 2 bytes; the streams, the maps and
their parameters at 4 (float32, as the configuration states them).
"""

from __future__ import annotations

BYTES = 2           # bf16 weights, latent rows and activations
STREAM_BYTES = 4    # the streams, the maps and phi are float32


def attention_params(s: dict) -> int:
    """W_qa, W_qb, W_kva, W_kvb, W_o (the norms' scales are left out)."""
    qk = s["nope"] + s["rope"]
    return (s["hidden"] * s["q_lora"] + s["q_lora"] * s["heads"] * qk
            + s["hidden"] * (s["kv_lora"] + s["rope"])
            + s["kv_lora"] * s["heads"] * (s["nope"] + s["v_head"])
            + s["heads"] * s["v_head"] * s["hidden"])


def expert_params(s: dict) -> int:
    """One gated expert (routed or shared): gate, up, down."""
    return 3 * s["hidden"] * s["expert_width"]


def dense_mlp_params(s: dict) -> int:
    """A leading dense layer's gated MLP."""
    return 3 * s["hidden"] * s["dense_width"]


def head_params(s: dict) -> int:
    return s["hidden"] * s["vocab_held"]


def sublayers(s: dict) -> int:
    """Hyper-connection maps a token passes: two a layer."""
    return 2 * s["layers"]


def hc_params(s: dict) -> int:
    """One sublayer's ``phi`` (the three scalars and the biases are left
    out): ``n*C`` rows of ``n*n + 2n``, float32."""
    return s["hc_streams"] * s["hidden"] * s["hc_maps"]


def always_params(s: dict) -> int:
    """The bf16 parameters every token passes through whatever it is routed
    to: every layer's attention, the dense layers' MLP, the sparse layers'
    shared expert and router, the head."""
    return (s["layers"] * attention_params(s)
            + s["dense_layers"] * dense_mlp_params(s)
            + s["sparse_layers"] * (expert_params(s)
                                    + s["hidden"] * s["experts_routed"])
            + head_params(s))


def latent_row_bytes(s: dict) -> int:
    """A token's cached latent row in one layer: ``kv_lora + rope`` values
    (stored padded to whole 128-lane tiles; the pad is not work)."""
    return (s["kv_lora"] + s["rope"]) * BYTES


def stream_bytes(s: dict) -> int:
    """A token's streams: ``n`` x hidden float32 (57,344 B at 4 x 3,584)."""
    return s["hc_streams"] * s["hidden"] * STREAM_BYTES


def mhc_call(kind: str, tokens: float, s: dict) -> dict:
    """One call of one of the two kernels over ``tokens`` tokens.
    ``pre``: the streams read once, ``u`` (hidden float32) and the maps'
    ``n*n + 2n`` values written, ``phi`` read once; two operations a
    ``phi`` entry a token, and the ``H_pre`` mix. ``post``: the streams read
    and written once, ``y`` and the maps read once; the ``H_res`` mix and
    the ``H_post`` write. Either is bound by the streams' bytes (0.1 to 3
    operations a byte against 240 at the ridge)."""
    n, c, maps = s["hc_streams"], s["hidden"], s["hc_maps"]
    small = (c + maps) * STREAM_BYTES           # u or y, and the maps
    if kind == "pre":
        return {"bytes": tokens * (stream_bytes(s) + small)
                + hc_params(s) * STREAM_BYTES,
                "flops": tokens * (2 * n * c * maps + 2 * n * c)}
    if kind == "post":
        return {"bytes": tokens * (2 * stream_bytes(s) + small),
                "flops": tokens * 2 * n * (n + 1) * c}
    raise ValueError(f"kind {kind!r} is neither 'pre' nor 'post'")


def moe_experts(touched_experts: float, pairs: float, s: dict) -> dict:
    """One call of the routed-expert layer, one sparse layer: the weights
    of every held expert with at least one token read once, each
    token-expert pair's row read and its result written once, a pair's
    three matmuls. A decode step's 128 pairs over ~55 experts are 2
    operations a byte, a 1,024-token chunk's 4,096 over 64 are 64, against
    240 at the ridge: bandwidth-bound either way."""
    return {"bytes": (touched_experts * expert_params(s)
                      + pairs * 2 * s["hidden"]) * BYTES,
            "flops": pairs * 2 * expert_params(s)}


def latent_decode(resident_tokens: float, rows: float, s: dict) -> dict:
    """One absorbed decode-attention call, one layer: ``rows`` queries
    against ``resident_tokens`` cached latent rows in all. Each latent row
    is read once whatever the number of heads that share it; a row's
    absorbed query (heads x (kv_lora + rope)) is read and its latent
    output (heads x kv_lora) written once. Two operations a cached value
    for the scores and two a kv_lora value for the output, in each head:
    68 operations a byte, bandwidth-bound."""
    width = s["kv_lora"] + s["rope"]
    return {"bytes": resident_tokens * latent_row_bytes(s)
            + rows * s["heads"] * (width + s["kv_lora"]) * BYTES,
            "flops": 2 * resident_tokens * s["heads"]
            * (width + s["kv_lora"])}


def mhc_flops_per_token(s: dict) -> float:
    """Both kernels on every sublayer."""
    return sublayers(s) * (mhc_call("pre", 1, s)["flops"]
                           + mhc_call("post", 1, s)["flops"])


def decode_step_bytes(touched_experts: float, resident_tokens: float,
                      rows: float, s: dict) -> float:
    """Bytes one decode step must move: the weights every token passes
    through, the held experts with at least one token (``touched_experts``
    summed over the sparse layers), every resident latent row in every
    layer, each sublayer's ``phi``, and each active row's streams read
    twice and written once a sublayer."""
    return (BYTES * (always_params(s) + touched_experts * expert_params(s))
            + s["layers"] * resident_tokens * latent_row_bytes(s)
            + sublayers(s) * (hc_params(s) * STREAM_BYTES
                              + rows * 3 * stream_bytes(s)))


def decode_flops_per_token(held_pairs_per_token: float, mean_context: float,
                           s: dict) -> float:
    """Operations one OUTPUT token needs: two per parameter it passes
    through (``held_pairs_per_token`` routed experts in all, summed over
    the sparse layers), attention over its context in the latent space
    (absorbed: scores over ``kv_lora + rope``, values over ``kv_lora``) on
    every layer, and the residual path's maps and mixes."""
    width = s["kv_lora"] + s["rope"]
    attend = (2 * s["heads"] * (width + s["kv_lora"]) * mean_context
              * s["layers"])
    return (2 * (always_params(s) + held_pairs_per_token * expert_params(s))
            + attend + mhc_flops_per_token(s))


def prefill_flops(tokens: float, context_sum: float, pairs_per_token: float,
                  s: dict) -> float:
    """Operations ``tokens`` uncached PROMPT tokens need, whose contexts
    (the keys each attends, itself included) sum to ``context_sum``: the
    same parameters but the head (a prompt's last token alone reads it:
    left out), ``pairs_per_token`` routed experts in all, attention
    expanded (a key's expansion is its pass through W_kvb, a parameter;
    scores over ``nope + rope`` and values over ``v_head`` a head), and the
    residual path."""
    per_token = (2 * (always_params(s) - head_params(s)
                      + pairs_per_token * expert_params(s))
                 + mhc_flops_per_token(s))
    attend = (2 * s["heads"] * (s["nope"] + s["rope"] + s["v_head"])
              * context_sum * s["layers"])
    return tokens * per_token + attend
