"""The operations and bytes Mistral-Small-4's serving step needs, from its
sizes: the numerators of the new cell's roofline and utilization shares.
Kept with the benchmark, beside ``kernel_costs.py`` (whose ``min_seconds``
turns a cost into a least time), so that no PR that claims a gain can
change what 'needed' means.

``sizes`` is what the ``serve_lm`` driver reads from the configuration
file: ``hidden``, ``heads``, ``q_lora``, ``kv_lora``, ``nope``, ``rope``,
``v_head``, ``expert_width``, ``experts_routed``, ``experts_held``,
``top_k``, ``vocab_held``, ``layers``; weights and cache at 2 bytes.
"""

from __future__ import annotations

BYTES = 2   # bf16 weights, cache rows and activations


def attention_params(s: dict) -> int:
    """W_qa, W_qb, W_kva, W_kvb, W_o (the norms' scales are left out)."""
    qk = s["nope"] + s["rope"]
    return (s["hidden"] * s["q_lora"] + s["q_lora"] * s["heads"] * qk
            + s["hidden"] * (s["kv_lora"] + s["rope"])
            + s["kv_lora"] * s["heads"] * (s["nope"] + s["v_head"])
            + s["heads"] * s["v_head"] * s["hidden"])


def expert_params(s: dict) -> int:
    """One gated expert: gate, up, down."""
    return 3 * s["hidden"] * s["expert_width"]


def dense_layer_params(s: dict) -> int:
    """What every token passes through in a layer whatever it is routed
    to: attention, the shared expert, the router."""
    return (attention_params(s) + expert_params(s)
            + s["hidden"] * s["experts_routed"])


def head_params(s: dict) -> int:
    return s["hidden"] * s["vocab_held"]


def mla_decode(resident_tokens: int, rows: int, s: dict) -> dict:
    """One absorbed decode-attention call over a batch: ``rows`` queries
    against ``resident_tokens`` cached latent rows in all (summed over
    the rows). Each latent row (kv_lora + rope values) is read once; a
    row's query (heads x (kv_lora + rope)) is read and its latent output
    (heads x kv_lora) written once. Two operations per cached value for
    the scores and two per kv_lora value for the output, in each head."""
    width = s["kv_lora"] + s["rope"]
    return {"bytes": (resident_tokens * width
                      + rows * s["heads"] * (width + s["kv_lora"])) * BYTES,
            "flops": 2 * resident_tokens * s["heads"]
            * (width + s["kv_lora"])}


def moe_experts(touched_experts: float, pairs: float, s: dict) -> dict:
    """One call of the routed-expert layer: the weights of every held
    expert that has at least one token are read once, each token-expert
    pair's row is read and its result written once, and a pair costs the
    expert's three matmuls."""
    return {"bytes": (touched_experts * expert_params(s)
                      + pairs * 2 * s["hidden"]) * BYTES,
            "flops": pairs * 2 * expert_params(s)}


def decode_step_bytes(touched_experts: float, resident_tokens: float,
                      s: dict) -> float:
    """Bytes one decode step must read: every layer's dense weights, the
    held experts with at least one token (``touched_experts`` summed over
    the layers), every resident latent row in every layer, the head."""
    return BYTES * (s["layers"] * dense_layer_params(s)
                    + touched_experts * expert_params(s)
                    + s["layers"] * resident_tokens
                    * (s["kv_lora"] + s["rope"])
                    + head_params(s))


def serve_flops_per_token(held_pairs_per_token: float, mean_context: float,
                          s: dict) -> float:
    """Operations one output token needs through this chip's share: two
    per parameter it passes through (``held_pairs_per_token`` routed
    experts a layer, of the top-k chosen) and the attention over its
    context in the latent space."""
    width = s["kv_lora"] + s["rope"]
    per_layer = (2 * (dense_layer_params(s)
                      + held_pairs_per_token * expert_params(s))
                 + 2 * s["heads"] * (width + s["kv_lora"]) * mean_context)
    return s["layers"] * per_layer + 2 * head_params(s)
