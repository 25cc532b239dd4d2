"""The operations and bytes Kimi-Linear's serving step needs, from its
sizes: the numerators of the ``kimi-linear-48b`` cell's roofline and
utilization shares. Kept with the benchmark, beside ``kernel_costs.py``
(whose ``min_seconds`` turns a cost into a least time), so that no PR that
claims a gain can change what 'needed' means.

``sizes`` is what the ``serve_state`` driver reads from the configuration
file: ``hidden``, ``heads``, ``kv_lora``, ``nope``, ``rope``, ``v_head``
(the latent layers), ``kda_heads``, ``kda_dim``, ``conv_kernel``,
``gate_rank`` (the linear-attention layers), ``dense_width``,
``expert_width``, ``experts_routed``, ``experts_held``, ``top_k``,
``vocab_held``, ``layers``, ``dense_layers``, ``sparse_layers``,
``kda_layers``, ``mla_layers``. Weights, latent rows, convolution tails
and activations at 2 bytes; the recurrent state at 4 (float32, as the
configuration states it).
"""

from __future__ import annotations

BYTES = 2         # bf16 weights, latent rows, tails and activations
STATE_BYTES = 4   # the KDA state is float32


def kda_params(s: dict) -> int:
    """W_q, W_k, W_v, W_o, the two low-rank gates, the write rate's
    projection and the three convolutions' filters (``A_log``, ``dt_bias``
    and the norm's scale are left out)."""
    inner = s["kda_heads"] * s["kda_dim"]
    return (4 * s["hidden"] * inner
            + 2 * (s["hidden"] * s["gate_rank"] + s["gate_rank"] * inner)
            + s["hidden"] * s["kda_heads"] + 3 * inner * s["conv_kernel"])


def mla_params(s: dict) -> int:
    """W_q (direct: no low-rank pair), W_kva, W_kvb, W_o."""
    qk = s["nope"] + s["rope"]
    return (s["hidden"] * s["heads"] * qk
            + s["hidden"] * (s["kv_lora"] + s["rope"])
            + s["kv_lora"] * s["heads"] * (s["nope"] + s["v_head"])
            + s["heads"] * s["v_head"] * s["hidden"])


def expert_params(s: dict) -> int:
    """One gated expert (routed or shared): gate, up, down."""
    return 3 * s["hidden"] * s["expert_width"]


def dense_mlp_params(s: dict) -> int:
    """The leading dense layer's gated MLP."""
    return 3 * s["hidden"] * s["dense_width"]


def head_params(s: dict) -> int:
    return s["hidden"] * s["vocab_held"]


def always_params(s: dict) -> int:
    """What every token passes through whatever it is routed to: every
    layer's mixer, the dense layers' MLP, the sparse layers' shared expert
    and router, the head."""
    return (s["kda_layers"] * kda_params(s) + s["mla_layers"] * mla_params(s)
            + s["dense_layers"] * dense_mlp_params(s)
            + s["sparse_layers"] * (expert_params(s)
                                    + s["hidden"] * s["experts_routed"])
            + head_params(s))


def state_bytes(s: dict) -> int:
    """One row's recurrent state in one KDA layer: heads x d_k x d_v."""
    return s["kda_heads"] * s["kda_dim"] ** 2 * STATE_BYTES


def tail_bytes(s: dict) -> int:
    """One row's convolution tails in one KDA layer: the last ``kernel -
    1`` inputs of the q, k and v convolutions."""
    return ((s["conv_kernel"] - 1) * 3 * s["kda_heads"] * s["kda_dim"]
            * BYTES)


def latent_row_bytes(s: dict) -> int:
    """A token's cached latent row in one MLA layer: ``kv_lora + rope``
    values (the row is stored padded to whole 128-lane tiles; the pad is
    not work)."""
    return (s["kv_lora"] + s["rope"]) * BYTES


def kda_decode(rows: float, s: dict) -> dict:
    """One single-token state update over a batch, one layer: each of the
    ``rows`` active rows' states is read once and written once, whatever
    implements the update; beside it a row's q, k, v, decay (heads x dim
    float32 each), write rate (heads) in and its output (heads x dim)
    out. Per state element: the decay (1), ``S'^T k`` (2), the rank-one
    update (2), ``S^T q`` (2). 1.75 operations a byte against 240 at the
    ridge: bandwidth-bound."""
    inner = s["kda_heads"] * s["kda_dim"]
    return {"bytes": rows * (2 * state_bytes(s)
                             + (5 * inner + s["kda_heads"]) * STATE_BYTES),
            "flops": rows * 7 * s["kda_heads"] * s["kda_dim"] ** 2}


def latent_decode(resident_tokens: float, rows: float, s: dict) -> dict:
    """One absorbed decode-attention call over a batch, one layer:
    ``rows`` queries against ``resident_tokens`` cached latent rows in all
    (summed over the rows). Each latent row is read once, whatever the
    number of heads that share it; a row's absorbed query (heads x
    (kv_lora + rope)) is read and its latent output (heads x kv_lora)
    written once. Two operations per cached value for the scores and two
    per kv_lora value for the output, in each head."""
    width = s["kv_lora"] + s["rope"]
    return {"bytes": resident_tokens * latent_row_bytes(s)
            + rows * s["heads"] * (width + s["kv_lora"]) * BYTES,
            "flops": 2 * resident_tokens * s["heads"]
            * (width + s["kv_lora"])}


def moe_experts(touched_experts: float, pairs: float, s: dict) -> dict:
    """One call of the routed-expert layer, one layer: the weights of
    every held expert that has at least one token are read once, each
    token-expert pair's row is read and its result written once, and a
    pair costs the expert's three matmuls. At 8 pairs an expert 4 operations
    a byte against 240 at the ridge: bandwidth-bound."""
    return {"bytes": (touched_experts * expert_params(s)
                      + pairs * 2 * s["hidden"]) * BYTES,
            "flops": pairs * 2 * expert_params(s)}


def decode_step_bytes(touched_experts: float, resident_tokens: float,
                      rows: float, s: dict) -> float:
    """Bytes one decode step must move: the weights every token passes
    through, the held experts with at least one token (``touched_experts``
    summed over the sparse layers), every resident latent row in each MLA
    layer, and in each KDA layer every active row's state read once and
    written once and its convolution tails likewise."""
    return (BYTES * (always_params(s) + touched_experts * expert_params(s))
            + s["mla_layers"] * resident_tokens * latent_row_bytes(s)
            + s["kda_layers"] * rows * 2 * (state_bytes(s) + tail_bytes(s)))


def serve_flops_per_token(held_pairs_per_token: float, mean_context: float,
                          s: dict) -> float:
    """Operations one output token needs through this chip's share: two
    per parameter it passes through (``held_pairs_per_token`` routed
    experts in all, summed over the sparse layers), the state update on
    each KDA layer and the attention over its whole context, in the latent
    space, on each MLA layer."""
    width = s["kv_lora"] + s["rope"]
    attend = (2 * s["heads"] * (width + s["kv_lora"]) * mean_context
              * s["mla_layers"])
    update = 7 * s["kda_heads"] * s["kda_dim"] ** 2 * s["kda_layers"]
    return 2 * (always_params(s)
                + held_pairs_per_token * expert_params(s)) + attend + update
