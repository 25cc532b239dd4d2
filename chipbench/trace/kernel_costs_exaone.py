"""The operations and bytes K-EXAONE's serving step needs, from its sizes:
the numerators of the ``k-exaone-236b`` cell's roofline and utilization
shares. Kept with the benchmark, beside ``kernel_costs.py`` (whose
``min_seconds`` turns a cost into a least time), so that no PR that claims
a gain can change what 'needed' means.

``sizes`` is what the ``serve_hybrid`` driver reads from the configuration
file: ``hidden``, ``heads``, ``kv_heads``, ``head_dim``, ``window``,
``dense_width``, ``expert_width``, ``experts_routed``, ``experts_held``,
``top_k``, ``vocab_held``, ``layers``, ``dense_layers``, ``sparse_layers``,
``global_layers``, ``window_layers``; weights and cache at 2 bytes.
"""

from __future__ import annotations

BYTES = 2   # bf16 weights, cache rows and activations


def attention_params(s: dict) -> int:
    """W_q, W_o (heads x head_dim wide) and W_k, W_v (kv_heads x head_dim);
    the norms' scales are left out."""
    return 2 * s["hidden"] * s["head_dim"] * (s["heads"] + s["kv_heads"])


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
    layer's attention, the dense layers' MLP, the sparse layers' shared
    expert and router, the head."""
    return (s["layers"] * attention_params(s)
            + s["dense_layers"] * dense_mlp_params(s)
            + s["sparse_layers"] * (expert_params(s)
                                    + s["hidden"] * s["experts_routed"])
            + head_params(s))


def kv_row_bytes(s: dict) -> int:
    """A token's K and V in one layer."""
    return 2 * s["kv_heads"] * s["head_dim"] * BYTES


def gqa_decode(visible_tokens: float, rows: float, s: dict) -> dict:
    """One decode-attention call over a batch: ``rows`` queries against
    ``visible_tokens`` cached positions in all (summed over the rows: a
    row's whole context on a global layer, ``min(context, window)`` on a
    window layer). Each visible K and V row (kv_heads x head_dim) is read
    once, whatever the number of query heads that share it; a row's query
    (heads x head_dim) is read and its output written once. Two
    operations per K element and query head for the scores, two per V
    element for the output."""
    return {"bytes": visible_tokens * kv_row_bytes(s)
            + rows * 2 * s["heads"] * s["head_dim"] * BYTES,
            "flops": 4 * visible_tokens * s["heads"] * s["head_dim"]}


def decode_step_bytes(touched_experts: float, resident_tokens: float,
                      window_tokens: float, s: dict) -> float:
    """Bytes one decode step must read: the weights every token passes
    through, the held experts with at least one token (``touched_experts``
    summed over the sparse layers), every resident K/V row in each global
    layer and the rows inside the window (``window_tokens``: the sum over
    the rows of ``min(context, window)``) in each window layer."""
    return (BYTES * (always_params(s) + touched_experts * expert_params(s))
            + kv_row_bytes(s) * (s["global_layers"] * resident_tokens
                                 + s["window_layers"] * window_tokens))


def serve_flops_per_token(held_pairs_per_token: float, mean_context: float,
                          mean_window_context: float, s: dict) -> float:
    """Operations one output token needs through this chip's share: two
    per parameter it passes through (``held_pairs_per_token`` routed
    experts in all, summed over the sparse layers) and the attention over
    its whole context on the global layers and over ``min(context,
    window)`` on the window layers."""
    attend = 4 * s["heads"] * s["head_dim"] * (
        s["global_layers"] * mean_context
        + s["window_layers"] * mean_window_context)
    return 2 * (always_params(s)
                + held_pairs_per_token * expert_params(s)) + attend
