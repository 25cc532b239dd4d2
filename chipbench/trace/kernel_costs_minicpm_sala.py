"""The operations and bytes MiniCPM-SALA's serving needs, from its sizes:
the numerators of the ``minicpm-sala`` cell's roofline and utilization
shares. Kept with the benchmark, beside ``kernel_costs.py`` (whose
``min_seconds`` turns a cost into a least time), so that no PR that claims
a gain can change what 'needed' means.

``sizes`` is what the ``serve_sala`` driver reads from the configuration
file: ``hidden``, ``heads``, ``kv_heads``, ``head_dim`` (the sparse
layer), ``lightning_heads``, ``lightning_head_dim``, ``width`` (the MLP),
``vocab_held``, ``layers``, ``sparse_layers``, ``lightning_layers`` and
the sparse sizes ``kernel``, ``stride``, ``block``, ``topk``,
``init_blocks``, ``window``, ``dense_len``. Weights, K/V rows and
activations at 2 bytes; the lightning state and the compressed keys at 4
(float32, as the configuration states them).

The functions follow the reader contract of ``readers/cost_*.py``: a
kernel's cost is ``f(rows, x, sizes) -> {"bytes", "flops"}`` with ``x`` the
quantity the reader's step list gives (resident tokens, or the blocks the
sparse layer's queries read); the serving shares read
:func:`decode_flops_per_token`, :func:`prefill_flops` and
:func:`decode_step_bytes`.
"""

from __future__ import annotations

BYTES = 2           # bf16 weights, K/V rows and activations
F32 = 4             # the lightning state and the compressed keys


def sparse_params(s: dict) -> int:
    """W_q, W_k, W_v, the output gate W_g, W_o and the two per-head norm
    scales."""
    q = s["heads"] * s["head_dim"]
    kv = s["kv_heads"] * s["head_dim"]
    return 3 * s["hidden"] * q + 2 * s["hidden"] * kv + 2 * s["head_dim"]


def lightning_params(s: dict) -> int:
    """W_q, W_k, W_v, W_g, W_o, the per-head norm scales of q and k and
    the output norm's scale."""
    inner = s["lightning_heads"] * s["lightning_head_dim"]
    return 5 * s["hidden"] * inner + 2 * s["lightning_head_dim"] + inner


def mlp_params(s: dict) -> int:
    return 3 * s["hidden"] * s["width"]


def trunk_params(s: dict) -> int:
    """Every layer: its mixer, the MLP and the two norms' scales."""
    per = mlp_params(s) + 2 * s["hidden"]
    return (s["sparse_layers"] * (sparse_params(s) + per)
            + s["lightning_layers"] * (lightning_params(s) + per))


def head_params(s: dict) -> int:
    return s["hidden"] * s["vocab_held"]


def state_bytes(s: dict) -> int:
    """One row's lightning state in one layer: heads x D x D float32."""
    return s["lightning_heads"] * s["lightning_head_dim"] ** 2 * F32


def selected_tokens(context: float, s: dict) -> float:
    """Tokens a decode query at visible length ``context`` attends a K/V
    head: all of them at or below ``dense_len``, else at most the init
    blocks, the window and ``topk`` blocks."""
    if context <= s["dense_len"]:
        return context
    return min(context, s["init_blocks"] * s["block"] + s["window"]
               + s["topk"] * s["block"])


def sparse_score(rows: float, resident_tokens: float, s: dict) -> dict:
    """One scoring call over a batch: every complete window's compressed
    key read once (float32, ``resident / stride`` a row and a K/V head),
    the last window's ``kernel`` keys, each row's query in and its window
    scores out; two operations a (query head, key dim) a window. Scale,
    softmax and sums are left out."""
    windows = resident_tokens / s["stride"]
    kvd = s["kv_heads"] * s["head_dim"]
    q = s["heads"] * s["head_dim"]
    return {"bytes": windows * kvd * F32 + rows * s["kernel"] * kvd * BYTES
            + rows * q * F32 + windows * s["kv_heads"] * F32,
            "flops": 2 * windows * q}


def sparse_decode(rows: float, blocks: float, s: dict) -> dict:
    """One block-sparse decode call over a batch: ``blocks`` selected
    blocks summed over the rows and K/V heads, each a K/V head's ``block``
    K and V rows read once; a row's query and output moved once; two
    operations a (query head, key dim) for the scores and two for the
    output, for the ``heads / kv_heads`` heads of a group."""
    group = s["heads"] // s["kv_heads"]
    keys = blocks * s["block"]
    q = s["heads"] * s["head_dim"]
    return {"bytes": keys * 2 * s["head_dim"] * BYTES + rows * 2 * q * BYTES,
            "flops": 4 * keys * group * s["head_dim"]}


def lightning_decode(rows: float, resident_tokens: float, s: dict) -> dict:
    """One single-token lightning update over a batch, one layer: each
    row's state read once and written once; its q, k, v in and y out,
    float32; per state element the decay (1), the rank-one write (2) and
    ``q S`` (2). (``resident_tokens`` is not read: a state does not grow
    with its context.)"""
    del resident_tokens
    inner = s["lightning_heads"] * s["lightning_head_dim"]
    return {"bytes": rows * (2 * state_bytes(s) + 4 * inner * F32),
            "flops": rows * 5 * inner * s["lightning_head_dim"]}


def decode_step_bytes(rows: float, resident_tokens: float, blocks: float,
                      s: dict) -> float:
    """Bytes one decode step must move: every layer's weights and the
    head once; each active row's lightning state read and written in
    every lightning layer; each sparse layer's compressed keys of every
    resident token's window and the K/V rows of the blocks its queries
    selected (``blocks`` summed over the rows, the K/V heads and the
    sparse layers)."""
    kvd = s["kv_heads"] * s["head_dim"]
    return (BYTES * (trunk_params(s) + head_params(s))
            + s["lightning_layers"] * rows * 2 * state_bytes(s)
            + s["sparse_layers"] * resident_tokens / s["stride"] * kvd * F32
            + blocks * s["block"] * 2 * s["head_dim"] * BYTES)


def decode_flops_per_token(mean_context: float, s: dict) -> float:
    """Operations one output token needs through this chip's share: two
    per parameter it passes through, the lightning update in every
    lightning layer, and in every sparse layer the scoring of its
    compressed keys and attention over the tokens it selects at the
    window's mean context."""
    q = s["heads"] * s["head_dim"]
    sparse = (2 * q * mean_context / s["stride"]
              + 4 * q * selected_tokens(mean_context, s))
    update = (5 * s["lightning_heads"] * s["lightning_head_dim"] ** 2
              * s["lightning_layers"])
    return (2 * (trunk_params(s) + head_params(s)) + update
            + s["sparse_layers"] * sparse)


def prefill_flops(tokens: float, context_sum: float, s: dict) -> float:
    """Operations a prefill of ``tokens`` prompt tokens needs, whose
    contexts sum to ``context_sum``: two per trunk parameter a token (the
    head is needed for a prompt's last token only, and left out), the
    lightning update a token, and attention over the tokens each query
    selects, at the tokens' mean context."""
    if not tokens:
        return 0.0
    q = s["heads"] * s["head_dim"]
    mean = context_sum / tokens
    return (tokens * (2 * trunk_params(s)
                      + 5 * s["lightning_heads"]
                      * s["lightning_head_dim"] ** 2 * s["lightning_layers"])
            + s["sparse_layers"] * tokens
            * (2 * q * mean / s["stride"] + 4 * q * selected_tokens(mean, s)))
