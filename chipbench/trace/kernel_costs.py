"""The operations and bytes a kernel's call needs, from its shapes: the
numerator of a roofline share. Kept with the benchmark, so that no PR that
claims a gain can change what 'needed' means.

The least time a call can take is the larger of operations over peak
FLOP/s and bytes over peak bytes/s; a kernel's roofline share is that
time over its measured time, and the caller says which of the two bounds
it.
"""

from __future__ import annotations


def decode_attention(resident_tokens: int, rows: int, num_heads: int,
                     head_dim: int, kv_bytes: int = 2, io_bytes: int = 2
                     ) -> dict:
    """One decode-attention call over a batch: ``rows`` single-token
    queries against ``resident_tokens`` cached positions in all (summed
    over the rows; each row reads only its own context). Every resident K
    and V element is read once; q is read and the output written once.
    Two operations (multiply, add) per K element for the scores and per V
    element for the output. Bandwidth-bound: 1 op per byte at bf16."""
    kv = 2 * resident_tokens * num_heads * head_dim
    return {"bytes": kv * kv_bytes + 2 * rows * num_heads * head_dim * io_bytes,
            "flops": 2 * kv}


def transformer_train_flops_per_token(n_params: int, num_layers: int,
                                      hidden: int, seq: int) -> float:
    """Operations one token needs forward + backward: 6 per parameter plus
    the attention term 6*L*d*S (bench.py's formula: 12*L*d*S for full
    attention, halved for a causal mask; kept as one formula for both
    model families so MFU compares across cells — bidirectional BERT is
    under-counted by that half, which makes its MFU conservative)."""
    return 6.0 * n_params + 6.0 * num_layers * hidden * seq


def min_seconds(cost: dict, peaks: dict) -> dict:
    by_flops = cost["flops"] / peaks["bf16_flops"]
    by_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(by_flops, by_bytes),
            "bound": "compute" if by_flops >= by_bytes else "bandwidth"}
