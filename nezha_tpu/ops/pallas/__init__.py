"""Pallas TPU kernels for the hot fused ops.

The reference's equivalent layer is its custom CUDA kernels (softmax,
layernorm, fused elementwise — SURVEY.md §2 `pkg/cuda`). Here the hot ops
are Mosaic/Pallas kernels tiled for MXU/VPU and VMEM:

- `flash_attention`: blockwise attention, online softmax, O(S) memory.
- `flash_decode_attention`: split-K single-token decode attention over a
  pooled KV cache — per-row lengths skip KV blocks instead of masking
  them (the serving hot path).
- `flash_prefill_attention`: chunked prefill attention through the block
  table, with the int8 block write fused into the kernel epilogue (the
  TTFT hot path).
- `fused_layer_norm`: single-pass normalization on VMEM rows.

The shared online-softmax scratch core lives in `common.py`. All kernels
run in interpret mode on CPU (tests) and compile on TPU.
"""

from nezha_tpu.ops.pallas.decode_attention import (
    flash_decode_attention,
    flash_decode_attention_sharded,
    paged_attention_composed,
    ring_entries,
)
from nezha_tpu.ops.pallas.flash_attention import flash_attention
from nezha_tpu.ops.pallas.layer_norm import fused_layer_norm
from nezha_tpu.ops.pallas.prefill_attention import (
    flash_prefill_attention,
    flash_prefill_attention_sharded,
)

__all__ = ["flash_attention", "flash_decode_attention",
           "flash_decode_attention_sharded", "flash_prefill_attention",
           "flash_prefill_attention_sharded", "fused_layer_norm",
           "paged_attention_composed", "ring_entries"]
