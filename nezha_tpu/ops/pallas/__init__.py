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
- `latent_decode_attention`: the paged decode kernel over ONE pool of
  latent rows (multi-head latent attention, absorbed).
- `kda_decode`: the one-pass state update of a gated delta-rule linear
  attention layer (`kda.py`, with its chunked and recurrent forms).
- `moe_experts`: the routed experts of a dropless layer as one grouped
  matmul (`nezha_moe_experts`: gate, up, SiLU and down over the pair rows
  sorted by held expert; a touched expert's weights cross HBM once a call,
  in `d_ff` tiles; row tile, `d_ff` tile and window from the static shapes
  by `tile_sizes`; float32 accumulation, `h` rounded to the compute dtype
  before the down projection).
- `mhc_pre` / `mhc_post`: the two halves of a manifold-constrained
  hyper-connection round a sublayer (`mhc.py`, `nezha_mhc_pre` /
  `nezha_mhc_post`: a token's streams read once for its three maps, with
  Sinkhorn's rounds in registers, and the sublayer's input; then the
  streams rewritten in place).
- `fused_layer_norm`: single-pass normalization on VMEM rows.

The shared online-softmax scratch core lives in `common.py`. All kernels
run in interpret mode on CPU (tests) and compile on TPU.
"""

from nezha_tpu.ops.pallas.decode_attention import (
    flash_decode_attention,
    flash_decode_attention_sharded,
    latent_attention_composed,
    latent_decode_attention,
    paged_attention_composed,
    ring_entries,
)
from nezha_tpu.ops.pallas.kda import (
    kda_chunked,
    kda_conv_step,
    kda_conv_step_reference,
    kda_decode,
    kda_decode_reference,
    kda_recurrent,
)
from nezha_tpu.ops.pallas.flash_attention import flash_attention
from nezha_tpu.ops.pallas.layer_norm import fused_layer_norm
from nezha_tpu.ops.pallas.mhc import mhc_post, mhc_pre
from nezha_tpu.ops.pallas.moe_experts import (
    moe_experts,
    moe_experts_reference,
)
from nezha_tpu.ops.pallas.prefill_attention import (
    flash_prefill_attention,
    flash_prefill_attention_sharded,
)

__all__ = ["flash_attention", "flash_decode_attention",
           "flash_decode_attention_sharded", "flash_prefill_attention",
           "flash_prefill_attention_sharded", "fused_layer_norm",
           "kda_chunked", "kda_conv_step", "kda_conv_step_reference",
           "kda_decode", "kda_decode_reference",
           "kda_recurrent", "latent_attention_composed",
           "latent_decode_attention", "mhc_post", "mhc_pre", "moe_experts",
           "moe_experts_reference", "paged_attention_composed",
           "ring_entries"]
