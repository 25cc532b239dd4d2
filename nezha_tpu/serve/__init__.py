"""Continuous-batching inference: the serving layer on top of
``models/generate.py``'s compiled decode.

One-shot ``generate()`` decodes a whole batch in lockstep: every request
shares sampling params, and nothing can join or leave mid-flight. The
serve stack replaces the batch lifecycle with a slot lifecycle:

- ``slots``: the KV pools. Default is the BLOCK-PAGED layout
  (``PagedSlotPool``): per-layer ``[num_blocks, block_size, H*D]``
  buffers (lane-dense rows: a position's heads side by side), a host-side free list of ref-counted blocks, and per-slot
  block tables threaded into the compiled programs — admission binds
  only what the prompt needs, decode binds lazily as positions
  advance, and a prefix-reuse trie lets a request whose prompt prefix
  matches cached blocks take REFERENCES instead of re-prefilling
  (copy-on-write protects shared blocks; exhaustion is typed
  backpressure, never a crash). ``ServeConfig.kv_dtype="int8"`` stores
  blocks as int8 with per-(block, head) fp32 absmax scales (the shared
  ``ops/quant.py`` core): ~2x resident requests at the same device
  budget, with the dequant fused into the flash-decode kernel's block
  loop.
- ``sampling``: per-row temperature / top-k / top-p as traced arrays, so
  one compiled program serves every mix of requests (top-k masks by
  per-row k under a static ``k_max`` cap — ``lax.top_k``'s k is static).
- ``engine``: exactly ``1 + len(prefill_buckets)`` jitted programs,
  reused forever — one prefill program per static prompt-pad bucket
  (prompts pick the smallest bucket that fits; prompts longer than
  ``max_prefill_len`` prefill in successive chunks through the same
  programs at traced offsets) and the batched decode step over all
  ``B_max`` rows. The step is a DEVICE-RESIDENT sampling loop: a
  ``lax.scan`` of ``ServeConfig.decode_horizon`` single-token steps in
  one compiled program (sampled tokens feed the next step's embedding
  without visiting the host; per-row EOS ids and new-token budgets are
  engine state, so completion flips a carried ``done`` mask mid-block
  and the row stops sampling and writing K/V), returning a ``[B, H]``
  token block + per-row emitted counts — the per-token host dispatch +
  sync cost shrinks by the horizon. On TPU the attention per scan step
  is the Pallas flash-decode kernel — per-row lengths skip KV blocks
  instead of masking them, and the emit mask zeroes finished rows'
  lengths. All programs route through the runtime ``CompileCache``, so
  the frozen-program steady state is provable from the
  ``compile_cache.*`` obs counters. ``ServeConfig.speculative`` grows
  the step into a fused draft→verify→accept loop: a cheap draft model
  (an early-exit slice of the target, or a separate checkpoint)
  proposes ``draft_k`` tokens per window, ONE batched target forward
  scores them all, and the longest agreeing prefix is emitted — up to
  ``decode_horizon * (draft_k + 1)`` tokens per dispatch at unchanged
  outputs (greedy bit-identical; sampled via lossless rejection
  sampling), the draft's KV mirroring the target pool's slot
  lifecycle.
  ``Engine.step`` has two forms. A direct caller gets
  launch-then-collect: the call returns the block it launched. The
  scheduler, the one caller that can hand a block's tokens to whoever
  held its rows a call earlier, asks for ONE BLOCK IN FLIGHT
  (``Engine.overlap_blocks``): a call launches block k first and then
  collects block k-1, so the host's work of a pass runs while the
  device runs a block (the speculative step, whose window width the
  host cannot know ahead, stays launch-then-collect).
  ``Engine.settle()`` brings the block in flight home without
  launching another, for whoever is about to read or move a live row's
  state.
- ``scheduler``: bounded FIFO admission with backpressure, per-request
  deadlines, and the iteration loop. A pass, in order: admit waiters ->
  LAUNCH block k for the rows held now -> COLLECT block k-1 -> emit its
  tokens to the requests that held its rows at its launch -> retire on
  EOS / max-new-tokens / deadline -> admit into the freed slots
  (retire/admit and deadline checks run once per horizon; a token
  reaches its client one block after it was sampled). Whatever reads
  or moves a live row's state outside that order (preemption, the
  migration calls, a drain, a block-exhaustion victim, the retry after
  a failed call) settles first. Failure is request-scoped: a prefill exception or
  NaN/inf logit burst retires only the affected request
  (``FinishReason.ERROR``) while the batch keeps decoding, and a step
  crash gets one bounded retry — provable on demand through the
  ``nezha_tpu.faults`` injection layer. Fully instrumented through
  ``nezha_tpu.obs`` (serve.ttft_s / serve.tpot_s histograms,
  queue-depth and batch-occupancy gauges,
  admitted/rejected/retired/errors counters).

Scale-out rides on top of the single-replica stack rather than inside
it:

- ``supervisor``: spawn N replicas (each the whole stack above on its
  own port — subprocess or in-process-thread backed), restart crashed
  ones with capped seeded backoff and a circuit breaker, and perform
  the rolling drain (replicas stop one at a time, so capacity never
  hits zero mid-drain).
- ``router``: the HTTP front end over those replicas — /healthz
  probing with K-miss ejection and readmission, least-loaded routing,
  queue-full 503 only when EVERY live replica is full, and bounded
  seeded-backoff failover for replicas that die before their response
  begins (a committed stream is never retried — typed error instead).

- ``sharded`` (imported on demand, not at package import): the SECOND
  scale axis — ``ShardedEngine`` spreads one replica over an M-device
  1xM ``tp`` mesh (params Megatron-sharded, paged K/V head-sharded,
  pool bookkeeping host-side and unchanged, the frozen program set
  traced under the GSPMD auto-partitioner with pinned output
  shardings), and ``reshard`` streams a training-topology checkpoint
  into the serve layout with per-leaf CRC verification
  (``nezha-reshard``; typed ``ReshardError`` = refuse to start).
  ``--replicas N --mesh M`` composes: N routed replicas x M-device
  meshes.

``nezha-serve`` (cli/serve.py) fronts the scheduler with stdio-JSONL and
stdlib-http modes (``--replicas N`` puts the router/supervisor pair in
front of N worker processes, ``--mesh M`` makes each worker an M-device
tensor-parallel engine); ``benchmarks/serving.py`` load-tests it
into the same run-dir telemetry artifacts training writes
(``--replicas/--kill-rate`` chaos-loads the router, ``--mesh`` runs the
single-replica loops sharded).
"""

from nezha_tpu.serve.engine import (Engine, ServeConfig,
                                    SpeculativeConfig, self_draft)
from nezha_tpu.serve.migrate import MigrationError
from nezha_tpu.serve.router import Router, register_router_instruments
from nezha_tpu.serve.sampling import sample_tokens
from nezha_tpu.serve.scheduler import (
    PRIORITIES,
    FinishReason,
    QueueFull,
    Request,
    RequestResult,
    Scheduler,
    TenantOverLimit,
)
from nezha_tpu.serve.slots import (KVBlocksExhausted, PagedSlotPool,
                                   PrefixTrie)
from nezha_tpu.serve.supervisor import (
    ProcessBackend,
    RouterConfig,
    Supervisor,
    ThreadBackend,
)

__all__ = [
    "Engine", "ServeConfig", "SpeculativeConfig", "self_draft",
    "PagedSlotPool", "PrefixTrie",
    "KVBlocksExhausted", "sample_tokens",
    "Scheduler", "Request", "RequestResult", "QueueFull",
    "TenantOverLimit", "PRIORITIES", "FinishReason",
    "Router", "RouterConfig", "Supervisor", "ProcessBackend",
    "ThreadBackend", "register_router_instruments", "MigrationError",
]
