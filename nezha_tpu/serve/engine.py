"""The continuous-batching engine: a frozen set of programs, reused forever.

Steady-state serving is exactly ``1 + len(prefill_buckets)`` XLA
programs regardless of request mix — the property that keeps TPU serving
latency flat:

- **prefill** — one compiled program per PREFILL BUCKET (static prompt
  pad widths, default powers of two up to ``max_prefill_len``). A
  prompt's tokens are padded to the smallest bucket that fits and the
  chunk runs through the model at its TRACED position offset against
  the slot's block-table row: its K/V is scattered through the table
  into the shared block pools and it attends everything previously
  written to (or referenced by) the slot.
  Prompts longer than ``max_prefill_len`` are no longer rejected: they
  prefill in successive chunks — full ``max_prefill_len``-wide chunks,
  then a bucketed tail — reusing the same bucket programs at advancing
  offsets, so CHUNKING ADDS NO PROGRAMS. Bucket pads beyond the prompt
  write garbage K/V that is never attended (the masks stop at the
  written prefix, and decode overwrites pad positions before its mask
  reaches them). On TPU the chunk's attention is the paged
  flash-prefill kernel (ops/pallas/prefill_attention.py), which takes
  the traced offset as an operand; ``prefill_impl="xla"`` is the
  composed masked path over the gathered table row.
- **step** — one batched decode BLOCK over all ``B_max`` rows: a
  ``lax.scan`` of ``decode_horizon`` single-token steps, the whole
  horizon inside one compiled program. Each scan step samples per row
  from the carried last-logits (per-row traced temperature / top-k /
  top-p — serve/sampling.py), forwards through the model with PER-ROW
  cache positions (models/gpt2.py per-row pos path), and feeds the
  sampled token straight into the next step's embedding — tokens never
  visit the host mid-block, so the per-token Python→XLA dispatch +
  device→host sync cost is paid once per H tokens instead of once per
  token. Completion is decided ON DEVICE: per-row ``eos_ids`` and
  remaining-``budgets`` (engine state set at prefill) flip a carried
  ``done`` mask the moment a row emits EOS or exhausts its budget, and
  the carried ``ok`` health mask (NaN/inf tripwire, ANDed per scan
  step) freezes a poisoned row from the bad step on — either way the
  row stops sampling AND stops writing K/V for the rest of the block,
  because the per-step ``active ∧ ¬done ∧ ok`` emit mask is what
  threads into the model as ``active``. On TPU the attention inside
  each scan step is the Pallas flash-decode kernel
  (ops/pallas/decode_attention.py): per-row ``lengths`` skip KV blocks
  above each row's depth, and non-emitting rows (inactive slots, done
  rows, frozen rows) skip every block instead of computing masked
  garbage (host-side masking still applies — their state is frozen by
  ``where(emit, ...)``). The program returns a ``[B, H]`` token block
  plus per-row ``emitted`` counts; overshoot columns past a row's
  count are pad and never reach the client. ``decode_horizon=1``
  (default) runs the scan body once inline — bit-identical to the
  classic one-token step.

The KV cache is the block-paged pool (serve/slots.py): every program
takes the per-slot block tables as a static-shaped operand, uploaded
from the pool's host mirror each dispatch, and the model's cache path
scatters K/V through the table into shared block pools (prefix-hit
requests prefill only their un-cached
suffix, through the same bucket programs at a nonzero start offset;
lazy block binding and copy-on-write happen host-side BEFORE each
dispatch, so in-program writes always land in exclusively-owned
blocks, with non-emitting rows routed to the reserved scratch block).

All programs route through the runtime ``Executor`` (compile-cache keyed
on function identity + full arg shape signature), so the program-count
claim is enforced by the ``compile_cache.*`` obs counters: a shape drift
would show up as an extra miss, and tests pin the count at
``1 + len(prefill_buckets)`` with misses frozen after warmup (a bucket
program compiles the first time a prompt lands in its bucket).

**One decode block in flight.** Nothing the host does between two
blocks needs the first one's tokens: sampling, EOS, the budget and the
health mask are inside the step program, ``positions`` / ``keys`` /
``budgets`` / ``last_logits`` stay on the device from one block to the
next, and a prefill dispatches without a sync. The one sync of a pass is
the fetch of the block's tokens. So ``step`` has two halves,
:meth:`Engine._launch` (stage the tables and the mask, run the program,
rebind the engine's device state to its outputs) and
:meth:`Engine._collect` (the fetch), and two forms:

- *launch-then-collect*, what any direct caller gets: a call returns the
  block it launched;
- *overlapped*, after :meth:`Engine.overlap_blocks`: a call launches
  block k FIRST and then collects block k-1, so the host's turnaround of
  a pass (emit, retire, admit, bind, upload, launch) runs while the
  device runs a block instead of beside an idle one. The first call
  after a drain returns an all-pad block; a call whose rows have no
  budget left launches nothing and only collects;
  :meth:`Engine.settle` collects what is in flight without launching,
  for whoever is about to read or move a live row's state. Only a
  caller that can hand a block's tokens to whoever held its rows ONE
  CALL EARLIER can use this form: the :class:`Scheduler`, which asks
  for it at construction and gets it where the engine can state a row's
  write window before the block returns, the classic step at any
  ``decode_horizon`` (the speculative step stays launch-then-collect).

What makes the late block safe: the host mirrors advance at the LAUNCH
by what a row emits if nothing stops it (``min(horizon, budget)``, the
window just bound) and are corrected at the collect by the shortfall, so
block k's windows are bound from mirrors that include block k-1; a row
that emitted its EOS carries a budget of ZERO out of its block (``done``
starts from zeros in the next one), so the block launched before the
host read the EOS emits nothing for it and writes the scratch block, as
for a row whose budget ran out; device programs run in launch order, so a
freed slot's blocks may be bound again at once. Device state after k
launches is what it is after k launch-then-collect steps; only the
tokens' delivery is one call later.

All per-request scalars cross into the programs as 0-d ARRAYS, never
Python numbers — the executor's signature (and jax.jit's) would
otherwise key on the literal value and recompile per request.

Token-range validation lives in the scheduler's admission path
(``Scheduler.submit``), NOT here: the engine trusts its caller so the
per-prefill host work is one ``np.zeros`` + copy per chunk, and a bad
request is bounced before it ever holds a slot.
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from nezha_tpu import faults, obs
from nezha_tpu.runtime.executor import Executor
from nezha_tpu.serve.sampling import (accept_mask, categorical_rows,
                                      filter_logits_and_flag,
                                      finite_rows, residual_logits,
                                      sample_tokens_and_flag,
                                      split_and_sample, topk_candidates)
from nezha_tpu.serve.slots import (KVBlocksExhausted, PagedSlotPool,
                                   layer_parts)

# An engine's number within its process: what its decode passes' span
# records say (``engine``), so that the report can tell the passes of
# two replicas that share one registry apart (obs/report.py).
_ENGINE_IDS = itertools.count()


def default_prefill_buckets(max_prefill_len: int) -> Tuple[int, ...]:
    """Powers of two from 8 up to (and always ending exactly at)
    ``max_prefill_len`` — e.g. 32 -> (8, 16, 32), 24 -> (8, 16, 24),
    8 -> (8,). Small prompts pad to a small program instead of the full
    width, so short-prompt TTFT stops paying the long-prompt pad tax."""
    buckets: List[int] = []
    b = 8
    while b < max_prefill_len:
        buckets.append(b)
        b *= 2
    buckets.append(max_prefill_len)
    return tuple(buckets)


@dataclasses.dataclass(frozen=True)
class SpeculativeConfig:
    """Speculative-decoding knobs (``ServeConfig.speculative``).

    ``draft_k`` is the number of draft tokens proposed per verify
    window: one verify forward scores all ``draft_k + 1`` positions, so
    a window emits between 1 (every proposal rejected) and
    ``draft_k + 1`` tokens per verify while staying exactly the target
    model's output (greedy: bit-identical; sampled: the lossless
    rejection-sampling law). ``draft_layers`` selects SELF-DRAFTING:
    the draft model is the target's first N layers sharing the
    target's own weights (early-exit drafting — no second checkpoint),
    with ``None`` meaning full depth, an identity draft whose accept
    rate is ~1 (the machinery-overhead measurement point, and the
    bench's guaranteed->1-token-per-verify configuration). Both are
    ignored for the draft's ARCHITECTURE when an explicit
    ``draft_model`` is handed to :class:`Engine` (``draft_k`` still
    applies)."""

    draft_k: int = 4
    draft_layers: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Static serving shapes — everything a compiled program is keyed on.

    ``max_batch_size`` is the slot count (rows decoded per step),
    ``max_len`` the per-slot KV capacity (prompt + generated),
    ``max_prefill_len`` the widest single prefill chunk — longer prompts
    (up to ``max_len``) are prefilled in successive chunks, not
    rejected. ``prefill_buckets`` are the static prompt pad widths (one
    compiled prefill program each; ``()`` selects the powers-of-two
    default from :func:`default_prefill_buckets` — the last bucket must
    equal ``max_prefill_len``). ``k_max`` is the static top-k cap
    per-row ks are clamped to. ``queue_capacity`` bounds the scheduler's
    FIFO (backpressure); ``pad_id`` is the token fed for inactive rows.
    ``decode_impl`` (None = keep the model's own ``GPT2Config.
    decode_impl``) overrides the decode-attention choice for this
    engine: "auto" | "kernel" | "xla" — the serving-side toggle for the
    flash-decode kernel. ``decode_horizon`` is the number of tokens one
    compiled step program decodes per dispatch (the fused device-
    resident sampling loop): 1 (default) is the classic one-token step,
    bit-identical to pre-horizon behavior; H > 1 amortizes the
    per-dispatch host gap over H tokens at the cost of coarser
    deadline/drain granularity (one horizon) — EOS/budget completion
    moves on device, so a row finishing mid-block stops sampling and
    K/V writes immediately and its overshoot is dropped before the
    block reaches the host.
    """

    max_batch_size: int = 4
    max_len: int = 128
    max_prefill_len: int = 32
    prefill_buckets: Tuple[int, ...] = ()
    k_max: int = 64
    queue_capacity: int = 16
    pad_id: int = 0
    cache_dtype: Any = jnp.bfloat16
    decode_impl: Optional[str] = None
    # Paged prefill-chunk attention override (None = keep the model
    # config's prefill_impl): "auto" resolves the flash-prefill kernel
    # by backend, "kernel" forces it (interpret off-TPU — the parity
    # path; on int8 pools the block write fuses into the kernel
    # epilogue), "xla" forces the composed masked path.
    prefill_impl: Optional[str] = None
    # Long-context prefill (PR 20). prefill_mode="sequence" shards each
    # prefill chunk's attention over the serve mesh (ShardedEngine
    # only — the single-device engine rejects it): ulysses all-to-all
    # when H % M == 0 (bitwise parity with the replicated path) or
    # ppermute ring hops (serve/sharded/seq_prefill.py).
    # "replicated" is the pre-PR-20 path, bit for bit, and the way
    # back from sequence sharding (long buckets keep serving the same
    # prompts either way).
    prefill_mode: str = "replicated"
    # Extra static chunk widths ABOVE max_prefill_len (each >
    # max_prefill_len, <= max_len, strictly increasing): one more
    # compiled prefill program each, letting an 8k-32k document prompt
    # prefill in a handful of wide dispatches instead of hundreds of
    # max_prefill_len strides. () keeps the classic plan byte-for-byte.
    # Under prefill_mode="sequence" every bucket width (short AND long)
    # must divide by the mesh size.
    long_prefill_buckets: Tuple[int, ...] = ()
    # Sequence-sharding layout: "auto" (ulysses when H % M == 0, which
    # the sharded engine's head-divisibility requirement guarantees),
    # "ulysses", or "ring" (docs/RUNBOOK.md §8 selection table).
    seq_prefill_variant: str = "auto"
    decode_horizon: int = 1
    # The block-paged KV pool: per-layer
    # [kv_num_blocks, kv_block_size, H*D] buffers, ref-counted blocks
    # bound lazily as positions advance, per-slot block tables threaded
    # into the compiled programs, and (with prefix_cache) shared-prefix
    # prefill reuse. kv_num_blocks None = every slot can reach max_len
    # (1 scratch + max_batch_size * ceil(max_len/block_size));
    # smaller values make block budget (tokens actually resident) the
    # admission limit instead of slot count. kv_eviction governs what
    # happens when the free list runs dry: "lru" evicts prefix-cache
    # blocks held only by the trie, "none" goes straight to typed
    # backpressure (KVBlocksExhausted).
    kv_block_size: int = 16
    kv_num_blocks: Optional[int] = None
    prefix_cache: bool = True
    kv_eviction: str = "lru"
    # Host tier (0 = off): when kv_eviction="lru" reclaims a trie-only
    # block, demote its int8 payload + per-block scales into a
    # host-RAM LRU of up to this many blocks instead of discarding it;
    # a later trie hit whose blocks were demoted promotes them back
    # with an async host->device copy dispatched ahead of the bucketed
    # prefill, so a returning chat user pays one tail chunk instead of
    # a full cold prefill. Requires kv_dtype="int8"
    # (demotion moves the lossless wire-format bytes verbatim), and
    # prefix_cache — host RAM typically holds ~100x the device's
    # resident conversations at int8 (docs/RUNBOOK.md §8).
    kv_host_blocks: int = 0
    # KV storage dtype. "bf16" (default) stores blocks in cache_dtype —
    # bit-identical to the pre-quantization engine. "int8"
    # stores K/V blocks as int8 with one fp32 absmax
    # scale per (block, head) (ops/quant.py — the EQuARX recipe the
    # wire collectives already use): ~2x the resident blocks at the
    # same device budget (scale overhead 4/(block_size*D) per
    # element), at a bounded per-block dequant error the
    # serve.kv.quant_error histogram samples. The dequant is fused
    # into the flash-decode kernel's block loop (and applied
    # identically on the gathered XLA fallback), so int8 blocks never
    # round-trip through a bf16 copy of the cache.
    kv_dtype: str = "bf16"
    # Speculative decoding (None = off, bit-identical to the classic
    # horizon engine): a cheap DRAFT model proposes draft_k tokens per
    # window, one batched target forward verifies all draft_k + 1
    # positions, and an in-program accept mask emits the longest
    # agreeing prefix — so one step dispatch can emit up to
    # decode_horizon * (draft_k + 1) tokens while every emitted token
    # remains exactly the target model's (greedy bit-identical;
    # sampled via standard rejection sampling with a carried residual
    # distribution). The draft's KV lives in a mirrored pool of the
    # same paged machinery (int8 welcome); accepted tokens flow into
    # the existing block-consumption path as ordinary emits.
    speculative: Optional[SpeculativeConfig] = None
    # Multi-tenant scheduling (PR 19). priority_weights selects the
    # weighted-fair-queueing share of admission grants each priority
    # lane gets (virtual-time WFQ — lower-priority lanes are SLOWED,
    # never starved); None keeps the built-in 4:2:1
    # interactive:batch:background split. Accepts a mapping or a
    # ("name", weight) pair sequence; normalized to a canonical tuple.
    # With every request in one lane (the default — Request.priority
    # defaults to "interactive") WFQ degenerates to the exact bounded
    # FIFO of the pre-PR-19 scheduler, bit for bit.
    priority_weights: Optional[Any] = None
    # Per-tenant admission bound (None = off): a tenant with this many
    # requests already queued gets the typed TenantOverLimit
    # (subclass of QueueFull, so HTTP still answers 503) instead of
    # consuming the shared queue_capacity — one bursty tenant cannot
    # wedge the door shut for everyone else.
    tenant_queue_cap: Optional[int] = None
    # Preemption (off by default — bit-for-bit prior behavior): under
    # slot/block pressure (or a burning interactive SLO) the scheduler
    # suspends the lowest-priority running decode, indexes its bound
    # blocks into the prefix trie (re-promotable; eviction demotes
    # them through the host tier when one is configured) and resumes
    # it when pressure clears — admission degrades gracefully instead
    # of rejecting at the door. preemption_budget bounds how many
    # times one request may be preempted (anti-thrash).
    preemption: bool = False
    preemption_budget: int = 2

    @property
    def all_prefill_buckets(self) -> Tuple[int, ...]:
        """Every compiled prefill width, ascending: the classic buckets
        (<= max_prefill_len) followed by the long-context buckets. The
        frozen program contract counts these: steady state is
        ``1 step + len(all_prefill_buckets)`` programs per engine."""
        return tuple(self.prefill_buckets) + tuple(
            self.long_prefill_buckets)

    def __post_init__(self):
        if self.max_batch_size < 1:
            raise ValueError("max_batch_size must be >= 1")
        if self.kv_block_size < 1:
            raise ValueError(
                f"kv_block_size must be >= 1, got {self.kv_block_size}")
        if self.kv_num_blocks is not None and self.kv_num_blocks < 2:
            raise ValueError(
                f"kv_num_blocks must be >= 2 (block 0 is scratch), got "
                f"{self.kv_num_blocks}")
        if self.kv_eviction not in ("lru", "none"):
            raise ValueError(
                f"kv_eviction must be 'lru' or 'none', got "
                f"{self.kv_eviction!r}")
        if self.kv_dtype not in ("bf16", "int8"):
            raise ValueError(
                f"kv_dtype must be 'bf16' or 'int8', got "
                f"{self.kv_dtype!r}")
        if self.kv_host_blocks < 0:
            raise ValueError(
                f"kv_host_blocks must be >= 0, got "
                f"{self.kv_host_blocks}")
        if self.kv_host_blocks:
            if self.kv_dtype != "int8":
                raise ValueError(
                    "kv_host_blocks requires "
                    "kv_dtype='int8' — the host tier demotes the "
                    "int8+scales block payload verbatim (lossless); "
                    "a bf16 tier would serve quantize-dequant blocks "
                    "that differ from a fresh prefill")
            if not self.prefix_cache:
                raise ValueError(
                    "kv_host_blocks requires prefix_cache (demotion "
                    "feeds off trie eviction)")
            if self.kv_eviction != "lru":
                raise ValueError(
                    "kv_host_blocks requires kv_eviction='lru' "
                    "(demotion IS the eviction path; 'none' never "
                    "evicts, so the tier would be inert)")
        if self.decode_horizon < 1:
            raise ValueError(
                f"decode_horizon must be >= 1, got {self.decode_horizon}")
        if self.speculative is not None:
            spec = self.speculative
            if isinstance(spec, dict):
                # Convenience for argv/JSON config paths.
                spec = SpeculativeConfig(**spec)
                object.__setattr__(self, "speculative", spec)
            if spec.draft_k < 1:
                raise ValueError(
                    f"speculative.draft_k must be >= 1, got "
                    f"{spec.draft_k}")
            if spec.draft_layers is not None and spec.draft_layers < 1:
                raise ValueError(
                    f"speculative.draft_layers must be >= 1 or None, "
                    f"got {spec.draft_layers}")
        if not 1 <= self.max_prefill_len <= self.max_len:
            raise ValueError(
                f"need 1 <= max_prefill_len <= max_len, got "
                f"{self.max_prefill_len} / {self.max_len}")
        if self.k_max < 1:
            raise ValueError("k_max must be >= 1")
        if self.queue_capacity < 1:
            raise ValueError("queue_capacity must be >= 1")
        if self.decode_impl not in (None, "auto", "kernel", "xla"):
            raise ValueError(
                f"decode_impl must be None, 'auto', 'kernel', or 'xla'; "
                f"got {self.decode_impl!r}")
        if self.prefill_impl not in (None, "auto", "kernel", "xla"):
            raise ValueError(
                f"prefill_impl must be None, 'auto', 'kernel', or 'xla'; "
                f"got {self.prefill_impl!r}")
        buckets = tuple(self.prefill_buckets) or default_prefill_buckets(
            self.max_prefill_len)
        if list(buckets) != sorted(set(buckets)):
            raise ValueError(
                f"prefill_buckets must be strictly increasing, got "
                f"{buckets}")
        if buckets[0] < 1 or buckets[-1] != self.max_prefill_len:
            # The last bucket IS the chunk width: every admissible tail
            # must fit some bucket, and chunking advances in
            # max_prefill_len strides.
            raise ValueError(
                f"prefill_buckets must be >= 1 and end exactly at "
                f"max_prefill_len={self.max_prefill_len}, got {buckets}")
        object.__setattr__(self, "prefill_buckets", buckets)
        if self.prefill_mode not in ("replicated", "sequence"):
            raise ValueError(
                f"prefill_mode must be 'replicated' or 'sequence', got "
                f"{self.prefill_mode!r}")
        if self.seq_prefill_variant not in ("auto", "ulysses", "ring"):
            raise ValueError(
                f"seq_prefill_variant must be 'auto', 'ulysses', or "
                f"'ring', got {self.seq_prefill_variant!r}")
        lb = tuple(self.long_prefill_buckets)
        if lb:
            if list(lb) != sorted(set(lb)):
                raise ValueError(
                    f"long_prefill_buckets must be strictly increasing, "
                    f"got {lb}")
            if lb[0] <= self.max_prefill_len or lb[-1] > self.max_len:
                raise ValueError(
                    f"long_prefill_buckets must lie in "
                    f"(max_prefill_len={self.max_prefill_len}, "
                    f"max_len={self.max_len}], got {lb}")
        object.__setattr__(self, "long_prefill_buckets", lb)
        if self.tenant_queue_cap is not None and self.tenant_queue_cap < 1:
            raise ValueError(
                f"tenant_queue_cap must be >= 1 or None, got "
                f"{self.tenant_queue_cap}")
        if self.preemption_budget < 0:
            raise ValueError(
                f"preemption_budget must be >= 0, got "
                f"{self.preemption_budget}")
        if self.priority_weights is not None:
            pw = self.priority_weights
            pairs = list(pw.items()) if isinstance(pw, dict) else list(pw)
            try:
                norm = {str(name): int(w) for name, w in pairs}
            except (TypeError, ValueError):
                raise ValueError(
                    f"priority_weights must map priority names to "
                    f"integer weights, got {pw!r}")
            classes = ("interactive", "batch", "background")
            if set(norm) != set(classes):
                raise ValueError(
                    f"priority_weights must name exactly "
                    f"{classes}, got {sorted(norm)}")
            if any(w < 1 for w in norm.values()):
                raise ValueError(
                    f"priority_weights must all be >= 1, got {norm}")
            object.__setattr__(self, "priority_weights",
                               tuple((c, norm[c]) for c in classes))


def self_draft(model, variables, num_layers: Optional[int] = None):
    """Build an early-exit SELF-DRAFT from the target: the same
    architecture truncated to its first ``num_layers`` transformer
    blocks (None = full depth), SHARING the target's embedding / trunk
    / final-norm weights — the no-second-checkpoint draft source
    ROADMAP item 3 names. -> ``(draft_model, draft_variables)``; the
    variables dict references the target's own leaves (no copy).
    Draft quality only moves the ACCEPT RATE — every emitted token is
    verified against the target, so a bad draft costs speed, never
    correctness."""
    cfg = model.cfg
    layers = cfg.num_layers if num_layers is None else int(num_layers)
    if not 1 <= layers <= cfg.num_layers:
        raise ValueError(
            f"draft_layers must be in [1, {cfg.num_layers}], got "
            f"{layers}")
    draft = type(model)(dataclasses.replace(cfg, num_layers=layers),
                        policy=model.policy)
    params = variables["params"]
    if cfg.scan_layers:
        dparams = {k: v for k, v in params.items() if k != "h_scan"}
        dparams["h_scan"] = jax.tree_util.tree_map(
            lambda p: p[:layers], params["h_scan"])
    else:
        dparams = {}
        for key, val in params.items():
            if key.startswith("h") and key[1:].isdigit():
                if int(key[1:]) < layers:
                    dparams[key] = val
            else:
                dparams[key] = val
    return draft, {"params": dparams, "state": variables.get("state", {})}


@dataclasses.dataclass
class _Block:
    """A launched decode block, until it is collected: what the host
    keeps of the launch, and the device arrays its fetch will read."""

    seq: int                # its number among the engine's launches
    rows: int               # active rows at the launch
    # [B] bool, the rows whose host mirrors this block still answers
    # for: the launch's mask, less every slot prefilled since
    mirrored: np.ndarray
    bound: np.ndarray       # [B] what the mirrors advanced by at launch
    tok: Any
    emitted: Any
    ok: Any
    full_sorts: Any
    load: List[Any]         # (pairs, visits) of an expert layer, or []
    mhc: Any                # the residual scalar, or None


class Engine:
    """Device-side serving state + the frozen program set.

    The engine is deliberately request-blind: it knows slots, not
    requests. Admission policy, deadlines, retirement, and the
    request-level telemetry (TTFT/TPOT, queue depth, spans) live in the
    scheduler; the engine emits only what it alone can see — the
    bucket/chunk instruments (``serve.prefill.bucket_len`` /
    ``serve.prefill.chunks_total``), since the bucket choice is made
    here. The contract is ``prefill(slot, ...)`` to load one slot
    (however many chunks that takes — including the row's EOS id and
    new-token budget, which become device state) and ``step(active)``
    to decode one BLOCK of up to ``decode_horizon`` tokens for every
    row and hand a ``[B, H]`` batch back to the host along with
    per-row emitted counts: the block the call launched, or, for the
    caller that asked for one block in flight
    (:meth:`overlap_blocks`; module docstring), the block the call
    BEFORE it launched. ``step_calls`` counts host dispatches of the
    step program — the denominator of the dispatch-per-token
    amortization this engine exists to improve.
    """

    # Whether this engine class can serve prefill_mode="sequence".
    # Only the mesh-sharded engine can — sequence sharding needs a
    # multi-device "tp" axis to spread the chunk over.
    _seq_prefill_capable = False

    def __init__(self, model, variables, cfg: ServeConfig = ServeConfig(),
                 draft_model=None, draft_variables=None):
        if cfg.max_len > model.cfg.max_positions:
            raise ValueError(
                f"max_len {cfg.max_len} exceeds the model's max_positions "
                f"{model.cfg.max_positions}")
        self.engine_id = next(_ENGINE_IDS)
        if (cfg.prefill_mode == "sequence"
                and not self._seq_prefill_capable):
            raise ValueError(
                "prefill_mode='sequence' requires the mesh-sharded "
                "engine (nezha-serve --mesh M with M > 1) — the "
                "single-device engine has no sequence axis to shard "
                "over")
        # The decode/prefill attention choices are model-config knobs
        # (the attention module reads them at trace time); honor the
        # serving overrides by rebuilding the module tree around a
        # replaced config — pure structure, the caller's ``variables``
        # slot straight in.
        impl_overrides = {}
        if (cfg.decode_impl is not None
                and cfg.decode_impl != model.cfg.decode_impl):
            impl_overrides["decode_impl"] = cfg.decode_impl
        if (cfg.prefill_impl is not None
                and cfg.prefill_impl != getattr(model.cfg, "prefill_impl",
                                                None)):
            impl_overrides["prefill_impl"] = cfg.prefill_impl
        if any(not hasattr(model.cfg, k) for k in impl_overrides):
            raise ValueError(
                f"decode_impl / prefill_impl {impl_overrides} given, but "
                f"{type(model).__name__} has one attention implementation "
                f"per path and no such knob")
        if impl_overrides:
            model = type(model)(
                dataclasses.replace(model.cfg, **impl_overrides),
                policy=model.policy)
        self.model = model
        self.variables = variables
        self.cfg = cfg
        # The logits' width: the vocabulary rows this model holds (a
        # model cut to a chip's share holds a slice).
        self.vocab = getattr(model.cfg, "vocab_held", model.cfg.vocab_size)
        self.k_max = min(cfg.k_max, self.vocab)
        self.kv_quant = cfg.kv_dtype == "int8"
        # What the model caches a layer: per-head K/V (GPT-2), or another
        # leaf set (a latent row). Everything that is written for K/V
        # only refuses here, typed, instead of taking a wrong path: the
        # model's own declaration refuses an int8 pool; the draft pool
        # of speculative decoding and the mesh's head sharding derive
        # their shapes from K/V heads.
        layers = [part for layer in layer_parts(model.cache_leaves(
            cfg.kv_block_size, cfg.cache_dtype, self.kv_quant))
                  for part in layer]
        leaves = sorted({name for _, _, lv in layers for name in lv})
        groups = sorted({g for g, _, _ in layers})
        self.kv_heads_cache = all({"k", "v"} <= set(lv)
                                  for g, _, lv in layers if g == "global")
        # THE predicate: every layer in the growing table of per-head
        # K/V. A ring cannot take back a rejected token's write and a
        # state has already folded it in; neither has a head axis the
        # mesh's sharding could split as it splits K/V.
        if not (self.kv_heads_cache and groups == ["global"]):
            unsupported = [what for what, on in (
                ("speculative decoding", cfg.speculative is not None),
                # only the mesh-sharded engine sets this
                ("a device mesh (--mesh)", self._seq_prefill_capable))
                if on]
            if unsupported:
                raise ValueError(
                    f"{type(model).__name__} caches {leaves} in groups "
                    f"{groups}, not per-head K/V in one growing table: "
                    f"{', '.join(unsupported)} not supported with it")
        # Whether prefill chunks dispatch through the flash-prefill
        # kernel (the model's own resolution, asked once). It drives
        # telemetry only: the pinned ``serve.prefill.kernel_active`` gauge
        # lets dashboards and `nezha-telemetry` label the prefill line
        # with the active impl without scraping model config, and it
        # selects the kernel span / fused-write accounting in
        # :meth:`prefill`.
        self.prefill_kernel_active = bool(
            model.paged_prefill_uses_kernel())
        obs.gauge("serve.prefill.kernel_active").set(
            1.0 if self.prefill_kernel_active else 0.0)
        # The entries sampling's last top-k ranking takes a row: which
        # form of ``sampling.top_k_values`` the programs took (the
        # vocabulary where the head is ranked whole). Re-pinned with
        # the gauge above.
        self.topk_candidates = topk_candidates(self.vocab, self.k_max)
        obs.gauge("serve.sampling.topk_candidates").set(
            self.topk_candidates)
        self.pool = self._make_paged_pool(
            model, num_blocks=cfg.kv_num_blocks,
            prefix_cache=cfg.prefix_cache, eviction=cfg.kv_eviction,
            quantized=self.kv_quant,
            host_blocks=cfg.kv_host_blocks)
        # Host mirrors of each row's next write position and
        # remaining token budget (set at prefill, advanced/decayed
        # by the block's emitted count): the lazy block binder must
        # size the write window BEFORE a dispatch without a device
        # sync, and must not bind blocks a nearly-finished row can
        # never write.
        self.host_positions = np.zeros((cfg.max_batch_size,), np.int64)
        self.host_budgets = np.zeros((cfg.max_batch_size,), np.int64)
        b = cfg.max_batch_size
        self.last_logits = jnp.zeros((b, self.vocab), jnp.float32)
        # [B] bool from the latest step: False where that row's logits
        # (carried-in or freshly produced) went non-finite — the
        # scheduler's signal to retire the row with FinishReason.ERROR.
        self.step_ok: Optional[np.ndarray] = None
        self.positions = jnp.zeros((b,), jnp.int32)
        self.keys = jnp.zeros((b, 2), jnp.uint32)
        self.temps = jnp.zeros((b,), jnp.float32)
        self.top_ks = jnp.zeros((b,), jnp.int32)
        self.top_ps = jnp.ones((b,), jnp.float32)
        # On-device completion state, set per row at prefill: the EOS id
        # (-1 = none) and the remaining new-token budget. Inside a decode
        # block a row that emits its EOS or exhausts its budget flips the
        # scan's carried `done` mask and stops sampling + K/V writes for
        # the rest of the block — the host never sees overshoot.
        self.eos_ids = jnp.full((b,), -1, jnp.int32)
        self.budgets = jnp.zeros((b,), jnp.int32)
        # Host dispatches of the step program (1 dispatch = up to
        # decode_horizon tokens for every row) — tests assert the
        # dispatch-per-token amortization against this.
        self.step_calls = 0
        # One block in flight (module docstring): whether ``step``
        # returns the block BEFORE the one it launches (the scheduler
        # asks for it, :meth:`overlap_blocks`), the launched block not
        # collected yet, and the mechanism's three ledgers beside
        # ``step_calls`` (the obs counters of the same names only count
        # inside a run; these always do): blocks launched while another
        # was in flight, forced drains (:meth:`settle`), and row results
        # the scheduler dropped because the row's request had changed.
        self._overlap = False
        self._in_flight: Optional[_Block] = None
        self.blocks_overlapped = 0
        self.settles = 0
        self.stale_rows = 0
        # [layers, experts held] int32 from the latest step, on the host:
        # the token-expert pairs each held expert computed (models with
        # a serving-side expert layer; None otherwise). It rides the
        # step's existing ok / tok / emitted fetch.
        self.last_expert_load: Optional[np.ndarray] = None
        # A model whose residual path is mixed by maps declares how many
        # it computes a token (``mhc_sublayers``) and how far a pass's
        # worst H_res lay from doubly stochastic (``mhc_residual``): the
        # serve programs return that scalar last and this is its running
        # maximum (0.0 for any other model). A model with state-space
        # layers declares how many (``ssm_layers``): the ``serve.ssm.*``
        # counters' multiplier. Both are 0 for any other model.
        self._mhc_pending: List[Any] = []
        self.mhc_residual_max = 0.0
        # Tokens the most recent prefill's compiled chunks pushed
        # through the target model (set per prefill call), and how many
        # chunk dispatches it took (the sequence-sharded engine's
        # ring-hop accounting multiplies by this).
        self.last_prefill_tokens = 0
        self.last_prefill_chunks = 0
        # Donate the pooled caches (positional arg 1 in EVERY program):
        # without donation every decoded token would copy the whole
        # K/V pool per layer just to write one row —
        # double the KV memory and a full-pool bandwidth tax on the
        # latency-bound loop. The engine rebinds the returned buffers
        # immediately, so the invalidated inputs are never reused.
        self.executor = Executor(donate_argnums=(1,))
        # One prefill program per bucket width — long-context buckets
        # included (compiled lazily: the executor keys on the function
        # object, so each closure is its own cache entry the first time
        # a prompt lands in its bucket). Prefill programs route through the
        # dedicated _wrap_prefill_program hook: the sharded engine in
        # sequence mode nests the seq-prefill scope around the trace.
        groups = self.pool.layer_groups
        self._prefill_fns = {w: self._wrap_prefill_program(
                                    _build_prefill(self.model, w,
                                                   quantized=self.kv_quant,
                                                   groups=groups))
                             for w in cfg.all_prefill_buckets}
        # Speculative decoding: a DRAFT engine rides along — its own
        # model (explicit, or an early-exit self-draft sharing the
        # target's weights), its own KV pool MIRRORING the target
        # pool's slot lifecycle (same paged machinery, int8 included),
        # its own executor for the bucket prefill programs. The draft's
        # decode never dispatches separately: it lives inside the ONE
        # fused draft→verify→accept step program, so the frozen
        # program-count contract is counted per engine — target:
        # 1 step + len(prefill_buckets); draft: len(prefill_buckets).
        self.spec = cfg.speculative
        self.draft_model = None
        self.draft_variables = None
        self.draft_pool = None
        self.draft_executor = None
        if self.spec is not None:
            if draft_model is not None:
                dm, dv = draft_model, draft_variables
                if dv is None:
                    raise ValueError(
                        "draft_model requires draft_variables")
                if (cfg.decode_impl is not None
                        and cfg.decode_impl != dm.cfg.decode_impl):
                    dm = type(dm)(
                        dataclasses.replace(dm.cfg,
                                            decode_impl=cfg.decode_impl),
                        policy=dm.policy)
            else:
                dm, dv = self_draft(self.model, self.variables,
                                    self.spec.draft_layers)
            if dm.cfg.vocab_size != self.model.cfg.vocab_size:
                raise ValueError(
                    f"draft vocab {dm.cfg.vocab_size} != target vocab "
                    f"{self.model.cfg.vocab_size} — the accept test "
                    f"compares distributions over one vocabulary")
            if cfg.max_len > dm.cfg.max_positions:
                raise ValueError(
                    f"max_len {cfg.max_len} exceeds the draft model's "
                    f"max_positions {dm.cfg.max_positions}")
            self.draft_model, self.draft_variables = dm, dv
            # Every slot can reach max_len + no prefix cache: the
            # draft pool is bookkeeping-cheap (draft blocks are a
            # fraction of target bytes) and must NEVER be the
            # backpressure source — admission budgets are sized
            # against the target pool alone.
            self.draft_pool = self._make_paged_pool(
                dm, num_blocks=None, prefix_cache=False,
                eviction="none", quantized=self.kv_quant)
            self.pool.mirror = self.draft_pool
            self.draft_executor = Executor(donate_argnums=(1,))
            self._draft_prefill_fns = {
                w: self._wrap_prefill_program(
                    _build_draft_prefill(dm, w))
                for w in cfg.all_prefill_buckets}
            # Carried residual-distribution flag: True where the row's
            # last_logits hold the rejection residual (already-filtered
            # log-probs — sampled raw, never re-filtered).
            self.residual = jnp.zeros((b,), bool)
            # Host ledgers for the bench record / acceptance gates.
            self.spec_verifies = 0
            self.spec_draft_tokens = 0
            self.spec_accepted = 0
            self._step_fn = self._wrap_program(_build_spec_step(
                self.model, dm, self.k_max, cfg.pad_id,
                cfg.decode_horizon, self.spec.draft_k))
        else:
            self._step_fn = self._wrap_program(
                _build_step(self.model, self.k_max, cfg.pad_id,
                            cfg.decode_horizon, groups=groups))

    # ----------------------------------------------- subsystem hooks
    # The tensor-sharded engine (serve/sharded/engine.py) specializes
    # the engine at a handful of seams — where pools are built and where
    # built programs are handed to the executor — so every other line
    # of the admission/decode machinery stays sharding-blind. Single-
    # device serving goes through the identity versions below.
    def _make_paged_pool(self, model, *, num_blocks, prefix_cache,
                         eviction, quantized, host_blocks=0):
        """Pool constructor hook (target AND draft pools route
        through here — the draft always passes ``host_blocks=0``: its
        pool keeps no prefix cache, so there is nothing to demote).
        Overridden by the sharded engine to lay the block pools out
        head-sharded across its mesh."""
        cfg = self.cfg
        return PagedSlotPool(
            model, cfg.max_batch_size, cfg.max_len, cfg.cache_dtype,
            block_size=cfg.kv_block_size, num_blocks=num_blocks,
            prefix_cache=prefix_cache, eviction=eviction,
            quantized=quantized, host_blocks=host_blocks)

    def _wrap_program(self, fn):
        """Program hook: every built prefill/step program passes through
        here before it reaches the executor. The sharded engine wraps
        the trace in ``auto_partitioner_scope(mesh)`` so model code
        sees the mesh (nested shard_map kernels, no Mosaic under the
        auto-partitioner); the identity keeps single-device dispatch
        byte-for-byte what it was."""
        return fn

    def _wrap_prefill_program(self, fn):
        """Prefill-program hook (target AND draft bucket programs):
        defaults to :meth:`_wrap_program`, so every engine keeps its
        existing wrapping. The sharded engine in
        ``prefill_mode="sequence"`` overrides this to ALSO enter the
        seq-prefill scope around the trace — the model's prefill-chunk
        branch then builds the nested sequence-sharded shard_map
        (serve/sharded/seq_prefill.py) while step/decode programs stay
        untouched."""
        return self._wrap_program(fn)

    # -------------------------------------------------------- host API
    def bucket_for(self, n: int) -> int:
        """The static pad width the TAIL chunk of an ``n``-token prompt
        runs at (the smallest bucket >= n for single-chunk prompts;
        with long buckets configured, possibly a pad-up long tail — see
        :meth:`_plan_chunks`). Benchmarks group TTFT by this value."""
        return self._plan_chunks(n)[-1][2]

    def _plan_chunks(self, n: int,
                     start: int = 0) -> List[Tuple[int, int, int]]:
        """Chunk plan for prefilling positions ``[start, n)`` of an
        ``n``-token prompt: ``(offset, real_len, pad_width)`` triples.
        Greedy largest-fit over ALL buckets: while the remainder
        exceeds ``max_prefill_len``, either pad UP into the smallest
        bucket covering the whole remainder (only when the pad waste is
        below one stride — an 8 001-token prompt takes one 8192-wide
        dispatch, a 100-token remainder never balloons to 8k) or stride
        by the largest bucket that fits (long buckets stride in big
        steps); then the classic bucketed tail. With
        ``long_prefill_buckets=()`` this reduces EXACTLY to the old
        plan: full ``max_prefill_len`` strides then a bucketed tail.
        With a shared-prefix ``start`` only the un-cached suffix is
        planned (partial-prefix prefill reuses the same bucket
        machinery). A padded tail that would spill past ``max_len``
        slides back over real tokens (rewriting positions recomputes
        identical K/V; the paged pool COWs any shared block the slide
        re-enters)."""
        cfg = self.cfg
        p_max = cfg.max_prefill_len
        buckets = cfg.all_prefill_buckets
        chunks: List[Tuple[int, int, int]] = []
        off = start
        width = None
        while n - off > p_max:
            rem = n - off
            up = [w for w in buckets if w >= rem]
            stride = max(w for w in buckets if w <= rem)
            if up and up[0] - rem < stride:
                # Pad-up tail: one wide dispatch covers the whole
                # remainder and wastes less than one more stride would
                # have advanced.
                width = up[0]
                break
            chunks.append((off, stride, stride))
            off += stride
        rem = n - off
        if width is None:
            width = next(w for w in buckets if w >= rem)
        if off + width > cfg.max_len and not (self.pool.window
                                              or self.pool.state_entries):
            # (A model with window or state layers writes no pad at all:
            # it is told the chunk's real length, and a slide would ask
            # its ring for keys it has already overwritten, or fold
            # tokens into a state that already holds them.)
            # A padded tail would spill past the slot's KV capacity
            # (max_len not a multiple of the stride, prompt near
            # capacity) — and dynamic_update_slice would CLAMP the write
            # start, corrupting the already-written prefix. Slide the
            # window back to cover the last `width` REAL tokens instead:
            # rewriting those positions recomputes identical K/V (same
            # tokens, same prefix), and no pad lands past capacity.
            # (off can dip below `start` here — with a shared prefix
            # the paged pool COWs the re-entered blocks, keeping the
            # cached copies intact.)
            off, rem = max(n - width, 0), min(width, n)
        chunks.append((off, rem, width))
        return chunks

    def prefill_span(self, n: int) -> int:
        """The highest position (exclusive) a cold prefill of an
        ``n``-token prompt writes, bucket pads included — what the
        scheduler's free-block admission budget is sized against."""
        off, _, width = self._plan_chunks(n)[-1]
        return max(off + width, n)

    def prefill_blocks_needed(self, n: int) -> int:
        """Worst-case (no prefix hit) block count an ``n``-token prompt
        binds at prefill."""
        return self.pool.blocks_for_span(self.prefill_span(n))

    def prefill(self, slot: int, tokens: Sequence[int], *, seed: int = 0,
                temperature: float = 0.0, top_k: Optional[int] = None,
                top_p: Optional[float] = None,
                eos_id: Optional[int] = None,
                max_new_tokens: Optional[int] = None) -> None:
        """Load one request into ``slot``: prompt K/V, position, PRNG
        key, sampling params, and the row's on-device completion state
        (``eos_id``, ``None`` = never stop on a token; and its
        new-token budget, ``None`` = everything the slot's KV capacity
        allows). ``tokens`` may be up to ``max_len - 1`` long (room for
        at least one generated token); prompts wider than
        ``max_prefill_len`` run as successive chunks through the same
        bucket programs. The prompt's full-block
        prefix is first matched against the prefix cache — matched
        blocks are REFERENCED, not recomputed, and only the suffix
        prefills (``KVBlocksExhausted`` from binding is typed
        backpressure the scheduler absorbs). Token ids are NOT
        validated here — admission (``Scheduler.submit``) is the
        validation boundary. The first generated token comes from the
        next :meth:`step`."""
        with obs.annotate("serve.engine.prefill",
                          tokens=len(tokens)) as ann:
            self._prefill(ann, slot, tokens, seed, temperature, top_k,
                          top_p, eos_id, max_new_tokens)

    def _prefill(self, ann, slot: int, tokens: Sequence[int], seed: int,
                 temperature: float, top_k: Optional[int],
                 top_p: Optional[float], eos_id: Optional[int],
                 max_new_tokens: Optional[int]) -> None:
        """The body of :meth:`prefill`, inside its
        ``serve.engine.prefill`` span ``ann``."""
        faults.point("serve.prefill")
        n = len(tokens)
        if not 1 <= n < self.cfg.max_len:
            raise ValueError(
                f"prompt length {n} not in [1, max_len-1="
                f"{self.cfg.max_len - 1}]")
        # The device budget is what stops a row mid-block; capping it at
        # the slot's remaining KV capacity means a block can never write
        # past max_len even for budget-less direct engine callers.
        cap = self.cfg.max_len - n
        budget = cap if max_new_tokens is None else min(max_new_tokens,
                                                        cap)
        tokens = np.asarray(tokens, np.int32)
        # Prefix reuse: take references on cached blocks covering
        # the prompt's full-block prefix (capped at n-1 — the last
        # token always re-runs so its logits seed decoding), then
        # bind/COW everything the planned chunks will write. With a
        # host tier the bind also PROMOTES host-demoted blocks: the
        # async host->device scatter is dispatched inside this call
        # — ahead of every chunk dispatch below — so the partial-
        # prefix chunk programs start from the promoted span and
        # queue behind the copy on the device stream (dataflow
        # through pool.caches orders them; no host sync anywhere).
        with obs.annotate("serve.engine.prefill.bind") as bind:
            start = self.pool.bind_for_prompt(slot, tokens.tolist())
            chunks = self._plan_chunks(n, start)
            try:
                bound = self.pool.prepare_write(
                    slot, min(off for off, _, _ in chunks),
                    max(off + width for off, _, width in chunks))
            except KVBlocksExhausted:
                if start == 0:
                    raise
                # Tight-pool edge: the hit's own references pinned the
                # evictable blocks its copy-on-write then needed. Fall
                # back to a COLD prefill — releasing our references
                # makes those blocks reclaimable again, and admission
                # sized its budget for exactly this no-hit footprint.
                self.pool.release_blocks(slot)
                start = 0
                chunks = self._plan_chunks(n, 0)
                bound = self.pool.prepare_write(
                    slot, 0,
                    max(off + width for off, _, width in chunks))
            bind.set(bound=bound)
        if start > 0:
            # Count the hit only once its binding MATERIALIZED —
            # the cold fallback above must not inflate cache wins.
            self.pool.count_prefix_hit()
        self.host_positions[slot] = n
        self.host_budgets[slot] = budget
        if self._in_flight is not None:
            # the block in flight was launched for the slot's last
            # holder: its shortfall is not this request's
            self._in_flight.mirrored[slot] = False
        obs.counter("serve.prefill.chunks_total").inc(len(chunks))
        # Re-pin per call, not just at init: benchmark harnesses reset
        # the registry after warmup, and the impl label must survive
        # into the measured run's summary.
        obs.gauge("serve.prefill.kernel_active").set(
            1.0 if self.prefill_kernel_active else 0.0)
        obs.gauge("serve.sampling.topk_candidates").set(
            self.topk_candidates)
        # Tokens the compiled chunks will actually push through the
        # target model: bucket pads included, a prefix hit's cached
        # span excluded (and a cold fallback's full re-plan included).
        # The sharded engine's collective-payload estimate reads this
        # after the call — prefill_span() would overcount hits.
        self.last_prefill_tokens = sum(w for _, _, w in chunks)
        self.last_prefill_chunks = len(chunks)
        ann.set(cached=start, chunks=len(chunks))
        if self.pool.state_entries:
            # chunks a state layer's prefill scan walks (a layer), and
            # the reset: the chunk at offset 0 starts from zeros
            state_chunks = sum(
                self.model.prefill_scan_chunks(w) for _, _, w in chunks)
            ann.set(state_chunks=state_chunks)
            if self.model.ssm_layers:
                obs.counter("serve.ssm.scan_chunks_total").inc(
                    state_chunks * self.model.ssm_layers)
            if start == 0:
                obs.counter("serve.state.resets_total").inc()
        qerrs: List[Any] = []
        for off, ln, width in chunks:
            obs.histogram("serve.prefill.bucket_len").observe(width)
            # Per-chunk trace fragment: recorded only when the scheduler
            # wrapped this prefill in the request's trace context (it
            # nests under the serve.prefill span), so the stitched
            # timeline shows which bucket/offset each chunk DISPATCHED
            # at — untraced requests pay one contextvar read per chunk.
            with obs.traced_span("serve.prefill.chunk", width=width,
                                 offset=off, tokens=ln):
                padded = np.zeros((1, width), np.int32)
                padded[0, :ln] = tokens[off:off + ln]
                scalars = (np.int32(ln), np.int32(slot), np.int32(off),
                           np.int32(seed), np.float32(temperature),
                           np.int32(0 if top_k is None else top_k),
                           np.float32(1.0 if top_p is None else top_p),
                           np.int32(-1 if eos_id is None else eos_id),
                           np.int32(budget))
                state = (self.last_logits, self.positions, self.keys,
                         self.temps, self.top_ks, self.top_ps,
                         self.eos_ids, self.budgets)
                # Pinned kernel span: brackets the chunk's DISPATCH
                # through the flash-prefill kernel program (async
                # under jit — wall time covers Python dispatch plus
                # any blocking first-trace compile, the executor's
                # usual measurement idiom). On an int8 pool every
                # layer fused its K and V block writes into the
                # kernel epilogue instead of the gather/requant
                # round-trip — count them so the fused-write rate
                # is auditable against chunk throughput.
                with obs.annotate("serve.engine.prefill.launch",
                                  width=width), \
                     (obs.span("serve.prefill.kernel_s", width=width)
                      if self.prefill_kernel_active
                      else contextlib.nullcontext()):
                    out = self.executor.run(
                        self._prefill_fns[width], self.variables,
                        self.pool.caches, self.pool.device_tables(),
                        jnp.asarray(padded), *scalars, *state)
                if self.prefill_kernel_active and self.kv_quant:
                    obs.counter("serve.prefill.fused_writes_total").inc(
                        getattr(self.model.cfg, "num_layers", 1))
                if self.kv_quant:
                    # The quantized prefill program's extra output: this
                    # chunk's max-abs dequant error. Collect the DEVICE
                    # scalar now, read after every chunk has been
                    # dispatched — the histogram observe must not
                    # serialize chunk k+1's dispatch behind chunk k's
                    # completion.
                    out, err = out[:-1], out[-1]
                    qerrs.append(err)
                if self.model.mhc_sublayers:
                    # the chunk's worst H_res, read at the next step
                    out, res = out[:-1], out[-1]
                    self._mhc_pending.append(res)
                    obs.counter("serve.mhc.maps_total").inc(
                        width * self.model.mhc_sublayers)
                (self.pool.caches, self.last_logits, self.positions,
                 self.keys, self.temps, self.top_ks, self.top_ps,
                 self.eos_ids, self.budgets) = out
        if self.kv_quant:
            hist = obs.histogram("serve.kv.quant_error")
            for err in qerrs:
                hist.observe(float(err))
        if self.spec is not None:
            # Draft-side prefill: the draft cache must hold the SAME
            # prompt before the first draft chain runs. Always a cold
            # plan from 0 — the draft pool keeps no prefix cache, and a
            # target-side prefix hit says nothing about draft KV. An
            # exception here (genuine or injected) unwinds through the
            # scheduler's admission handler, which retires only this
            # request and frees the slot — the mirror releases the
            # draft pool's partial binds in the same free().
            dchunks = self._plan_chunks(n, 0)
            self.draft_pool.prepare_write(
                slot, 0,
                max(off + width for off, _, width in dchunks))
            for off, ln, width in dchunks:
                padded = np.zeros((1, width), np.int32)
                padded[0, :ln] = tokens[off:off + ln]
                dscalars = (np.int32(ln), np.int32(slot), np.int32(off))
                with obs.annotate("serve.engine.prefill.launch",
                                  width=width):
                    self.draft_pool.caches = self.draft_executor.run(
                        self._draft_prefill_fns[width],
                        self.draft_variables, self.draft_pool.caches,
                        self.draft_pool.device_tables(),
                        jnp.asarray(padded), *dscalars)
            # Fresh request: its carried logits are real target logits,
            # not a residual distribution.
            self.residual = self.residual.at[slot].set(False)
        # Index this prompt's full blocks for future prefix hits
        # (the trie takes its own references — the cache outlives
        # this request's slot).
        self.pool.register_prefix(slot, tokens.tolist())
        if faults.enabled():
            self.last_logits = faults.corrupt(
                "serve.prefill.logits", self.last_logits, rows=(slot,))

    def _bind_decode_windows(self, active: np.ndarray, cap: int,
                             pools) -> int:
        """Lazy binding: make every active row's write
        window for this block — ``[pos, pos + min(cap, budget))``,
        clamped to capacity — exclusively owned in each of ``pools``
        BEFORE the dispatch. The bound is what the row can actually
        EMIT: once done (or for a degenerate budget-0 row) its
        non-emitting writes route to the scratch block, so nothing
        past the budget needs binding — a row one token from finishing
        must never be retired for blocks it would never write. A bind
        that finds no block (genuine exhaustion or an injected
        serve.kv.bind fault) surfaces as the typed KVBlocksExhausted
        carrying the victim slot — the scheduler retires that one
        request and redials; the batch never crashes. -> the blocks
        newly bound."""
        bound = 0
        for slot in np.flatnonzero(np.asarray(active, bool)):
            pos_h = int(self.host_positions[slot])
            need = min(cap, max(int(self.host_budgets[slot]), 0))
            if need == 0:
                continue
            start = min(pos_h, self.cfg.max_len - 1)
            end = max(min(pos_h + need, self.cfg.max_len), start + 1)
            try:
                for pool in pools:
                    bound += pool.prepare_write(int(slot), start, end)
            except faults.InjectedFault as e:
                raise KVBlocksExhausted(str(e), slot=int(slot)) from e
        return bound

    def _dispatch_attrs(self, active: np.ndarray) -> dict:
        """What the ``serve.engine.dispatch`` span says of a step: the
        active ``rows`` and the table entries they hold going in, a
        group (``blocks``, ``latent_blocks`` on a latent pool: the
        growing table; ``window_blocks``: the ring, where the model has
        window layers; a state layer updates the state of every one of
        the ``rows``, so it adds no attr). ``blocks / (rows * M)`` is the
        share of the block table the paged decode kernel visits: it
        skips, without a DMA, every entry past a row's length."""
        active = np.asarray(active, bool)
        held = self.host_positions[active] // self.cfg.kv_block_size + 1
        attrs = {"rows": int(np.count_nonzero(active)),
                 "blocks" if self.kv_heads_cache else "latent_blocks":
                 int(held.sum())}
        if self.pool.window:
            attrs["window_blocks"] = int(
                np.minimum(held, self.pool.window_entries).sum())
        return attrs

    def _stage_step(self, ann, active: np.ndarray, cap: int, pools):
        """The host's part of a step before its launch, inside the
        step's ``serve.engine.dispatch`` span ``ann``: that span's attrs
        (counted here, so in its own self time), the rows' write windows
        bound in each of ``pools`` (``serve.engine.bind``), and every
        host-to-device upload of the step, each pool's tables and the
        active mask (``serve.engine.tables``). -> (the tables a pool, the
        mask on the device)."""
        attrs = self._dispatch_attrs(active)
        ann.set(**attrs)
        with obs.annotate("serve.engine.bind", rows=attrs["rows"]) as bind:
            bind.set(bound=self._bind_decode_windows(active, cap, pools))
        with obs.annotate("serve.engine.tables") as upload:
            tables = [pool.device_tables() for pool in pools]
            mask = jnp.asarray(active, bool)
            upload.set(bytes=mask.nbytes + sum(
                t.nbytes for group in tables for t in group.values()))
        return tables, mask

    def step(self, active: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Decode one BLOCK of up to ``decode_horizon`` tokens for every
        row; ``active`` is a ``[B_max]`` bool mask. Returns
        ``(tokens, emitted)`` as host arrays: ``tokens`` is the
        ``[B_max, H]`` block — a row's valid tokens are
        ``tokens[r, :emitted[r]]``; everything past its count (overshoot
        after EOS / budget / a mid-block NaN freeze, or all H columns of
        an inactive row) is pad and must be ignored. After the call
        :attr:`step_ok` holds the returned block's ``[B_max]`` bool
        health mask: False where a row's logits went non-finite at any
        scan step (only meaningful for rows the caller knows are
        active) — such a row's pre-burst tokens are still counted in
        ``emitted``.

        Launch-then-collect (any direct caller): the block returned is
        the one this call launched. Overlapped (after
        :meth:`overlap_blocks`): this call launches block k FIRST and
        returns block k-1, launched by the call before it with THAT
        call's mask; with nothing in flight it returns an all-pad block
        with ``emitted`` zero. A call whose rows can emit nothing, every
        host budget spent, launches nothing and only collects."""
        faults.point("serve.step")
        if self.spec is not None:
            self.step_calls += 1
            return self._spec_step(active)
        prev = self._in_flight
        if prev is not None and not (
                self.host_budgets[np.asarray(active, bool)] > 0).any():
            # Overlapped, and the block in flight spends the last budget
            # of every row in the mask: a block launched now could emit
            # nothing (the device's budget never exceeds the mirror).
            self._in_flight = None
            return self._collect(prev)
        self.step_calls += 1
        block = self._launch(active)
        if not self._overlap:
            return self._collect(block)
        self._in_flight = block
        if prev is None:
            self.step_ok = np.ones((self.cfg.max_batch_size,), bool)
            return (np.full((self.cfg.max_batch_size,
                             self.cfg.decode_horizon), self.cfg.pad_id,
                            np.int32),
                    np.zeros((self.cfg.max_batch_size,), np.int32))
        self.blocks_overlapped += 1
        obs.counter("serve.engine.blocks_overlapped_total").inc()
        return self._collect(prev)

    def overlap_blocks(self, on: bool = True) -> bool:
        """Switch :meth:`step` to its overlapped form, where the engine
        can state a row's write window before the block returns: the
        classic step at any ``decode_horizon`` (a speculative window's
        width depends on acceptance, so that engine stays
        launch-then-collect). For the one caller that can consume a
        block one call late, the :class:`Scheduler`, which asks at
        construction; ``on=False`` takes it back for whoever then steps
        the engine of a built stack by hand (nothing may be in flight).
        -> whether ``step`` is overlapped now."""
        if self._in_flight is not None:
            raise RuntimeError("a block is in flight: settle() first")
        self._overlap = bool(on) and self.spec is None
        return self._overlap

    @property
    def overlapped(self) -> bool:
        """Whether :meth:`step` returns the block BEFORE the one it
        launches (:meth:`overlap_blocks`)."""
        return self._overlap

    @property
    def in_flight(self) -> bool:
        """Whether a launched block has not been collected yet."""
        return self._in_flight is not None

    def settle(self, reason: str = "drain"
               ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """Collect the block in flight WITHOUT launching another, and
        return its ``(tokens, emitted)``; ``None`` when nothing is in
        flight. For whoever is about to read or move a live row's state
        (preemption, migration, a drain, the retry after a failed
        call): after it the host mirrors, ``step_ok`` and every token
        are what they are after a launch-then-collect step. ``reason``
        says why, on the collect's ``serve.engine.wait`` span."""
        block, self._in_flight = self._in_flight, None
        if block is None:
            return None
        self.settles += 1
        obs.counter("serve.engine.settles_total").inc()
        return self._collect(block, settle=reason)

    def _launch(self, active: np.ndarray) -> "_Block":
        """Stage and launch one block, and rebind the engine's device
        state to its outputs: after k launches that state is what it is
        after k steps, whether or not any block has been collected. The
        host mirrors advance HERE, by what each row emits if nothing
        stops it (``min(horizon, budget)``, the window just bound), so
        the next launch binds from mirrors that include this block;
        :meth:`_collect` takes back what the row fell short by."""
        active = np.array(active, bool)
        # ``seq`` on a block's dispatch and on its wait: overlapped, the
        # wait that follows a dispatch is the block before it's, and the
        # report joins the two halves of one block by it (obs/report.py).
        with obs.annotate("serve.engine.dispatch", engine=self.engine_id,
                          seq=self.step_calls) as ann:
            (tables,), mask = self._stage_step(
                ann, active, self.cfg.decode_horizon, (self.pool,))
            with obs.annotate("serve.engine.launch"):
                out = self.executor.run(
                    self._step_fn, self.variables, self.pool.caches,
                    tables, self.last_logits, self.positions,
                    mask, self.keys,
                    self.temps, self.top_ks, self.top_ps,
                    self.eos_ids, self.budgets)
            (tok, emitted, ok, full_sorts, caches, last, pos, keys,
             budgets, *load) = out
            # Start the block's device->host transfers NOW, before any
            # host bookkeeping (state rebinds here, retire/admit/stream
            # in the scheduler): the fetches then find bytes already in
            # flight instead of paying the full sync serially.
            _start_host_copies(tok, emitted, ok, full_sorts, *load)
            mhc = load.pop() if self.model.mhc_sublayers else None
        self.pool.caches = caches
        if faults.enabled():
            last = faults.corrupt(
                "serve.step.logits", last,
                rows=lambda: np.flatnonzero(active))
        self.last_logits, self.positions, self.keys = last, pos, keys
        self.budgets = budgets
        bound = np.where(active, np.clip(self.host_budgets, 0,
                                         self.cfg.decode_horizon), 0)
        if self.model.sparse_attention_layers:
            self._count_sparse_blocks(bound)
        self.host_positions += bound
        self.host_budgets -= bound
        return _Block(self.step_calls, int(np.count_nonzero(active)),
                      active, bound, tok, emitted, ok, full_sorts, load, mhc)

    def _collect(self, block: "_Block", **attrs
                 ) -> Tuple[np.ndarray, np.ndarray]:
        """Fetch a launched block: the one sync of a pass. Sets
        :attr:`step_ok`, the expert and residual counters, and corrects
        the host mirrors by each row's shortfall ``bound - emitted``
        (EOS, a NaN freeze): a row prefilled since the launch has
        mirrors of its own, and is left alone. ``attrs`` go on the
        ``serve.engine.wait`` span."""
        with obs.annotate("serve.engine.wait", engine=self.engine_id,
                          seq=block.seq, **attrs):
            # The host blocked on the device: the first fetch returns
            # when the block has run; the others (their copies started
            # with it) are further round trips.
            self.step_ok = np.asarray(block.ok)
            with obs.annotate("serve.engine.fetch"):
                tok_h = np.asarray(block.tok)
                emitted_h = np.asarray(block.emitted)
                full_sorts_h = int(np.asarray(block.full_sorts))
                if block.load:
                    self.last_expert_load = np.asarray(block.load[0])
                    visits_h = np.asarray(block.load[1])
                if block.mhc is not None:
                    self._record_mhc(block.rows, block.mhc)
        obs.counter("serve.sampling.full_sort_steps_total").inc(full_sorts_h)
        if self.model.ssm_layers:
            # a row's state advances once a token it emitted, a layer
            obs.counter("serve.ssm.decode_rows_total").inc(
                int(emitted_h.sum()) * self.model.ssm_layers)
        if block.load:
            self._record_expert_load(block.rows, visits_h)
        # Positions advance and budgets decay on device exactly once an
        # emitted token; the mirrors were advanced by ``bound`` at the
        # launch. (A NaN-frozen row may lag by one: it is retired on
        # this block's result, so its window is never grown.)
        short = np.where(block.mirrored, block.bound - emitted_h, 0)
        self.host_positions -= short
        self.host_budgets += short
        return tok_h, emitted_h

    def _count_sparse_blocks(self, bound: np.ndarray) -> None:
        """The blocks the sparse layers' decode queries of a launched
        block read and the blocks their rows hold, by the model's
        selection rule at each token's visible length (the host mirrors
        before the launch, no device read): ``serve.sparse.
        blocks_read_total`` and ``serve.sparse.blocks_resident_total``."""
        read = held = 0
        for i in range(int(bound.max(initial=0))):
            r, h = self.model.sparse_blocks(
                np.where(bound > i, self.host_positions + 1 + i, 0))
            read, held = read + r, held + h
        obs.counter("serve.sparse.blocks_read_total").inc(read)
        obs.counter("serve.sparse.blocks_resident_total").inc(held)

    def _record_mhc(self, tokens: int, residual) -> None:
        """The residual path's maps of one step (``tokens`` rows) and of
        the prefill chunks since the step before it (their tokens were
        counted at dispatch; their residuals, device scalars that have
        long been computed by now, are read here so that no prefill
        waits for one)."""
        pending, self._mhc_pending = self._mhc_pending, []
        worst = max(float(np.asarray(r)) for r in (residual, *pending))
        self.mhc_residual_max = max(self.mhc_residual_max, worst)
        obs.counter("serve.mhc.maps_total").inc(
            tokens * self.model.mhc_sublayers)
        obs.gauge("serve.mhc.sinkhorn_residual_max").set(
            self.mhc_residual_max)

    def _record_expert_load(self, rows: int, visits: np.ndarray) -> None:
        """The expert layer's counters for one step (registry
        instruments: no-ops without a run dir). ``pairs`` is what the
        router chose over all experts (rows x top-k a layer); ``held``
        what this chip's experts computed of it; ``visits`` ``[layers,
        2]`` the (row tile, expert) visits of the experts' kernel beside
        the held experts it touched: their ratio is how many times a
        touched expert's weights were read a layer call, 1.0 where no
        group is cut by a row tile's edge."""
        load = self.last_expert_load
        layers, held = load.shape
        top_k = self.model.cfg.num_experts_per_tok
        obs.counter("serve.moe.pairs_total").inc(rows * top_k * layers)
        obs.counter("serve.moe.held_pairs_total").inc(int(load.sum()))
        obs.counter("serve.moe.expert_visits_total").inc(
            int(visits[:, 0].sum()))
        obs.counter("serve.moe.experts_touched_total").inc(
            int(visits[:, 1].sum()))
        mean = load.mean(axis=1)
        if (mean > 0).all():
            obs.gauge("serve.moe.load_max_over_mean").set(
                float((load.max(axis=1) / mean).mean()))

    def _spec_step(self, active: np.ndarray
                   ) -> Tuple[np.ndarray, np.ndarray]:
        """The speculative decode block (``step()`` dispatches here
        when ``cfg.speculative`` is set): one compiled program runs
        ``decode_horizon`` draft→verify→accept windows and returns the
        SAME ``(tokens, emitted)`` contract as the classic step — the
        emitted tokens are compacted to a left-aligned prefix of the
        ``[B, H*(k+1)]`` block, so the scheduler's slice-at-emitted
        consumption path is unchanged. Always launch-then-collect,
        whoever calls: a window's width depends on what the verify
        accepts, so the host cannot advance its mirrors (and bind the
        next block's write windows) before this block's counts are
        back; :meth:`overlap_blocks` answers False for this engine."""
        k = self.spec.draft_k
        cap = self.cfg.decode_horizon * (k + 1)
        with obs.annotate("serve.engine.dispatch",
                          engine=self.engine_id) as ann:
            # Both pools bind the same window: verify/draft writes
            # past it are garbage by construction and route to the
            # scratch block through the unbound table tail.
            (tables, dtables), mask = self._stage_step(
                ann, active, cap, (self.pool, self.draft_pool))
            with obs.annotate("serve.engine.launch"):
                out = self.executor.run(
                    self._step_fn, self.variables,
                    (self.pool.caches, self.draft_pool.caches),
                    self.draft_variables, tables, dtables,
                    self.last_logits, self.positions,
                    mask, self.keys,
                    self.temps, self.top_ks, self.top_ps,
                    self.eos_ids, self.budgets, self.residual)
            (tok, emitted, ok, win_emitted, full_sorts, caches_all, last,
             pos, keys, budgets, residual) = out
            _start_host_copies(tok, emitted, ok, win_emitted, full_sorts)
        self.pool.caches, self.draft_pool.caches = caches_all
        if faults.enabled():
            # The pinned verify-step fault point: a nan/inf rule
            # poisons one active row's carried logits, which the next
            # dispatch's in-program tripwire converts into a
            # victim-only retirement (FinishReason.ERROR, zero leaks);
            # an error rule raises typed InjectedFault into the
            # scheduler's bounded-retry envelope.
            last = faults.corrupt(
                "serve.spec.verify", last,
                rows=lambda: np.flatnonzero(active))
        self.last_logits, self.positions, self.keys = last, pos, keys
        self.budgets, self.residual = budgets, residual
        with obs.annotate("serve.engine.wait", engine=self.engine_id):
            self.step_ok = np.asarray(ok)
            with obs.annotate("serve.engine.fetch"):
                tok_h, emitted_h = np.asarray(tok), np.asarray(emitted)
                win_h = np.asarray(win_emitted)
                full_sorts_h = int(np.asarray(full_sorts))
        obs.counter("serve.sampling.full_sort_steps_total").inc(full_sorts_h)
        # Speculation ledger: every window that emitted >= 1 token ran
        # one verify forward; its accepted-prefix length is (e_w - 1)
        # draft tokens (the t0 column is the classic carried-logits
        # sample, always exact). The drafted denominator charges all k
        # proposals per verify even when EOS/budget truncation made
        # some positions unacceptable — on short-completion loads the
        # reported accept_rate therefore UNDERSTATES draft fidelity
        # (tokens_per_verify, the headline, is unaffected: it counts
        # what was actually emitted per dispatch paid).
        ws = win_h[np.asarray(active, bool)]
        ran = ws[ws > 0]
        if ran.size:
            verifies = int(ran.size)
            accepted = int((ran - 1).sum())
            self.spec_verifies += verifies
            self.spec_draft_tokens += verifies * k
            self.spec_accepted += accepted
            obs.counter("serve.spec.draft_tokens_total").inc(
                verifies * k)
            obs.counter("serve.spec.accepted_total").inc(accepted)
            hist = obs.histogram("serve.spec.accepted_len")
            for v in (ran - 1).tolist():
                hist.observe(v)
        self.host_positions += emitted_h.astype(np.int64)
        self.host_budgets -= emitted_h.astype(np.int64)
        return tok_h, emitted_h

    @property
    def tokens_per_dispatch(self) -> int:
        """Ceiling on tokens one step dispatch can emit:
        ``decode_horizon`` windows of ``1 + draft_k`` tokens each
        (``decode_horizon`` exactly when speculative is off) — the
        value the ``serve.decode.horizon`` histogram observes."""
        h = self.cfg.decode_horizon
        return h * (1 + self.spec.draft_k) if self.spec else h

    def compile_stats(self) -> dict:
        """Executor cache stats — steady state is ``entries ==
        1 + len(prefill_buckets)`` (step + one prefill per bucket),
        misses frozen there after every bucket has been warmed while
        hits grow. Speculative mode keeps the SAME count: the
        draft→verify→accept loop is baked into the one step program
        (the draft engine's own bucket prefills are counted separately
        — :meth:`draft_compile_stats`)."""
        return self.executor.stats()

    def draft_compile_stats(self) -> Optional[dict]:
        """Draft-engine executor stats (None when speculative is off):
        steady state is ``entries == len(prefill_buckets)`` — the
        draft's bucket prefill programs; its decode never dispatches
        on its own."""
        return (self.draft_executor.stats()
                if self.draft_executor is not None else None)


def _start_host_copies(*arrays) -> None:
    for arr in arrays:
        copy_async = getattr(arr, "copy_to_host_async", None)
        if copy_async is not None:
            copy_async()


def _with_tables(caches, tables, groups=None, valid=None):
    """The model's paged cache rows: each layer's pool leaves plus the
    block table of the layer's group (``tables``: group -> table;
    ``groups``: the group a layer, all ``"global"`` when None; a layer in
    several groups gets the first one's table as ``tables`` and each
    further one's as ``<group>_tables``). The
    dict-merge keeps every leaf riding into the model (int8 pools carry
    k_scale/v_scale beside k/v: the scales are cache state like any
    other). A model with window or state layers is also told how many
    of a prefill chunk's tokens are real (``valid``): a pad written into
    a ring would overwrite keys the next queries still see, and a pad
    folded into a state could not be taken out again."""
    groups = groups or ("global",) * len(caches)
    extra = {} if valid is None or not (
        {"window", "state"} & set(tables)) else {"valid": valid}

    def layer_tables(g):
        if isinstance(g, str):
            return {"tables": tables[g]}
        return {"tables": tables[g[0]],
                **{f"{o}_tables": tables[o] for o in g[1:]}}

    return [{**pool, **layer_tables(g), **extra}
            for pool, g in zip(caches, groups)]


def _table_rows(tables, slot):
    """One slot's row of every group's table, ``[1, M]`` each."""
    zero = jnp.zeros((), jnp.int32)
    return {g: lax.dynamic_slice(t, (slot, zero), (1, t.shape[1]))
            for g, t in tables.items()}


def _pool_leaves(new_rows, caches):
    """The pool's own leaves back out of the rows the model returned
    (which also hold the tables and, on an int8 prefill, ``qerr``)."""
    return [{kk: r[kk] for kk in pool} for r, pool in zip(new_rows, caches)]


def _build_prefill(model, width: int, quantized: bool = False,
                   groups=None):
    def prefill(variables, caches, tables, tokens, length, slot, pos,
                seed, temperature, top_k, top_p, eos_id, budget,
                last_logits, positions, keys, temps, top_ks, top_ps,
                eos_ids, budgets):
        # One prompt chunk, padded to this bucket's static `width`, runs
        # against the slot's own cache storage at a traced offset: it
        # sees the prefix earlier chunks wrote (pos > 0) or nothing
        # (pos == 0), so the same program serves first chunks, middle
        # chunks, and bucketed tails. Rows past `length` are pad — their
        # K/V lands above the prompt and is overwritten by decode before
        # any mask attends it. The chunk runs against the slot's TABLE
        # ROW (one [1, M] slice of the uploaded tables) — the model
        # scatters K/V through it into the shared block pools and
        # attends the gathered prefix, so a shared-prefix request
        # starting at a nonzero `pos` sees the cached blocks it
        # referenced instead of recomputing them.
        rows = _with_tables(caches, _table_rows(tables, slot), groups,
                            valid=length)
        logits, states = model.apply(variables, tokens, training=False,
                                     cache=rows, pos=pos)
        new_rows = model.caches_from_states(states, rows)
        new_caches = _pool_leaves(new_rows, caches)
        if quantized:
            # Max-abs dequant error across layers (each attention
            # write reported its chunk's error) — returned as one
            # extra scalar output the engine host-observes into
            # serve.kv.quant_error.
            errs = [r["qerr"] for r in new_rows if "qerr" in r]
            qerr = jnp.max(jnp.stack(errs)) if errs \
                else jnp.zeros((), jnp.float32)
        row = lax.dynamic_slice(
            logits, (0, length - 1, jnp.zeros((), jnp.int32)),
            (1, 1, logits.shape[-1]))[:, 0, :]          # [1, V] last REAL row
        key = jax.random.PRNGKey(seed).astype(keys.dtype)

        def set_row(buf, val):
            return lax.dynamic_update_slice(
                buf, jnp.asarray(val, buf.dtype).reshape(
                    (1,) + buf.shape[1:]),
                (slot,) + (jnp.zeros((), jnp.int32),) * (buf.ndim - 1))

        # Every chunk overwrites the whole per-slot state; only the final
        # chunk's values survive to decode (positions advances to the
        # running prefix length either way).
        out = (new_caches,
               set_row(last_logits, row),
               set_row(positions, pos + length),
               set_row(keys, key),
               set_row(temps, temperature),
               set_row(top_ks, top_k),
               set_row(top_ps, top_p),
               set_row(eos_ids, eos_id),
               set_row(budgets, budget))
        if model.mhc_sublayers:         # last: Engine.prefill pops it
            out += (model.mhc_residual(states),)
        return out + (qerr,) if quantized else out

    return prefill


def _build_step(model, k_max: int, pad_id: int, horizon: int, groups=None):
    def body(active, temps, top_ks, top_ps, eos_ids, budgets,
             variables, tables, carry):
        """One fused decode step: the single-token body the horizon scan
        iterates. Everything request-terminating happens on device:

        - ``ok`` is the carried health mask (PR 4's NaN/inf tripwire),
          ANDed per step against the carried-in logits BEFORE sampling
          (a burst that landed between steps makes this step's sample
          garbage — never emit it) and against the fresh row AFTER the
          forward pass (matching the classic step's conservative
          discard). A row that trips freezes from that step on.
        - ``done`` flips when a row emits its EOS id or fills its
          remaining budget; ``emitted`` counts only genuinely emitted
          tokens, so the host can slice each row's valid prefix out of
          the block.
        - ``emit = active ∧ ¬done ∧ ok`` is the mask that threads into
          the model as ``active``: the flash-decode kernel zeroes
          non-emitting rows' lengths and skips their KV blocks, so a
          finished/frozen row stops writing K/V mid-block (the composed
          fallback ignores it; garbage rows are masked below either
          way). Keys advance only on emit — a request's RNG stream is a
          function of (seed, emitted count), horizon-invariant.

        The per-slot block tables thread into each layer's cache dict —
        the model
        scatters emitted tokens' K/V through them (non-emitting rows
        write the scratch block) and the flash-decode kernel gathers KV
        blocks via the table with the per-row length skip intact.
        """
        caches, last_logits, positions, keys, done, ok, emitted = carry
        ok = ok & finite_rows(last_logits)
        # (emitted < budgets) is redundant with the done flip below for
        # every block the scheduler dispatches (live rows always carry
        # budget >= 1) — it guards the degenerate budget-0 row a direct
        # engine caller could create, which must emit nothing.
        emit = active & ~done & ok & (emitted < budgets)
        # A row that emits nothing has its token replaced by the pad, so
        # it gets no nucleus: an empty or retired slot's stale top_p over
        # stale logits must not send the step into the vocabulary sort.
        next_keys, tok, full_sort = split_and_sample(
            keys, last_logits, temps, top_ks,
            jnp.where(emit, top_ps, 1.0), k_max)
        tok = jnp.where(emit, tok, pad_id)
        rows = _with_tables(caches, tables, groups)
        logits, states = model.apply(variables, tok[:, None],
                                     training=False, cache=rows,
                                     pos=positions, active=emit)
        new_rows = model.caches_from_states(states, rows)
        # [layers, experts held] pairs computed per held expert, or None
        # (a model with no serving-side expert layer: nothing is added
        # to its program).
        load = model.expert_load(states)
        if load is not None:    # and the experts' kernel's (visits, touched)
            load = (load, model.expert_visits(states))
        # ... and, of a model whose residual path is mixed by maps, how
        # far this pass's worst H_res lay from doubly stochastic
        mhc = model.mhc_residual(states) if model.mhc_sublayers else None
        new_caches = _pool_leaves(new_rows, caches)
        row_logits = logits[:, -1, :]
        ok = jnp.where(emit, ok & finite_rows(row_logits), ok)
        counted = emit & ok
        emitted = emitted + counted.astype(jnp.int32)
        done = done | (counted & (eos_ids >= 0) & (tok == eos_ids)) \
                    | (counted & (emitted >= budgets))
        act = emit[:, None]
        return (new_caches,
                jnp.where(act, row_logits, last_logits),
                jnp.where(emit, positions + 1, positions),
                jnp.where(act, next_keys, keys),
                done, ok, emitted), (tok, full_sort, load, mhc)

    def step(variables, caches, tables, last_logits, positions, active,
             keys, temps, top_ks, top_ps, eos_ids, budgets):
        b = positions.shape[0]
        init = (caches, last_logits, positions, keys,
                jnp.zeros((b,), bool),        # done (within this block)
                jnp.ones((b,), bool),         # ok   (health, carried)
                jnp.zeros((b,), jnp.int32))   # emitted (within block)

        def scan_body(carry, _):
            return body(active, temps, top_ks, top_ps, eos_ids, budgets,
                        variables, tables, carry)

        if horizon == 1:
            # Inline, not a length-1 scan: the default must stay
            # bit-identical to the classic single-token step program.
            carry, (tok, full_sort, load, mhc) = scan_body(init, None)
            tok_block = tok[:, None]
        else:
            carry, (toks, full_sort, loads, mhcs) = lax.scan(
                scan_body, init, None, length=horizon)
            tok_block = jnp.transpose(toks, (1, 0))        # [H,B]->[B,H]
            # counts add up over the block's steps; the residual is the
            # worst of them
            load = None if loads is None else tuple(
                x.sum(axis=0) for x in loads)
            mhc = None if mhcs is None else mhcs.max(axis=0)
        caches, last_logits, positions, keys, done, ok, emitted = carry
        # How many of the block's steps sorted the vocabulary (sampling's
        # wide-nucleus branch): 0 or 1 at horizon 1.
        full_sorts = jnp.sum(full_sort, dtype=jnp.int32)
        # The carried budget: what is left, and NOTHING for a row that
        # finished in this block (``done`` starts from zeros in the
        # next one: its EOS would be forgotten, and the block launched
        # before the host has read this one would emit for it again).
        # Such a row then emits nothing and writes the scratch block,
        # as one whose budget ran out does.
        left = jnp.where(done, 0, jnp.maximum(budgets - emitted, 0))
        out = (tok_block, emitted, ok, full_sorts, caches, last_logits,
               positions, keys, left)
        # Engine.step unpacks the tail: the expert counts, then the residual
        return out + (load or ()) + (() if mhc is None else (mhc,))

    return step


def _build_draft_prefill(model, width: int):
    """The draft engine's bucket prefill: the same chunk-at-traced-
    offset move as the target's (:func:`_build_prefill`) minus all
    sampling/completion state — the draft only needs its KV loaded.
    Logits are discarded; a quantized pool's per-chunk ``qerr`` is
    dropped with the dict re-filter (draft quant error is not a
    serving metric — the accept test measures draft fidelity end to
    end)."""
    def prefill(variables, caches, tables, tokens, length, slot, pos):
        del length
        rows = _with_tables(caches, _table_rows(tables, slot))
        _, states = model.apply(variables, tokens, training=False,
                                cache=rows, pos=pos)
        return _pool_leaves(model.caches_from_states(states, rows), caches)

    return prefill


def _build_spec_step(model, draft_model, k_max: int, pad_id: int,
                     horizon: int, draft_k: int):
    """The fused speculative step: ONE compiled program scanning
    ``horizon`` draft→verify→accept windows, device-resident end to
    end. Each window:

    1. samples ``t0`` from the carried target logits — exactly the
       classic step's move (or, after a rejection, a raw categorical
       from the carried RESIDUAL logits — the deferred rejection
       resample of lossless speculative sampling);
    2. runs ``draft_k + 1`` single-token draft forwards (a ``lax.scan``
       chain feeding sampled proposals), collecting the k proposals and
       the filtered draft distributions each was drawn from — the last
       forward only keeps the draft cache complete for the
       all-accepted case;
    3. runs ONE ``draft_k + 1``-wide target forward over
       ``[t0, d_1..d_k]`` at per-row traced positions (the
       models/gpt2.py verify-window write path: per-position scatter,
       overshoot and non-emitting rows routed to scratch/drop);
    4. accepts the longest agreeing prefix in-program
       (serve/sampling.py accept_mask: greedy exact-match, sampled
       ``u·q <= p``), cuts it at EOS / budget / a non-finite verify
       row, emits ``e ∈ [0, k+1]`` tokens, advances positions and the
       per-row PRNG key by exactly ``e`` split steps (the carried key
       stream stays a function of (seed, emitted count) — spec outputs
       are horizon-invariant, and greedy rows are bit-identical to the
       classic engine), and carries either the next plain target
       logits (``P[e-1]``) or the rejection residual.

    The carried done/ok masks freeze rows mid-horizon exactly as the
    classic scan does; rejected/overshoot columns never reach the host
    — the program compacts each row's emitted tokens to a left-aligned
    prefix of the ``[B, horizon*(k+1)]`` block and returns per-window
    emitted counts for the acceptance histogram."""
    k = draft_k
    w = k + 1

    def window(active, temps, top_ks, top_ps, eos_ids, budgets,
               variables, dvariables, tables, dtables, carry):
        (caches, dcaches, last_logits, positions, keys, done, ok,
         emitted, residual) = carry
        b = positions.shape[0]
        ok = ok & finite_rows(last_logits)
        emit0 = active & ~done & ok & (emitted < budgets)
        greedy = temps <= 0.0
        splits = jax.vmap(lambda kk: jax.random.split(kk, 2))(keys)
        sub0 = splits[:, 1]
        # t0: the classic carried-logits sample — the same key the
        # classic engine would use at this emitted count, so sampled
        # spec streams stay aligned with the classic stream at every
        # window boundary. Residual rows draw a RAW categorical: their
        # carried logits are already-filtered log-probs.
        # Rows that emit nothing this window get no nucleus (as in the
        # classic step): their draws and distributions are never used.
        top_ps = jnp.where(emit0, top_ps, 1.0)
        t_cls, sorted0 = sample_tokens_and_flag(
            last_logits, sub0, temps, top_ks, top_ps, k_max)
        t_res = categorical_rows(sub0, last_logits)
        t0 = jnp.where(residual, t_res, t_cls)
        t0 = jnp.where(emit0, t0, pad_id).astype(jnp.int32)

        def dstep(c, j):
            dc, tok_in = c
            rows = _with_tables(dc, dtables)
            dlog, dstates = draft_model.apply(
                dvariables, tok_in[:, None], training=False,
                cache=rows, pos=positions + j, active=emit0)
            dc2 = _pool_leaves(
                draft_model.caches_from_states(dstates, rows), dc)
            row = dlog[:, -1, :]
            # The draft proposes from the row's FILTERED distribution
            # (same temperature/top-k/top-p as the target side): the
            # rejection law is lossless for any proposal q, but a
            # proposal outside the target's truncated support has
            # p = 0 and always rejects — matching the support is what
            # keeps sampled accept rates near the draft's actual
            # fidelity.
            fl, sorted_d = filter_logits_and_flag(row, temps, top_ks,
                                                  top_ps, k_max)
            dkey = jax.vmap(
                lambda kk: jax.random.fold_in(kk, 1 + j))(keys)
            d = jnp.where(greedy, jnp.argmax(row, axis=-1),
                          categorical_rows(dkey, fl)).astype(jnp.int32)
            d = jnp.where(emit0, d, pad_id)
            return (dc2, d), (d, jax.nn.softmax(fl, axis=-1), sorted_d)

        (dcaches, _), (d_all, q_all, sorted_d) = lax.scan(
            dstep, (dcaches, t0), jnp.arange(w))
        win = jnp.concatenate(
            [t0[:, None], jnp.transpose(d_all[:k], (1, 0))], axis=1)

        vrows = _with_tables(caches, tables)
        vlog, vstates = model.apply(variables, win, training=False,
                                    cache=vrows, pos=positions,
                                    active=emit0)
        new_caches = _pool_leaves(
            model.caches_from_states(vstates, vrows), caches)
        # Health: the whole verify window must be finite — a poisoned
        # window emits NOTHING (the conservative discard of the classic
        # step at window granularity); pre-window tokens were already
        # delivered, and the carried ok=False retires the row.
        okrow = jnp.isfinite(vlog).all(axis=(1, 2))
        ok = jnp.where(emit0, ok & okrow, ok)

        tmax = jnp.argmax(vlog, axis=-1).astype(jnp.int32)    # [B, w]
        # The k verify positions of a row as k rows with the row's
        # params (row-major, so a reshape splits them again): a vmap
        # would batch the filter's "any row needs the sort" predicate
        # and turn its cond into a select that always sorts.
        per_pos = lambda a: jnp.repeat(a, k)
        pf_logits, sorted_v = filter_logits_and_flag(
            vlog[:, :k, :].reshape(b * k, -1), per_pos(temps),
            per_pos(top_ks), per_pos(top_ps), k_max)
        pf = jax.nn.softmax(pf_logits, axis=-1).reshape(b, k, -1)
        qf = jnp.transpose(q_all[:k], (1, 0, 2))              # [B, k, V]
        u = jax.vmap(lambda kk: jax.random.uniform(
            jax.random.fold_in(kk, w + 1), (k,)))(keys)       # [B, k]
        acc = accept_mask(win[:, 1:], pf, qf, u, greedy, tmax[:, :k])

        jidx = jnp.arange(w)
        acc_full = jnp.concatenate([jnp.ones((b, 1), bool), acc],
                                   axis=1)                    # [B, w]
        acc_prefix = jnp.cumprod(acc_full.astype(jnp.int32),
                                 axis=1).astype(bool)
        is_eos = (eos_ids >= 0)[:, None] & (win == eos_ids[:, None])
        no_prior_eos = (jnp.cumsum(is_eos.astype(jnp.int32), axis=1)
                        - is_eos.astype(jnp.int32)) == 0
        within_budget = (emitted[:, None] + jidx[None, :]
                         < budgets[:, None])
        emit_w = ((emit0 & okrow)[:, None] & acc_prefix
                  & no_prior_eos & within_budget)             # [B, w]
        e = emit_w.sum(axis=1).astype(jnp.int32)
        tok_out = jnp.where(emit_w, win, pad_id)
        emitted_new = emitted + e
        done = done | (emit_w & is_eos).any(axis=1) \
            | (emit0 & okrow & (emitted_new >= budgets))

        # Carried distribution for the next window: the plain target
        # logits after the last emitted token — or, when the stop was a
        # REJECTION (sampled rows only), the residual norm(max(p-q, 0))
        # in log space, flagged so the next t0 samples it raw.
        e1 = jnp.clip(e, 1, w)
        sel = jnp.take_along_axis(vlog, (e1 - 1)[:, None, None],
                                  axis=1)[:, 0, :]
        stop = jnp.minimum(e, w - 1)
        gat = lambda m: jnp.take_along_axis(m, stop[:, None],
                                            axis=1)[:, 0]
        rej = (emit0 & okrow & (e < w) & ~greedy & gat(no_prior_eos)
               & gat(within_budget) & ~gat(acc_full))
        ek = jnp.clip(e, 1, k)
        pf_e = jnp.take_along_axis(pf, (ek - 1)[:, None, None],
                                   axis=1)[:, 0, :]
        qf_e = jnp.take_along_axis(qf, (ek - 1)[:, None, None],
                                   axis=1)[:, 0, :]
        rlog = residual_logits(pf_e, qf_e)
        upd = emit0 & okrow
        last_new = jnp.where(upd[:, None],
                             jnp.where(rej[:, None], rlog, sel),
                             last_logits)
        residual_new = jnp.where(upd, rej, residual)

        # Keys advance by exactly e split steps — the classic
        # one-split-per-emit chain, so the carried stream is a function
        # of (seed, emitted count) alone.
        def adv(kk, j):
            nxt = jax.vmap(lambda key: jax.random.split(key, 2)[0])(kk)
            return jnp.where((j < e)[:, None], nxt, kk), None

        keys_new, _ = lax.scan(adv, keys, jnp.arange(w))

        return ((new_caches, dcaches, last_new, positions + e, keys_new,
                 done, ok, emitted_new, residual_new),
                (tok_out, emit_w, e, sorted0 | sorted_d.any() | sorted_v))

    def spec_step(variables, caches_all, dvariables, tables, dtables,
                  last_logits, positions, active, keys, temps, top_ks,
                  top_ps, eos_ids, budgets, residual):
        caches, dcaches = caches_all
        b = positions.shape[0]
        init = (caches, dcaches, last_logits, positions, keys,
                jnp.zeros((b,), bool),        # done (within this block)
                jnp.ones((b,), bool),         # ok   (health, carried)
                jnp.zeros((b,), jnp.int32),   # emitted (within block)
                residual)

        def scan_body(carry, _):
            return window(active, temps, top_ks, top_ps, eos_ids,
                          budgets, variables, dvariables, tables,
                          dtables, carry)

        if horizon == 1:
            carry, (tok_w, emit_m, e_w, full_sort) = scan_body(init, None)
            toks = tok_w[:, None, :]
            mask = emit_m[:, None, :]
            win_emitted = e_w[:, None]
        else:
            carry, (tok_s, emit_s, e_s, full_sort) = lax.scan(
                scan_body, init, None, length=horizon)
            toks = jnp.transpose(tok_s, (1, 0, 2))     # [B, H, w]
            mask = jnp.transpose(emit_s, (1, 0, 2))
            win_emitted = jnp.transpose(e_s, (1, 0))   # [B, H]
        (caches, dcaches, last_logits, positions, keys, done, ok,
         emitted, residual) = carry
        width = horizon * w
        tok_flat = toks.reshape(b, width)
        mask_flat = mask.reshape(b, width)
        # Compact each row's emitted tokens to a left-aligned prefix
        # (stable: emission order preserved) so the scheduler's
        # slice-at-emitted consumption works unchanged; everything past
        # a row's count is pad (masked to pad_id before the sort, so
        # the unemitted tail lands as pad already left-aligned).
        order = jnp.argsort(
            jnp.logical_not(mask_flat).astype(jnp.int32), axis=1,
            stable=True)
        tok_block = jnp.take_along_axis(
            jnp.where(mask_flat, tok_flat, pad_id), order, axis=1)
        # Windows of the block in which some filter sorted the vocabulary.
        full_sorts = jnp.sum(full_sort, dtype=jnp.int32)
        return (tok_block, emitted, ok, win_emitted, full_sorts,
                (caches, dcaches), last_logits, positions, keys,
                jnp.maximum(budgets - emitted, 0), residual)

    return spec_step
