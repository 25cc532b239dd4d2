"""Admission, retirement, and the serving iteration loop.

The scheduler owns everything request-shaped: a bounded admission
queue served by WEIGHTED FAIR QUEUEING across tenants within priority
lanes (submit past capacity fails fast — backpressure, not unbounded
memory; with every request in one lane and one tenant, the default,
WFQ degenerates to the classic bounded FIFO bit for bit), per-request
deadlines, and the continuous-batching iteration:

    admit waiters into free slots -> LAUNCH block k (up to
    ``decode_horizon`` tokens per row, one compiled dispatch) for all
    rows held now -> COLLECT block k-1 -> emit its tokens -> retire rows
    on EOS / max-new-tokens / deadline -> admit again (a slot freed by
    retirement is refilled in the SAME iteration, so capacity never
    idles while work is queued).

**One block in flight.** The launch and the collect are the two halves
of ONE ``engine.step(active)`` call a pass (serve/engine.py): the
scheduler asks the engine for its overlapped form at construction, so
everything the host does in a pass (emit, retire, admit, bind, upload,
launch) runs while the device runs a block, instead of beside an idle
one. The price is that a block comes back one call after its launch, and
the rows may have changed hands meanwhile. So the scheduler keeps,
beside each call, what was true at the launch (``_Launched``: which
request held each row, and when), and ``_emit_block`` serves a row only
if the SAME request still holds it: a slot retired and granted again
while its old block was in flight must not have the newcomer handed the
old row's tokens, nor retired by its ``ok == False``. A row stays in the
mask until its request retires on the host; the block launched meanwhile
emits nothing for it (its budget is spent, or its EOS zeroed it on the
device). ``_settle()`` brings the block in flight home and hands it out
without launching another: what every path does FIRST that reads or
moves a live row's device state, or retires rows outside a decode pass
(``_maybe_preempt``, ``export_parked`` / ``resume_parked`` /
``install_migrated`` / ``export_prefix`` / ``install_pulled``,
``cancel_remaining``, a ``KVBlocksExhausted`` victim, the bounded retry
after a failed call), and the pass in which the last live row retires
(so ``has_work()`` and ``run_until_idle()`` never leave a block behind).
Where the engine keeps launch-then-collect (speculative decoding) the
call returns its own block and none of this engages. A direct caller of
``Engine.step`` never sees the overlapped form unless it asks.

The decode consumes the engine's ``[B, H]`` token block: each live
row's tokens are sliced at its device-computed emitted count (overshoot
past EOS/budget never reaches here — it was dropped on device), events
stream per token, and retire/admit runs once per horizon, so the host
cost between dispatches is paid once per H tokens. Deadlines are
checked once per block, against the block's collect time — granularity
coarsens to one horizon, and a token streams one block after it was
sampled.

Telemetry flows through ``nezha_tpu.obs`` at the serving layer's
metrics of record: ``serve.ttft_s`` (submit -> first token, placed at
the row's position WITHIN its first block) and ``serve.tpot_s``
(``block_dt / tokens_emitted`` counted once per emitted token, so
percentiles stay comparable across horizon settings; a block's window
runs from its launch, or from the collect before it where that came
later, to its own collect) histograms,
``serve.host_gap_s`` (host time between one ``engine.step`` call's
return and the next one's start, minus the time inside
``Engine.prefill`` during it — the gap the decode horizon amortizes;
with a block in flight the device works through it) and ``serve.decode.horizon``
(tokens-per-dispatch ceiling in effect) histograms,
``serve.prefill.bucket_len`` (static pad width per prefill chunk — the
bucket-occupancy view), ``serve.queue_depth`` and
``serve.batch_occupancy`` gauges,
``serve.{admitted,rejected,expired,retired,tokens}_total``,
``serve.{errors,step_retries}_total``, ``serve.prefill.chunks_total`` and
``serve.engine.{blocks_overlapped,settles,stale_rows}_total`` counters, ``faults.injected_total`` (the chaos ledger), and the
layer spans of a pass (``obs.LAYER_SPANS``: ``serve.sched.*`` here,
``serve.engine.*`` around every decode block's dispatch and fetch) —
the names tools/check_telemetry_schema.py pins. With no run active
every call site is the registry's branch-only no-op.

Distributed tracing rides the same lifecycle: a request carrying a
``trace_id`` (router-minted and forwarded on the wire, or minted at
``submit`` when this scheduler is the admission edge) emits one
per-request span fragment per lifecycle stage — ``serve.queue_wait``
(submit -> admit), ``serve.prefill`` (+ the engine's per-chunk
``serve.prefill.chunk``), ``serve.park`` / ``serve.kv_export`` (the
migration handoff) and ``serve.decode`` (residency + the first-token
milestone) —
all stamped with the trace id so ``nezha-telemetry RUN_DIR --trace``
can stitch the fleet's fragments into one per-request timeline. Each
dispatch a request rode (``serve.decode_window``) is NOT a record of
its own: the report joins the request's ``serve.decode`` interval
against the engine's pass records (obs/report.py::decode_windows), so
what a decode pass writes does not grow with its rows.
Untraced (or sampled-out) requests emit ZERO extra spans, and with
telemetry disabled the whole layer stays branch-only no-op.

Failure isolation is request-scoped by design: a prefill exception or a
non-finite logit row retires ONLY the affected request
(``FinishReason.ERROR``, slot freed the same iteration) while the loop
keeps decoding everyone else, and a crashed ``engine.step`` gets one
bounded backoff retry before the failure surfaces. The fault-injection
layer (``nezha_tpu.faults``) manufactures all three on demand;
tests/test_faults.py proves zero slot leaks under a seeded chaos plan.
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import threading
import time
from typing import Callable, Deque, Dict, List, Optional, Sequence

import numpy as np

from nezha_tpu import faults, obs
from nezha_tpu.serve.engine import Engine
from nezha_tpu.serve.slots import KVBlocksExhausted


class QueueFull(Exception):
    """Admission queue at capacity — the backpressure signal. Callers
    should shed load or retry later (HTTP mode maps this to 503)."""


class TenantOverLimit(QueueFull):
    """One TENANT's queued share hit ``tenant_queue_cap`` — the typed
    per-tenant backpressure signal (PR 19). A subclass of
    :class:`QueueFull` so every existing handler still maps it to 503;
    callers that care about the distinction catch it first. Counted
    into ``serve.tenant_over_limit_total`` (and, like every shed,
    ``serve.rejected_total``)."""


# Priority classes, highest first — rank 0 outranks rank 2 when the
# preemption trigger and the WFQ tie-break compare lanes.
PRIORITIES = ("interactive", "batch", "background")
_PRIORITY_RANK = {p: i for i, p in enumerate(PRIORITIES)}

# Default WFQ admission-grant split (ServeConfig.priority_weights
# None): per 7 grants under full backlog, 4 interactive, 2 batch,
# 1 background — lower lanes are slowed, never starved.
_DEFAULT_WEIGHTS = (("interactive", 4), ("batch", 2), ("background", 1))


class FinishReason:
    EOS = "eos"
    LENGTH = "length"          # max_new_tokens reached
    DEADLINE = "deadline"      # expired (queued, mid-decode, or at the
                               # drain cutoff)
    ERROR = "error"            # prefill failure or non-finite logits —
                               # the request is retired, its slot freed,
                               # and the batch keeps decoding
    PREFILLED = "prefilled"    # prefill_only request: prompt KV computed
                               # and PARKED for migration — not an end
                               # state for the request, which decodes on
                               # whichever replica pulls (or resumes) it


@dataclasses.dataclass
class Request:
    """One generation request. ``deadline_s`` is a wall-clock budget in
    seconds from submit; expired requests are retired with whatever
    tokens they have (possibly none, if still queued)."""

    prompt: Sequence[int]
    max_new_tokens: int = 16
    temperature: float = 0.0
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    eos_id: Optional[int] = None
    seed: int = 0
    deadline_s: Optional[float] = None
    request_id: Optional[str] = None
    # Disaggregated serving (serve/migrate.py): prefill the prompt and
    # PARK the slot (blocks held under a TTL) instead of decoding — the
    # admission half of the two-phase KV handoff. The request finishes
    # with FinishReason.PREFILLED; decoding happens wherever the parked
    # KV is pulled to (or locally via resume_parked).
    prefill_only: bool = False
    # Distributed tracing: the fleet-wide trace id this request carries
    # (minted by the router at admission and forwarded on the wire, or
    # minted at submit when the field is ABSENT and a telemetry run is
    # active — subject to obs.set_trace_sample). "" = the router
    # already sampled this request OUT: honored as untraced, never
    # re-minted. Untraced requests' lifecycles emit ZERO extra spans.
    trace_id: Optional[str] = None
    # Multi-tenant scheduling (PR 19): the WFQ lane this request queues
    # in (one of PRIORITIES; the default keeps every pre-PR-19 caller
    # in one lane — exact FIFO) and the tenant whose fair share and
    # queue cap it counts against.
    priority: str = "interactive"
    tenant_id: str = "default"


@dataclasses.dataclass
class RequestResult:
    request_id: str
    tokens: List[int]
    finish_reason: str
    ttft_s: Optional[float]    # None when expired before the first token
    latency_s: float
    error: Optional[str] = None   # set for FinishReason.ERROR: what broke


@dataclasses.dataclass
class _Live:
    """Host bookkeeping for one occupied slot."""

    req: Request
    request_id: str
    submit_t: float
    deadline_t: Optional[float]
    tokens: List[int] = dataclasses.field(default_factory=list)
    ttft_s: Optional[float] = None
    # Distributed-tracing state (None everywhere for untraced requests):
    # the trace id plus the epoch-clock milestones the per-request
    # lifecycle spans are emitted from. Wall (epoch) time, not monotonic
    # — fragments from different processes must stitch on one clock.
    trace_id: Optional[str] = None
    submit_wall: Optional[float] = None     # submit()
    decode_t0_wall: Optional[float] = None  # prefill done / resume
    first_token_wall: Optional[float] = None
    park_wall: Optional[float] = None       # prefill_only park
    # Preemption ledger (PR 19): how many times this request has been
    # suspended mid-decode — capped by ServeConfig.preemption_budget so
    # one request cannot thrash between slot and host tier forever.
    preempt_count: int = 0


@dataclasses.dataclass
class _Launched:
    """What was true when a decode block was launched, kept until the
    block comes back (one ``engine.step`` call later in the overlapped
    form): which request held each row of its mask, and the launch's
    start on the monotonic clock with its epoch twin under a run dir."""

    rows: Dict[int, _Live]
    t0: float
    t0_wall: Optional[float]


def register_serve_instruments() -> None:
    """Pre-register (get-or-create) the full serving instrument set so
    every serving run's summary carries it — a run with zero rejections
    still reports ``rejected_total = 0`` (the stable schema
    tools/check_telemetry_schema.py pins). Called at scheduler
    construction; call again after a registry reset (e.g. a benchmark
    that starts its run AFTER warmup)."""
    for c in ("admitted", "rejected", "expired", "retired", "tokens",
              "errors", "step_retries"):
        obs.counter(f"serve.{c}_total")
    obs.counter("serve.prefill.chunks_total")
    # Flash-prefill kernel (PR 18): whether paged prefill chunks go
    # through the Pallas kernel (gauge re-set by the engine at init)
    # and the per-layer int8 K/V block writes its epilogue fused in
    # place of the gather/requant round-trip. Impl-invariant: the XLA
    # path and bf16 pools report 0s, never omit the names.
    obs.gauge("serve.prefill.kernel_active")
    obs.counter("serve.prefill.fused_writes_total")
    # Sequence-sharded prefill (PR 20): the mesh shards each prefill
    # chunk spans (0 = replicated mode, M = sequence mode on a 1xM
    # mesh; gauge re-set by the engine) and the ppermute hops ring-
    # variant chunks paid. Mode-invariant: replicated and ulysses runs
    # report 0s, never omit the names.
    obs.gauge("serve.prefill.seq_shards")
    obs.counter("serve.prefill.ring_hops_total")
    # The fault layer's injection count rides in every serving summary
    # (0 when no plan is active) so chaos runs and clean runs share one
    # schema — dashboards can divide errors by injections.
    obs.counter("faults.injected_total")
    # Paged-KV instruments (schema-pinned for every serving run):
    # blocks resident, requests that took cached prefix references
    # instead of re-prefilling, and copy-on-write block copies.
    obs.counter("serve.kv.prefix_hits_total")
    obs.counter("serve.kv.cow_copies_total")
    # The serving-side expert layer's load (a model with routed experts
    # only; the engine records them a step). Model-invariant 0s.
    obs.counter("serve.moe.pairs_total")
    obs.counter("serve.moe.held_pairs_total")
    # The experts' kernel (ops/pallas/moe_experts.py): (row tile, expert)
    # visits of the decode steps and the held experts they touched.
    obs.counter("serve.moe.expert_visits_total")
    obs.counter("serve.moe.experts_touched_total")
    obs.gauge("serve.moe.load_max_over_mean")
    # The residual path's maps (a model with hyper-connections only; the
    # engine records them a step): maps computed (rows x sublayers), and
    # how far the worst H_res so far lay from doubly stochastic.
    obs.counter("serve.mhc.maps_total")
    obs.gauge("serve.mhc.sinkhorn_residual_max")
    # Decode steps whose sampling sorted the whole vocabulary (a row's
    # nucleus wider than the k_max head; serve/sampling.py). 0 for
    # top-k traffic and for peaked top-p traffic.
    obs.counter("serve.sampling.full_sort_steps_total")
    # Cross-replica migration (disaggregated prefill/decode tiers,
    # serve/migrate.py): committed installs and their wire bytes —
    # migration GB/s is bytes / the router.migrate span durations.
    # Layout-invariant 0s on runs that never migrate.
    obs.counter("serve.kv.migrations_total")
    obs.counter("serve.kv.migration_bytes")
    obs.gauge("serve.kv.blocks_used")
    # The window layers' ring (0 for a model without window layers):
    # every taken slot holds its whole ring.
    obs.gauge("serve.kv.window_blocks_used")
    # The state group (0s for a model without recurrent-state layers):
    # every taken slot holds one entry; a prefill from offset 0 resets it.
    obs.gauge("serve.state.slots_bound")
    obs.gauge("serve.state.bytes_resident")
    obs.counter("serve.state.resets_total")
    # Tiered KV host spill (PR 15): trie blocks demoted to host RAM on
    # eviction instead of discarded, and blocks promoted back on a
    # returning prefix hit; occupancy gauges for the host-side LRU.
    # Layout/knob-invariant 0s on runs without a host tier.
    obs.counter("serve.kv.demotions_total")
    obs.counter("serve.kv.promotions_total")
    obs.gauge("serve.kv.host_blocks_used")
    obs.gauge("serve.kv.host_bytes_resident")
    # Fleet-wide KV reuse (PR 17, serve/fleetcache): requests that
    # reused cached prefix blocks, split by the tier the blocks came
    # from — own device trie, own host tier, or a sibling replica's
    # peer pull — plus the wire bytes peer pulls installed. Knob-
    # invariant 0s on single-replica / affinity-off runs, so every
    # serving summary renders the same "fleet kv:" line.
    obs.counter("serve.kv.fleet_hits_total")
    obs.counter("serve.kv.fleet_hits_device_total")
    obs.counter("serve.kv.fleet_hits_host_total")
    obs.counter("serve.kv.fleet_hits_peer_total")
    obs.counter("serve.kv.pull_bytes")
    # KV quantization instruments (schema-pinned, dtype
    # invariant): device bytes the resident KV actually holds (the
    # capacity lever int8 moves), the storage width in bits (8 = int8,
    # 16 = bf16, 32 = f32 — lets the report label the dtype), and the
    # per-block max-abs dequant error sampled at each prefill-chunk
    # write (empty on bf16 runs — nothing is quantized).
    obs.gauge("serve.kv.bytes_resident")
    obs.gauge("serve.kv.quant_bits")
    obs.histogram("serve.kv.quant_error")
    # Speculative decoding instruments (schema-pinned, 0/empty when the
    # knob is off so every serving summary shares one shape): draft
    # tokens proposed, draft tokens accepted, and the per-verify
    # accepted-prefix length histogram (tokens-per-verify = p50 + 1).
    obs.counter("serve.spec.draft_tokens_total")
    obs.counter("serve.spec.accepted_total")
    obs.histogram("serve.spec.accepted_len")
    # Tensor-sharded serving (serve/sharded, PR 14): the mesh size this
    # engine spans (1 = classic single-device) and the trace-shape
    # estimate of cross-shard collective payload (0 off-mesh) — every
    # serving summary carries both, so dashboards can split fleets by
    # topology without schema forks.
    obs.gauge("serve.mesh.devices")
    obs.counter("serve.mesh.collective_bytes")
    obs.gauge("serve.queue_depth")
    obs.gauge("serve.batch_occupancy")
    obs.histogram("serve.ttft_s")
    obs.histogram("serve.tpot_s")
    # Multi-tenant scheduling (PR 19): preempt/resume lifecycle
    # counters, the per-tenant cap's typed sheds, the live count of
    # suspended requests, and the per-priority-class TTFT split (the
    # registry has no labels, so the split is three pinned names the
    # report and /metrics render alongside the aggregate). Knob-
    # invariant: runs with preemption off and one lane report 0s /
    # empty splits, never omit the names.
    obs.counter("serve.preemptions_total")
    obs.counter("serve.resumes_total")
    obs.counter("serve.tenant_over_limit_total")
    obs.gauge("serve.preempted_live")
    for p in PRIORITIES:
        obs.histogram(f"serve.ttft_s.{p}")
    obs.histogram("serve.prefill.bucket_len")
    # Decode-horizon instruments: the host gap between consecutive step
    # dispatches (what a horizon > 1 amortizes over H tokens) and the
    # horizon each dispatch ran at (count = dispatches, so
    # tokens_total / count is the realized tokens-per-dispatch).
    obs.histogram("serve.host_gap_s")
    obs.histogram("serve.decode.horizon")
    # One decode block in flight (PR 37): blocks launched while the one
    # before them was still in flight (over serve.decode.horizon's count,
    # the share of a run that was overlapped), forced drains of the block
    # in flight (Engine.settle; its serve.engine.wait span says why), and
    # row results dropped because the row's request had changed while
    # its block was in flight and the result was not empty. 0s under
    # speculative decoding, which stays launch-then-collect.
    obs.counter("serve.engine.blocks_overlapped_total")
    obs.counter("serve.engine.settles_total")
    obs.counter("serve.engine.stale_rows_total")


class Scheduler:
    """Bounded-FIFO continuous-batching scheduler over an :class:`Engine`.

    ``on_token(request_id, token)`` streams each decoded token;
    ``on_finish(result)`` fires at retirement. Both run on the thread
    driving :meth:`step`. ``submit`` is thread-safe (HTTP handlers call
    it concurrently with the decode loop).

    ``step_retry_backoff_s`` is the pause before the single
    ``engine.step`` retry — long enough for a transient to clear, short
    enough that in-flight TPOT survives one hiccup.
    """

    step_retry_backoff_s = 0.05

    # How long a parked (prefill_only) slot waits for its migration
    # pull / ACK / resume before the scheduler reclaims it — the
    # leak-proofing backstop of the two-phase handoff: a decode replica
    # that pulled and died, or an ACK lost on the wire, costs the
    # source at most this window of held blocks.
    parked_ttl_s = 60.0

    # Cross-thread state and the lock that guards it — the declaration
    # nezha-lint's lock-discipline rule enforces: every write to these
    # outside `with self._lock` (or a method marked `[holds: _lock]`,
    # meaning the caller already holds it) fails the build. submit()
    # runs on HTTP handler threads against the decode loop's step(),
    # and the migration endpoints (export/ack/resume) run on handler
    # threads too.
    _LOCK_GUARDED = {"_lanes": "_lock", "_lane_vt": "_lock",
                     "_lane_rr": "_lock", "_queued_n": "_lock",
                     "_vt_now": "_lock", "_preempted": "_lock",
                     "preemptions": "_lock", "resumes": "_lock",
                     "_live": "_lock",
                     "results": "_lock", "_host_gap_t": "_lock",
                     "_gap_prefill_s": "_lock",
                     "_launched": "_lock", "_collect_t": "_lock",
                     "_settled_tokens": "_lock",
                     "_parked": "_lock", "_digest_cache": "_lock"}

    def __init__(self, engine: Engine,
                 on_token: Optional[Callable[[str, int], None]] = None,
                 on_finish: Optional[Callable[[RequestResult], None]] = None):
        self.engine = engine
        self.on_token = on_token
        self.on_finish = on_finish
        self.queue_capacity = engine.cfg.queue_capacity
        # WFQ admission state (PR 19): priority lane -> tenant -> FIFO
        # deque, the per-lane virtual-time clock the weighted pick
        # compares, the per-lane tenant round-robin ring (a tenant is
        # in its lane dict and ring exactly while its deque is
        # non-empty), the total queued count, and the virtual time of
        # the last grant (an idling lane re-enters at this clock so it
        # can never burst a backlog of unearned credit).
        self._lanes: Dict[str, Dict[str, Deque[_Live]]] = {}
        self._lane_vt: Dict[str, float] = {}
        self._lane_rr: Dict[str, Deque[str]] = {}
        self._queued_n = 0
        self._vt_now = 0.0
        self._weights = dict(engine.cfg.priority_weights
                             or _DEFAULT_WEIGHTS)
        # Requests suspended mid-decode by the preemption trigger:
        # request_id -> _Live (no slot held — their KV sits in the
        # prefix trie / host tier until resume re-admits them).
        self._preempted: Dict[str, _Live] = {}
        # Optional fast-path SLO signal (PR 16's tracker): when set
        # (cli/serve wires the first interactive serve.ttft_s --slo
        # spec), _decode feeds it per interactive first token and a
        # burn rate > 1 lifts the one-preemption-per-admission-pass
        # quota — assigned once at startup, like on_token/on_finish.
        self.slo_tracker = None
        # Plain preemption ledgers (obs counters only count inside a
        # run; these always do — benchmarks read them directly).
        self.preemptions = 0
        self.resumes = 0
        self._live: Dict[int, _Live] = {}          # slot -> request state
        # Parked prefill_only requests awaiting their migration pull
        # (or a local-decode resume): request_id -> (slot, live,
        # expires_t). Slots here hold their prompt blocks but never
        # decode; step() reclaims entries past their TTL.
        self._parked: Dict[str, tuple] = {}
        # Lazily built fleet digest (PR 17) — created on the first
        # /healthz hit that asks for one, recreated when the knobs
        # change (the CLI passes them per call).
        self._digest_cache = None
        self._lock = threading.RLock()
        self._ids = itertools.count()
        self.results: Dict[str, RequestResult] = {}
        # End timestamp of the previous decode dispatch, None when the
        # loop was idle in between — serve.host_gap_s only measures the
        # host gap WITHIN continuous decoding, never idle waits.
        self._host_gap_t: Optional[float] = None
        # Host seconds inside Engine.prefill since that timestamp
        # (_prefill sums them): prefill time, not gap.
        self._gap_prefill_s = 0.0
        # One decode block in flight: this scheduler can hand a block's
        # tokens to the requests that held its rows at the LAUNCH, so it
        # asks the engine for the overlapped form of step() (granted
        # where the step is the classic one; _decode reads what the
        # engine says it does). _launched is the launch the engine's
        # block in flight came from (None: nothing in flight),
        # _collect_t when the last block came back, and _settled_tokens
        # what forced drains delivered since the last pass reported its
        # count.
        engine.overlap_blocks()
        self._launched: Optional[_Launched] = None
        self._collect_t: Optional[float] = None
        self._settled_tokens = 0
        register_serve_instruments()
        pool = engine.pool
        obs.gauge("serve.kv.quant_bits").set(
            8 if pool.quantized
            else 8 * int(np.dtype(pool.dtype).itemsize))
        # 1 for the classic engine; the sharded engine set M already at
        # its own construction — re-set here so the gauge is correct
        # whichever was built first.
        obs.gauge("serve.mesh.devices").set(
            getattr(engine, "mesh_devices", 1))

    # ------------------------------------------------------- admission
    def submit(self, req: Request) -> str:
        """Enqueue; returns the request id. Raises :class:`QueueFull`
        past capacity and ``ValueError`` for requests that can never be
        served (prompt too long for the static prefill width, or
        prompt + max_new_tokens past the slot's KV capacity)."""
        cfg = self.engine.cfg
        n = len(req.prompt)
        # Admission limit is the slot's KV capacity, not the prefill
        # width: prompts past max_prefill_len prefill in chunks
        # (engine.py), so only max_len bounds what can be served.
        if n < 1:
            raise ValueError("prompt must be non-empty")
        if req.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if n + req.max_new_tokens > cfg.max_len:
            raise ValueError(
                f"prompt ({n}) + max_new_tokens ({req.max_new_tokens}) "
                f"exceeds max_len {cfg.max_len}")
        # A request whose prefill span (or full resident footprint)
        # needs more blocks than the pool could EVER free can never
        # be served — bounce it here, before it wedges the queue
        # head forever waiting for blocks that cannot exist.
        pool = self.engine.pool
        need = max(self.engine.prefill_blocks_needed(n),
                   pool.blocks_for_span(n + req.max_new_tokens))
        if need > pool.max_request_blocks:
            raise ValueError(
                f"request needs {need} KV blocks "
                f"(block_size {pool.block_size}) but the pool can "
                f"bind at most {pool.max_request_blocks} per "
                f"request — raise kv_num_blocks or lower the "
                f"request's footprint")
        vocab = self.engine.vocab
        if not all(0 <= t < vocab for t in req.prompt):
            # Admission IS the validation boundary (the engine trusts its
            # caller): a bad id surfacing inside prefill/step would kill
            # the decode loop with other requests in flight — and would
            # have allocated a slot first — instead of bouncing this
            # submit before any resource is held.
            raise ValueError(f"prompt ids must be in [0, {vocab})")
        if req.priority not in _PRIORITY_RANK:
            raise ValueError(
                f"priority must be one of {PRIORITIES}, got "
                f"{req.priority!r}")
        if not isinstance(req.tenant_id, str) or not req.tenant_id:
            raise ValueError(
                f"tenant_id must be a non-empty string, got "
                f"{req.tenant_id!r}")
        # Trace adoption: a request arriving with a router-minted trace
        # id keeps it; the empty string means "routed, and the ROUTER's
        # sample knob rolled it out" — the minting edge already
        # decided, so re-minting here would double the effective
        # sample rate and leave root-less traces. Only a request with
        # NO verdict at all (trace_id None: direct submit, stdio, a
        # pre-tracing client) makes this scheduler the admission edge
        # that mints — None again when no run is active or the local
        # sample knob rolls it out, in which case the whole lifecycle
        # emits zero extra spans.
        if req.trace_id == "":
            trace_id = None
        elif req.trace_id is not None:
            trace_id = req.trace_id
        else:
            trace_id = obs.mint_trace_id()
        with self._lock:
            if self._queued_n >= self.queue_capacity:
                obs.counter("serve.rejected_total").inc()
                raise QueueFull(
                    f"admission queue at capacity {self.queue_capacity}")
            cap = cfg.tenant_queue_cap
            if cap is not None and self._tenant_depth(
                    req.tenant_id) >= cap:
                # The per-tenant bound fails typed — one tenant's burst
                # never reads as a full fleet to everyone else. Still a
                # shed, so rejected_total keeps meaning ALL sheds.
                obs.counter("serve.tenant_over_limit_total").inc()
                obs.counter("serve.rejected_total").inc()
                raise TenantOverLimit(
                    f"tenant {req.tenant_id!r} at queue cap {cap}")
            rid = req.request_id or f"req-{next(self._ids)}"
            now = time.monotonic()
            self._queue_push(_Live(
                req=req, request_id=rid, submit_t=now,
                deadline_t=None if req.deadline_s is None
                else now + req.deadline_s,
                trace_id=trace_id,
                submit_wall=time.time() if trace_id else None))
            obs.gauge("serve.queue_depth").set(self._queued_n)
        return rid

    # ------------------------------------------------------- iteration
    def step(self) -> int:
        """One serving iteration. Returns the number of tokens decoded
        (0 when fully idle)."""
        # An idle poll (the serving loops call step() every 2 ms with
        # nothing to do) leaves no span record behind: only a pass with
        # work mirrors its span into the registry.
        with self._lock, obs.annotate(
                "serve.sched.pass",
                record=bool(self._live or self._queued_n
                            or self._preempted),
                live=len(self._live), queued=self._queued_n):
            self._expire_queued()
            self._expire_parked()
            self._expire_preempted()
            self._admit()
            if self._live:
                emitted = self._decode()
            else:
                emitted = 0
                self._host_gap_t = None     # idle: no gap to measure
            self._admit()          # refill slots freed by retirement
            emitted += self._settled_tokens
            self._settled_tokens = 0
            obs.gauge("serve.queue_depth").set(self._queued_n)
            obs.gauge("serve.batch_occupancy").set(
                self.engine.pool.occupancy)
            obs.gauge("serve.kv.blocks_used").set(
                self.engine.pool.blocks_used)
            obs.gauge("serve.kv.window_blocks_used").set(
                self.engine.pool.window_blocks_used)
            obs.gauge("serve.state.slots_bound").set(
                self.engine.pool.state_slots_bound)
            obs.gauge("serve.state.bytes_resident").set(
                self.engine.pool.state_bytes_resident)
            obs.gauge("serve.kv.bytes_resident").set(
                self.engine.pool.bytes_resident
                + self.engine.pool.window_bytes_resident
                + self.engine.pool.state_bytes_resident)
            obs.gauge("serve.kv.host_blocks_used").set(
                self.engine.pool.host_blocks_used)
            obs.gauge("serve.kv.host_bytes_resident").set(
                self.engine.pool.host_bytes_resident)
            return emitted

    def run_until_idle(self, max_iters: Optional[int] = None) -> int:
        """Drive :meth:`step` until queue and slots are empty; returns
        the iteration count."""
        iters = 0
        while self.has_work():
            self.step()
            iters += 1
            if max_iters is not None and iters >= max_iters:
                break
        return iters

    def has_work(self) -> bool:
        with self._lock:
            return bool(self._queued_n or self._live or self._preempted)

    @property
    def parked_count(self) -> int:
        with self._lock:
            return len(self._parked)

    @property
    def preempted_count(self) -> int:
        with self._lock:
            return len(self._preempted)

    @property
    def queue_depth(self) -> int:
        """Current admission-queue length (all lanes, all tenants).
        Pacing clients (the stdio reader, closed-loop benchmarks)
        should wait for room here instead of hammering submit() —
        every QueueFull counts into ``serve.rejected_total``, which
        must mean SHED REQUESTS, not retry polls."""
        with self._lock:
            return self._queued_n

    def tenant_queue_depths(self) -> Dict[str, int]:
        """Per-tenant queued counts across every lane — the
        ``/healthz`` / ``/stats`` view operators size tenant_queue_cap
        against. Empty when nothing is queued."""
        with self._lock:
            out: Dict[str, int] = {}
            for lane in self._lanes.values():
                for tenant, dq in lane.items():
                    out[tenant] = out.get(tenant, 0) + len(dq)
            return out

    # ----------------------------------------------- WFQ queue plumbing
    # Invariant: a tenant appears in its lane's dict and round-robin
    # ring exactly while its deque is non-empty, and a priority key
    # appears in _lanes/_lane_rr exactly while the lane holds work —
    # so ring[0] always names a servable tenant. _lane_vt persists
    # across idleness (clamped forward by _queue_push).

    def _tenant_depth(self, tenant: str) -> int:
        """[holds: _lock]"""
        return sum(len(lane[tenant]) for lane in self._lanes.values()
                   if tenant in lane)

    def _queue_push(self, live: _Live) -> None:
        """[holds: _lock]"""
        pri, tenant = live.req.priority, live.req.tenant_id
        lane = self._lanes.setdefault(pri, {})
        if not lane:
            # The lane was idle: re-enter at the current virtual time,
            # never behind it — an empty lane earns no credit.
            self._lane_vt[pri] = max(self._lane_vt.get(pri, 0.0),
                                     self._vt_now)
        dq = lane.get(tenant)
        if dq is None:
            lane[tenant] = dq = collections.deque()
            self._lane_rr.setdefault(
                pri, collections.deque()).append(tenant)
        dq.append(live)
        self._queued_n += 1

    def _pick_lane(self) -> Optional[str]:
        """[holds: _lock] The non-empty lane with the smallest virtual
        time — the weighted-fair pick; PRIORITIES order breaks ties,
        so interactive wins an exact draw."""
        best = None
        for pri in PRIORITIES:
            if pri not in self._lanes:
                continue
            vt = self._lane_vt.get(pri, 0.0)
            if best is None or vt < best[0]:
                best = (vt, pri)
        return None if best is None else best[1]

    def _peek_next(self) -> Optional[_Live]:
        """[holds: _lock] The request _pop_next would grant next,
        without granting it (the admission loop's block-budget peek)."""
        pri = self._pick_lane()
        if pri is None:
            return None
        return self._lanes[pri][self._lane_rr[pri][0]][0]

    def _pop_next(self) -> Optional[_Live]:
        """[holds: _lock] Grant one admission: pop the WFQ pick,
        advance its lane's virtual clock by 1/weight, and rotate the
        lane's tenant ring (equal-share round robin within a lane)."""
        pri = self._pick_lane()
        if pri is None:
            return None
        ring = self._lane_rr[pri]
        tenant = ring[0]
        dq = self._lanes[pri][tenant]
        live = dq.popleft()
        self._queued_n -= 1
        ring.rotate(-1)
        if not dq:
            del self._lanes[pri][tenant]
            ring.remove(tenant)
            if not self._lanes[pri]:
                del self._lanes[pri]
                del self._lane_rr[pri]
        vt = self._lane_vt.get(pri, 0.0)
        self._vt_now = max(self._vt_now, vt)
        self._lane_vt[pri] = vt + 1.0 / self._weights[pri]
        return live

    # -------------------------------------------------------- internals
    def _expire_queued(self) -> None:
        """[holds: _lock] — step() calls this inside the lock."""
        now = time.monotonic()
        for pri in list(self._lanes):
            lane = self._lanes[pri]
            ring = self._lane_rr[pri]
            for tenant in list(lane):
                kept: Deque[_Live] = collections.deque()
                for live in lane[tenant]:
                    if (live.deadline_t is not None
                            and now >= live.deadline_t):
                        obs.counter("serve.expired_total").inc()
                        self._finish(live, FinishReason.DEADLINE)
                        self._queued_n -= 1
                    else:
                        kept.append(live)
                if kept:
                    lane[tenant] = kept
                else:
                    del lane[tenant]
                    ring.remove(tenant)
            if not lane:
                del self._lanes[pri]
                del self._lane_rr[pri]

    def _expire_parked(self) -> None:
        """[holds: _lock] — step() calls this inside the lock. The park
        TTL is what makes the two-phase handoff leak-proof against a
        decode replica that pulled and died before ACKing (or an ACK
        lost on the wire): the source reclaims the slot and its blocks
        itself. The request's "prefilled" answer was already delivered;
        this is resource reclamation, counted like any other deadline
        miss."""
        now = time.monotonic()
        for rid in [r for r, (_, _, exp) in self._parked.items()
                    if now >= exp]:
            slot, live, _ = self._parked.pop(rid)
            self.engine.pool.free(slot)
            self._emit_park_span(live, "expired")
            obs.counter("serve.expired_total").inc()
            obs.counter("serve.retired_total").inc()

    def _expire_preempted(self) -> None:
        """[holds: _lock] — step() calls this inside the lock. A
        deadline keeps ticking while a request is suspended: it
        retires here with whatever tokens it already has, counted like
        any other deadline miss (and into ``retired_total`` — it WAS
        admitted once)."""
        now = time.monotonic()
        expired = [r for r, l in self._preempted.items()
                   if l.deadline_t is not None and now >= l.deadline_t]
        for rid in expired:
            live = self._preempted.pop(rid)
            obs.counter("serve.expired_total").inc()
            obs.counter("serve.retired_total").inc()
            self._finish(live, FinishReason.DEADLINE)
        if expired:
            obs.gauge("serve.preempted_live").set(len(self._preempted))

    # ------------------------------------------------------- preemption
    def _peek_preempted(self) -> Optional[_Live]:
        """[holds: _lock] The suspended request resume would re-admit
        next: highest priority first, oldest submit within it."""
        if not self._preempted:
            return None
        return min(self._preempted.values(),
                   key=lambda l: (_PRIORITY_RANK[l.req.priority],
                                  l.submit_t, l.request_id))

    def _pop_preempted(self, request_id: str) -> _Live:
        """[holds: _lock]"""
        live = self._preempted.pop(request_id)
        obs.gauge("serve.preempted_live").set(len(self._preempted))
        return live

    def _slo_burning(self) -> bool:
        """[holds: _lock] True when the wired interactive-TTFT SLO
        tracker is burning its error budget faster than it earns it —
        the PR 16 control signal that lifts the gentle one-preemption-
        per-pass quota."""
        return (self.slo_tracker is not None
                and self.slo_tracker.burn_rate() > 1.0)

    def _maybe_preempt(self, target: _Live, already: int) -> bool:
        """[holds: _lock] Try to free capacity for ``target`` by
        preempting one live decode of STRICTLY lower priority (lowest
        class first, least-progressed row within it) whose
        ``preemption_budget`` is not exhausted. Gentle by default —
        one preemption per admission pass — unless the interactive SLO
        is burning, when the quota opens to the whole batch. False
        when the knob is off, no victim qualifies, or the
        ``scheduler.preempt`` drill vetoed the suspend (the victim
        just keeps decoding)."""
        cfg = self.engine.cfg
        if not cfg.preemption:
            return False
        if already >= (len(self._live) if self._slo_burning() else 1):
            return False
        rank = _PRIORITY_RANK[target.req.priority]

        def pick():
            victim = None
            for slot, live in self._live.items():
                if _PRIORITY_RANK[live.req.priority] <= rank:
                    continue
                if live.preempt_count >= cfg.preemption_budget:
                    continue
                key = (-_PRIORITY_RANK[live.req.priority],
                       len(live.tokens), slot)
                if victim is None or key < victim[0]:
                    victim = (key, slot, live)
            return victim

        if pick() is None:
            return False
        # A victim is suspended with every token its KV holds, and chosen
        # by its progress: first bring the block in flight home.
        self._settle("preempt")
        victim = pick()
        if victim is None:
            # whoever qualified finished in the settled block: capacity
            # came free without a suspension
            return True
        return self._preempt(victim[1], victim[2])

    def _preempt(self, slot: int, live: _Live) -> bool:
        """[holds: _lock] Suspend one live decode: index its bound
        blocks (prompt + every emitted token) into the prefix trie —
        where admission pressure can LRU-evict them and, with a host
        tier, demote them through the serve.kv.demotions_total path —
        free the slot, and park the request in ``_preempted`` for
        resume. With the cache off (or kv_eviction="none", where trie
        refs would pin blocks forever) nothing is indexed: resume pays a
        cold re-prefill, trading compute instead of leaking capacity. The ``scheduler.preempt``
        fault point fires FIRST: an injected error is the typed
        degradation drill — the victim simply keeps decoding."""
        try:
            faults.point("scheduler.preempt")
        except Exception:
            return False
        pool = self.engine.pool
        with obs.span("serve.preempt_s", request_id=live.request_id,
                      priority=live.req.priority,
                      tokens=len(live.tokens)):
            if pool.prefix_cache_enabled and pool.eviction == "lru":
                pool.register_prefix(
                    slot, list(live.req.prompt) + live.tokens)
            del self._live[slot]
            pool.free(slot)
        live.preempt_count += 1
        self._preempted[live.request_id] = live
        self.preemptions += 1
        obs.counter("serve.preemptions_total").inc()
        obs.gauge("serve.preempted_live").set(len(self._preempted))
        return True

    def _resume_one(self, live: _Live) -> None:
        """[holds: _lock] Re-admit one preempted request: prefill its
        full context (prompt + emitted tokens) into a fresh slot with
        the REMAINING token budget and rejoin the batch. Greedy decode
        is deterministic given the context, so the resumed stream is
        bit-identical to an uninterrupted run; full blocks indexed at
        preemption prefix-hit the trie (or promote back from the host
        tier) instead of recomputing. A prefill failure retires the
        request typed, exactly like admission."""
        pool = self.engine.pool
        self._pop_preempted(live.request_id)
        slot = pool.alloc()
        req = live.req
        context = list(req.prompt) + live.tokens
        try:
            with obs.trace_context(live.trace_id):
                with obs.span("serve.prefill",
                              request_id=live.request_id,
                              prompt_len=len(context), resumed=True):
                    self._prefill(
                        slot, context, seed=req.seed,
                        temperature=req.temperature, top_k=req.top_k,
                        top_p=req.top_p, eos_id=req.eos_id,
                        max_new_tokens=(req.max_new_tokens
                                        - len(live.tokens)))
        except Exception as e:
            pool.free(slot)
            obs.counter("serve.errors_total").inc()
            # Admitted once at first grant — balance with a retirement.
            obs.counter("serve.retired_total").inc()
            self._finish(live, FinishReason.ERROR,
                         error=f"resume prefill failed: "
                               f"{type(e).__name__}: {e}")
            return
        self.resumes += 1
        obs.counter("serve.resumes_total").inc()
        if live.trace_id is not None:
            live.decode_t0_wall = time.time()
        self._live[slot] = live

    def _prefill(self, slot: int, tokens, **kwargs) -> None:
        """[holds: _lock] ``Engine.prefill``, its host time summed into
        ``_gap_prefill_s`` (what ``serve.host_gap_s`` leaves out), a
        failed one included."""
        t0 = time.monotonic()
        try:
            self.engine.prefill(slot, tokens, **kwargs)
        finally:
            self._gap_prefill_s += time.monotonic() - t0

    def _admit(self) -> None:
        """[holds: _lock] — step() calls this inside the lock. One
        admission pass under its ``serve.sched.admit`` span."""
        with obs.annotate("serve.sched.admit",
                          record=bool(self._queued_n
                                      or self._preempted)) as ann:
            ann.set(admitted=self._admit_pass())

    def _admit_pass(self) -> int:
        """[holds: _lock] Grant free slots to the WFQ pick among queued
        requests and resumable preempted ones (a preempted request
        outranks a queued pick of equal or lower priority — it is
        older, already-admitted work whose KV may still be cached),
        preempting a strictly-lower-priority live decode when the pick
        cannot get a slot or its blocks any other way. Returns the
        number of slots granted (each one a prefill)."""
        pool = self.engine.pool
        preempts = granted = 0
        while True:
            cand = self._peek_next()
            pre = self._peek_preempted()
            use_pre = pre is not None and (
                cand is None or _PRIORITY_RANK[pre.req.priority]
                <= _PRIORITY_RANK[cand.req.priority])
            target = pre if use_pre else cand
            if target is None:
                return granted
            if not pool.num_free:
                # Slot pressure: make room by suspending a lower-
                # priority live decode — or wait for retirement.
                if not self._maybe_preempt(target, preempts):
                    return granted
                preempts += 1
                continue
            # Admission budget is FREE BLOCKS, not free slots: only
            # admit the pick if its worst-case (no prefix hit)
            # prefill binding fits the free list plus what cache
            # eviction could reclaim. The worst case also COVERS a
            # host-tier promotion: a promoted span allocates
            # exactly the device blocks a cold prefill of that
            # span would have bound (promotion substitutes a
            # host->device copy for recompute, never extra
            # footprint), so promotable requests need no separate
            # budget line. A resumed request budgets its full
            # context (prompt + emitted tokens). Otherwise wait —
            # live rows retire and release blocks, and lane order
            # holds (skipping ahead would starve long prompts).
            ctx = len(target.req.prompt) + (len(target.tokens)
                                            if use_pre else 0)
            need = self.engine.prefill_blocks_needed(ctx)
            if pool.available_blocks() < need:
                if self._maybe_preempt(target, preempts):
                    # The victim's blocks moved to the trie (or
                    # the free list): re-check the budget.
                    preempts += 1
                    continue
                if not self._live:
                    # Nothing in flight will EVER free more blocks
                    # (with kv_eviction="none" the prefix cache
                    # pins its blocks permanently): waiting would
                    # livelock, so retire the pick with a typed
                    # error instead — later, smaller requests may
                    # still be servable.
                    if use_pre:
                        # Already counted admitted once — balance
                        # the books with a retirement.
                        self._pop_preempted(target.request_id)
                        obs.counter("serve.retired_total").inc()
                    else:
                        self._pop_next()
                    obs.counter("serve.errors_total").inc()
                    self._finish(
                        target, FinishReason.ERROR,
                        error=f"kv blocks exhausted: need {need}, "
                              f"{pool.available_blocks()} "
                              f"reclaimable, {pool.blocks_used} "
                              f"in use (kv_eviction="
                              f"{pool.eviction!r})")
                    continue
                return granted
            granted += 1
            if use_pre:
                self._resume_one(target)
            else:
                self._admit_one()

    def _admit_one(self) -> None:
        """[holds: _lock] Grant the WFQ pick its slot and prefill it —
        the per-request tail of the admission pass (_admit checked the
        slot and block budgets first)."""
        pool = self.engine.pool
        live = self._pop_next()
        slot = pool.alloc()
        req = live.req
        if live.trace_id is not None:
            # Queue wait is only measurable retroactively (submit ->
            # this admission) — the first stitched-timeline segment
            # after the router hop.
            obs.emit_span("serve.queue_wait", live.submit_wall,
                          time.time(), trace_id=live.trace_id,
                          request_id=live.request_id)
        try:
            # The ambient trace context makes serve.prefill (and the
            # engine's per-chunk serve.prefill.chunk spans beneath
            # it) carry the request's trace id; a no-op for
            # untraced requests.
            with obs.trace_context(live.trace_id):
                with obs.span("serve.prefill",
                              request_id=live.request_id,
                              prompt_len=len(req.prompt)):
                    self._prefill(
                        slot, req.prompt, seed=req.seed,
                        temperature=req.temperature, top_k=req.top_k,
                        top_p=req.top_p, eos_id=req.eos_id,
                        max_new_tokens=req.max_new_tokens)
        except Exception as e:
            # submit() pre-validates the request SHAPE, but runtime/
            # XLA errors (OOM-ish transients, injected faults) can
            # still surface here — and one bad request must never
            # kill the decode loop with neighbors in flight. Free
            # the slot, retire the request as an ERROR, keep
            # admitting. (The span recorded the exception type.)
            pool.free(slot)
            obs.counter("serve.errors_total").inc()
            self._finish(live, FinishReason.ERROR,
                         error=f"prefill failed: "
                               f"{type(e).__name__}: {e}")
            return
        obs.counter("serve.admitted_total").inc()
        if req.prefill_only:
            # Disaggregation: park the prefilled slot for the
            # migration pull instead of decoding. The request
            # finishes PREFILLED (its waiter gets the handle); the
            # slot holds its prompt blocks until kv_ack / resume /
            # TTL. A duplicate id would orphan the first park's
            # slot, so it is a typed error.
            if live.request_id in self._parked:
                pool.free(slot)
                obs.counter("serve.errors_total").inc()
                self._finish(live, FinishReason.ERROR,
                             error=f"request {live.request_id!r} "
                                   f"already parked")
                return
            if live.trace_id is not None:
                live.park_wall = time.time()
            self._parked[live.request_id] = (
                slot, live, time.monotonic() + self.parked_ttl_s)
            self._finish(live, FinishReason.PREFILLED)
            return
        if live.trace_id is not None:
            live.decode_t0_wall = time.time()
        self._live[slot] = live

    def _decode(self) -> int:
        """[holds: _lock] — step() calls this inside the lock. One
        ``engine.step`` call: in the overlapped form it launches the
        block of this pass's rows and brings back the block of the pass
        before, whose tokens go to the requests that held its rows
        then."""
        # Occupancy OF THIS DECODE, folded into the metric.* histogram
        # the report renders percentiles from (the same name a
        # record_metrics stream would fold into) — the gauge alone only
        # keeps the final value, which is 0 for any drained server.
        obs.histogram("metric.batch_occupancy").observe(
            len(self._live) / self.engine.cfg.max_batch_size)
        # Wall-clock twin of the dispatch window's monotonic start,
        # taken only under a run dir: a traced request's first-token
        # milestone is stitched on the epoch clock across processes.
        t0_wall = time.time() if obs.enabled() else None
        t0 = time.monotonic()
        if self._host_gap_t is not None:
            # Host time since the previous engine.step call returned,
            # MINUS the time spent inside Engine.prefill since then (its
            # serve.engine.prefill span): the retire/admit/stream pass
            # alone — the per-dispatch cost a horizon > 1 spreads over
            # H tokens. An admission's prefill is work of its own (4-10
            # ms of host time a prompt on a v5e, PERF.md PR 24, more
            # where its dispatch waits for the device), not gap.
            obs.histogram("serve.host_gap_s").observe(
                t0 - self._host_gap_t - self._gap_prefill_s)

        def _dispatch():
            # KV block exhaustion (genuine, or an injected serve.kv.bind
            # fault) is TYPED BACKPRESSURE, not an engine failure: retire
            # only the victim row — freeing its blocks — and redial with
            # the survivors. The block in flight comes home first: the
            # victim keeps every token already decoded for it (and may
            # turn out to have finished there). Convergence is
            # guaranteed (every retirement releases blocks); None means
            # nobody is left to decode.
            while self._live:
                active = np.zeros((self.engine.cfg.max_batch_size,), bool)
                active[list(self._live)] = True
                try:
                    return self.engine.step(active)
                except KVBlocksExhausted as e:
                    slot = e.slot
                    if slot is None or slot not in self._live:
                        raise
                    self._settle("kv_exhausted")
                    victim = self._live.pop(slot, None)
                    if victim is None:      # finished in the settled block
                        continue
                    self.engine.pool.free(slot)
                    obs.counter("serve.errors_total").inc()
                    obs.counter("serve.retired_total").inc()
                    self._finish(victim, FinishReason.ERROR,
                                 error=f"kv blocks exhausted: {e}")
            return None

        try:
            out = _dispatch()
        except Exception:
            # One bounded retry with backoff: a transient step crash
            # (preempted device, injected fault) must not retire
            # every in-flight request. A second consecutive failure
            # surfaces to the caller — that is a dead engine, not a
            # hiccup. (If the first dispatch died AFTER consuming
            # its donated cache buffers the retry fails fast on the
            # donation error and surfaces the same way.) The retry
            # starts from a settled engine: what was in flight before
            # the failed call is collected and handed out first.
            obs.counter("serve.step_retries_total").inc()
            time.sleep(self.step_retry_backoff_s)
            self._settle("retry")
            out = _dispatch()
        if out is None:
            self._host_gap_t = None
            return 0
        tokens, block_emitted = out
        now = time.monotonic()
        self._host_gap_t = now
        self._gap_prefill_s = 0.0
        # The call's own launch; overlapped, the block it brought back
        # is the one the call before it launched.
        launched = _Launched(dict(self._live), t0, t0_wall)
        if self.engine.overlapped:
            launched, self._launched = self._launched, (
                launched if self.engine.in_flight else None)
        emitted = 0
        if launched is not None:
            emitted = self._emit(tokens, block_emitted, launched, now)
        if not self._live:
            # The block retired the whole batch. What was launched for
            # its rows meanwhile holds nothing for anybody: collect it
            # now, so that has_work() and run_until_idle() never leave a
            # block behind. And the next decode only happens after new
            # admissions, which may be arbitrarily later (open-loop
            # callers gate step() on has_work(), so the idle reset in
            # step() never runs for them) — a gap measured across that
            # wait would be idle time, not host overhead.
            self._settle("idle")
            self._host_gap_t = None
        return emitted

    def _settle(self, reason: str) -> None:
        """[holds: _lock] Bring the block in flight home without
        launching another, and hand its tokens out: what every path
        does first that reads or moves a live row's state on the device
        or retires rows from outside a decode pass (preemption, the
        migration calls, a drain, a block-exhaustion victim, the retry
        after a failed call) and the pass in which ``_live`` empties.
        After it the scheduler and the engine stand as after a
        launch-then-collect step. The tokens it delivers are added to
        the next pass's count."""
        out = self.engine.settle(reason)
        launched, self._launched = self._launched, None
        if out is not None and launched is not None:
            self._settled_tokens += self._emit(*out, launched,
                                               time.monotonic())

    def _emit(self, tokens, block_emitted, launched: _Launched,
              now: float) -> int:
        """[holds: _lock] :meth:`_emit_block` under its
        ``serve.sched.emit`` span."""
        rows = len(self._live)
        with obs.annotate("serve.sched.emit", rows=rows) as ann:
            emitted = self._emit_block(tokens, block_emitted, launched,
                                       now)
            ann.set(emitted=emitted, retired=rows - len(self._live))
        return emitted

    def _emit_block(self, tokens, block_emitted, launched: _Launched,
                    now: float) -> int:
        """[holds: _lock] Hand one decoded block to its requests: per
        row the appends, the first-token and per-token latency
        observations, ``on_token``, and retirement (EOS / length /
        deadline / non-finite logits). Only a row still held by the
        SAME request as at the block's launch is served: a slot retired
        (and perhaps granted again) while its old block was in flight
        must not have the newcomer handed the old row's tokens, nor
        retired by its ``ok == False``; such a result is dropped, and
        counted in ``serve.engine.stale_rows_total`` where it was not
        empty. The block's window on the monotonic clock runs to
        ``now``, its collect, from its launch — or from the collect
        before it where that came later: with a block in flight the
        device only turned to this one then, and a launch-to-collect
        time would read a token's latency as nearly two blocks.
        Returns the tokens emitted."""
        horizon = self.engine.cfg.decode_horizon
        t0, t0_wall = launched.t0, launched.t0_wall
        if self._collect_t is not None and self._collect_t > t0:
            if t0_wall is not None:
                t0_wall += self._collect_t - t0
            t0 = self._collect_t
        self._collect_t = now
        dt = now - t0
        obs.histogram("serve.decode.horizon").observe(
            self.engine.tokens_per_dispatch)
        speculative = self.engine.spec is not None
        ok = self.engine.step_ok
        emitted = stale = 0
        # tokens delivered by the block, keyed by their row's emitted
        # count: what serve.tpot_s is told once the rows are through
        delivered: Dict[int, int] = {}
        for slot, live in launched.rows.items():
            e = int(block_emitted[slot])
            if self._live.get(slot) is not live:
                stale += bool(e or (ok is not None and not ok[slot]))
                continue
            retired = False
            for i in range(e):
                tok = int(tokens[slot, i])
                live.tokens.append(tok)
                emitted += 1
                if live.ttft_s is None:
                    # The first token landed at its position WITHIN the
                    # block, not at the block end — a fresh row emits
                    # from scan step 0, so crediting the whole block
                    # would overstate TTFT by (H-1)/H of a block. In
                    # speculative mode the block's width varies with
                    # acceptance, so the first ACCEPTED token is
                    # credited at its position among the row's e
                    # actually-emitted tokens (PR 5's move, denominator
                    # adjusted); classic keeps the exact /horizon form.
                    denom = e if speculative else horizon
                    live.ttft_s = ((t0 - live.submit_t)
                                   + dt * (i + 1) / denom)
                    if live.trace_id is not None and t0_wall is not None:
                        live.first_token_wall = (t0_wall
                                                 + dt * (i + 1) / denom)
                    obs.histogram("serve.ttft_s").observe(live.ttft_s)
                    # Per-priority-class split (pinned): one histogram
                    # per lane so the report/exposition can show
                    # interactive latency separately from the batch
                    # traffic it preempts.
                    obs.histogram(
                        f"serve.ttft_s.{live.req.priority}").observe(
                            live.ttft_s)
                    if (self.slo_tracker is not None
                            and live.req.priority == "interactive"):
                        # Feed the wired interactive-TTFT SLO tracker
                        # per first token: its burn rate is the PR 16
                        # control signal that widens the preemption
                        # quota in _maybe_preempt.
                        cfg = self.slo_tracker.cfg
                        self.slo_tracker.observe(
                            {"<": live.ttft_s < cfg.threshold,
                             "<=": live.ttft_s <= cfg.threshold,
                             ">": live.ttft_s > cfg.threshold,
                             ">=": live.ttft_s >= cfg.threshold,
                             }[cfg.op])
                delivered[e] = delivered.get(e, 0) + 1
                if self.on_token is not None:
                    self.on_token(live.request_id, tok)
                reason = None
                if (live.req.eos_id is not None
                        and tok == live.req.eos_id):
                    reason = FinishReason.EOS
                elif len(live.tokens) >= live.req.max_new_tokens:
                    reason = FinishReason.LENGTH
                elif (live.deadline_t is not None
                        and now >= live.deadline_t):
                    # Deadlines are block-granular now: the whole block
                    # shares one `now`, and tokens decoded past a
                    # mid-block deadline are dropped with the
                    # retirement (RUNBOOK §8 documents the coarsening).
                    reason = FinishReason.DEADLINE
                if reason is not None:
                    del self._live[slot]
                    self.engine.pool.free(slot)
                    obs.counter("serve.retired_total").inc()
                    if reason == FinishReason.DEADLINE:
                        # expired_total counts EVERY deadline miss,
                        # queued or mid-decode (FinishReason's
                        # documented contract).
                        obs.counter("serve.expired_total").inc()
                    self._finish(live, reason)
                    retired = True
                    break
            if retired:
                continue
            if ok is not None and not ok[slot]:
                # Non-finite logits (NaN/inf burst) at some scan step:
                # the device froze the row there and excluded the
                # garbage from its emitted count, so everything
                # delivered above is pre-burst. Retire ONLY this
                # request; the rest of the batch keeps decoding.
                del self._live[slot]
                self.engine.pool.free(slot)
                obs.counter("serve.errors_total").inc()
                obs.counter("serve.retired_total").inc()
                self._finish(live, FinishReason.ERROR,
                             error="non-finite logits")
        # Per-token decode latency: the block cost split over the
        # tokens a row produced, counted once per delivered token —
        # horizon=1 degenerates to the classic one-dt-per-token and
        # percentiles stay comparable across horizons. Rows that
        # emitted alike share one value, so a block observes once a
        # distinct count, not once a token or a row.
        tpot = obs.histogram("serve.tpot_s")
        for e, n in delivered.items():
            tpot.observe(dt / e, n)
        obs.counter("serve.tokens_total").inc(emitted)
        if stale:
            self.engine.stale_rows += stale
            obs.counter("serve.engine.stale_rows_total").inc(stale)
        return emitted

    def _finish(self, live: _Live, reason: str,
                error: Optional[str] = None) -> None:
        """[holds: _lock] — every caller (admission, decode, drain)
        already holds the lock; ``results`` is read by waiter threads."""
        if live.trace_id is not None and live.decode_t0_wall is not None:
            # The retire fragment: one span covering this request's
            # whole decode residency, carrying the first-token epoch
            # milestone the stitcher ends the TTFT decomposition at.
            attrs = {"request_id": live.request_id,
                     "finish_reason": reason,
                     "tokens": len(live.tokens),
                     "engine": self.engine.engine_id}
            if live.ttft_s is not None:
                attrs["ttft_s"] = live.ttft_s
            if live.first_token_wall is not None:
                attrs["first_token"] = live.first_token_wall
            obs.emit_span("serve.decode", live.decode_t0_wall,
                          time.time(), trace_id=live.trace_id, **attrs)
        result = RequestResult(
            request_id=live.request_id, tokens=live.tokens,
            finish_reason=reason, ttft_s=live.ttft_s,
            latency_s=time.monotonic() - live.submit_t, error=error)
        self.results[live.request_id] = result
        if self.on_finish is not None:
            self.on_finish(result)

    def _emit_park_span(self, live: _Live, outcome: str) -> None:
        """[holds: _lock] One ``serve.park`` fragment per traced park,
        emitted at its release (ACK / resume / TTL / drain) — the
        stitched timeline's view of how long the source held the blocks
        and which way the two-phase handoff resolved."""
        if live.trace_id is None or live.park_wall is None:
            return
        obs.emit_span("serve.park", live.park_wall, time.time(),
                      trace_id=live.trace_id,
                      request_id=live.request_id, outcome=outcome)

    # ------------------------------------------------------- migration
    def export_parked(self, request_id: str) -> dict:
        """The source half of the migration pull (``/kv_export``):
        export the parked request's full-block prompt prefix as the
        int8+scales wire object (serve/migrate.py). Read-only — the
        parked refs survive until :meth:`ack_parked` (the two-phase
        commit) or the TTL. Raises ``KeyError`` for an unknown/expired
        park and :class:`~nezha_tpu.serve.migrate.MigrationError` when
        this pool's leaves have no K/V wire format. Runs under the scheduler
        lock: the gather must not race a decode dispatch that donates
        the cache buffers."""
        from nezha_tpu.serve import migrate
        faults.point("replica.kv_export")
        with self._lock:
            if request_id not in self._parked:
                raise KeyError(request_id)
            self._settle("migrate")
            slot, live, _ = self._parked[request_id]
            pool = self.engine.pool
            # The export fragment adopts the PARKED request's trace (the
            # authoritative id — it arrived with the prefill_only
            # admission); untraced parks record nothing.
            with obs.trace_context(live.trace_id):
                with obs.traced_span("serve.kv_export",
                                     request_id=request_id) as sp:
                    tokens = [int(t) for t in live.req.prompt]
                    nfull = min(len(tokens) // pool.block_size,
                                int(pool._bound[slot]))
                    if nfull == 0:
                        # Sub-block prompt: nothing reusable to ship —
                        # a legal, empty payload (the decode side just
                        # prefills cold).
                        return migrate.encode_wire([], [],
                                                   pool.block_size)
                    layers, _ = pool.export_block_payload(slot, nfull)
                    wire = migrate.encode_wire(
                        tokens[:nfull * pool.block_size], layers,
                        pool.block_size)
                    sp.set(blocks=nfull, bytes=wire["nbytes"])
                    return wire

    def ack_parked(self, request_id: str) -> bool:
        """Commit of the two-phase handoff (``/kv_ack``): the decode
        side holds its own copy, so release the parked slot and its
        block refs. -> False (idempotently) when the park is unknown —
        already acked, TTL-reclaimed, or drained."""
        with self._lock:
            parked = self._parked.pop(request_id, None)
            if parked is None:
                return False
            slot, live, _ = parked
            self.engine.pool.free(slot)
            self._emit_park_span(live, "acked")
            obs.counter("serve.retired_total").inc()
            return True

    def resume_parked(self, request_id: str) -> bool:
        """Local-decode fallback (``role=both`` degradation): move a
        parked request into the live set and decode it HERE — the path
        the router takes when no decode-tier replica is live or every
        migration attempt failed. The parked prompt KV is already in
        this pool, so decoding starts immediately. -> False when the
        park is unknown (expired / acked away)."""
        with self._lock:
            parked = self._parked.pop(request_id, None)
            if parked is None:
                return False
            slot, live, _ = parked
            self._settle("migrate")
            self._emit_park_span(live, "resumed")
            if live.trace_id is not None:
                live.decode_t0_wall = time.time()
            # The "prefilled" result was this request's park receipt,
            # not its answer — drop it so the real retirement's result
            # is the one waiters read.
            self.results.pop(request_id, None)
            self._live[slot] = live
            return True

    def install_migrated(self, tokens: Sequence[int], layers: list,
                         nbytes: int) -> int:
        """The destination half of the pull: install a decoded wire
        payload into this replica's pool + prefix trie (fresh blocks at
        ref == 1 — the write invariant by construction). The request
        submitted afterwards takes prefix-cache references through the
        ordinary admission path. Counts committed installs into the
        schema-pinned ``serve.kv.migrations_total`` /
        ``serve.kv.migration_bytes``."""
        faults.point("replica.kv_install")
        with self._lock:
            self._settle("migrate")
            installed = self.engine.pool.install_block_payload(tokens,
                                                               layers)
            if installed > 0:
                # Committed installs only: an empty sub-block payload,
                # a disabled prefix cache, or an already-cached prefix
                # installs nothing and must not inflate the ledger
                # ("N pulls, 0 bytes moved" would misread as cache
                # wins). The router's plain ledgers count every
                # successful PULL separately.
                obs.counter("serve.kv.migrations_total").inc()
                obs.counter("serve.kv.migration_bytes").inc(nbytes)
            return installed

    # ----------------------------------------------------- fleet cache
    def fleet_digest(self, interval_s: float = 2.0,
                     max_entries: int = 256) -> dict:
        """The ``/healthz`` digest payload (PR 17): a bounded
        prefix-hash summary of what this replica's pool holds, rebuilt
        at most once per ``interval_s``."""
        from nezha_tpu.serve import fleetcache
        with self._lock:
            dc = self._digest_cache
            if (dc is None or dc.interval_s != float(interval_s)
                    or dc.max_entries != int(max_entries)):
                dc = fleetcache.DigestCache(interval_s, max_entries)
                self._digest_cache = dc
            return dc.payload(self.engine.pool)

    def export_prefix(self, tokens: Sequence[int]) -> dict:
        """The source half of a PEER pull (``/kv_export`` tokens
        mode, PR 17): the longest cached full-block prefix of
        ``tokens`` this pool holds, as the int8+scales wire object.
        Unlike :meth:`export_parked` there is no park, no request and
        no ACK — the export is a read-only cache probe; the source
        gives up nothing and zero coverage is a legal empty wire.
        Runs under the scheduler lock (the gather must not race a
        cache-donating decode dispatch). The peer path's chaos knob is
        ``replica.kv_pull`` on the DESTINATION client (one registered
        site per point) — source-side failure is exercised by killing
        the owner outright."""
        from nezha_tpu.serve import migrate
        with self._lock:
            self._settle("migrate")
            pool = self.engine.pool
            covered, layers, _ = pool.export_prefix_payload(tokens)
            return migrate.encode_wire(covered, layers, pool.block_size)

    def install_pulled(self, tokens: Sequence[int], layers: list,
                       nbytes: int) -> int:
        """The destination half of a peer pull: install the wire
        payload into this pool's prefix cache with the blocks tagged
        ``origin="peer"`` so their first reuse counts as a fleet peer
        hit, and account the wire bytes into the schema-pinned
        ``serve.kv.pull_bytes`` (NOT the migration ledgers — a peer
        pull is a cache transfer, not a request handoff)."""
        with self._lock:
            self._settle("migrate")
            installed = self.engine.pool.install_block_payload(
                tokens, layers, origin="peer")
            if installed > 0:
                obs.counter("serve.kv.pull_bytes").inc(nbytes)
            return installed

    # ----------------------------------------------------------- drain
    def cancel_remaining(self, reason: str = FinishReason.DEADLINE,
                         error: Optional[str] = None) -> int:
        """Retire EVERYTHING still queued or in flight — the drain
        cutoff. Each request finishes with ``reason`` and whatever
        tokens it already has, every slot returns to the pool, and the
        count of cancellations comes back (0 when already idle).
        Deadline-reason cancellations count into ``serve.expired_total``
        (the documented every-deadline-miss contract); error-reason ones
        (a dead engine at shutdown) into ``serve.errors_total`` with
        ``error`` as the detail."""
        def _count():
            if reason == FinishReason.DEADLINE:
                obs.counter("serve.expired_total").inc()
            elif reason == FinishReason.ERROR:
                obs.counter("serve.errors_total").inc()

        with self._lock:
            # what is in flight was decoded: its tokens are delivered
            self._settle("drain")
            n = 0
            while self._queued_n:
                live = self._pop_next()
                _count()
                self._finish(live, reason, error=error)
                n += 1
            for slot in list(self._live):
                live = self._live.pop(slot)
                self.engine.pool.free(slot)
                obs.counter("serve.retired_total").inc()
                _count()
                self._finish(live, reason, error=error)
                n += 1
            # Preempted requests hold no slot or blocks (their KV, if
            # any survived, lives in the trie/host tier) — retire them
            # with whatever tokens they already emitted.
            for rid in list(self._preempted):
                live = self._preempted.pop(rid)
                obs.counter("serve.retired_total").inc()
                _count()
                self._finish(live, reason, error=error)
                n += 1
            obs.gauge("serve.preempted_live").set(0)
            # Parked migrations: their "prefilled" answers were already
            # delivered, so this is pure resource release — a drained
            # source simply stops being pullable (the router's next
            # /kv_export gets a typed 404 and retries elsewhere).
            for rid in list(self._parked):
                slot, parked_live, _ = self._parked.pop(rid)
                self.engine.pool.free(slot)
                self._emit_park_span(parked_live, "drained")
                obs.counter("serve.retired_total").inc()
            obs.gauge("serve.queue_depth").set(0)
            obs.gauge("serve.batch_occupancy").set(
                self.engine.pool.occupancy)
            obs.gauge("serve.kv.blocks_used").set(
                self.engine.pool.blocks_used)
            return n
