"""`nezha-serve` — continuous-batching inference server.

The serving counterpart of `nezha-generate`: same three weight sources
(--ckpt-dir / --hf-dir / --random-init), but requests are admitted and
retired individually against the slot-pooled engine
(`nezha_tpu.serve`) — a late request joins the running batch instead of
waiting for it. Two front ends, zero new dependencies:

stdio JSONL (default) — one request object per stdin line, streamed
events per stdout line::

    {"id": "a", "prompt_tokens": [5, 17, 3], "max_new_tokens": 8}
    {"id": "b", "prompt": "hello", "temperature": 0.8, "top_p": 0.9}

    -> {"id": "a", "event": "token", "token": 42}
       ...
       {"id": "a", "event": "done", "tokens": [...], "finish_reason":
        "length", "ttft_s": ..., "latency_s": ...}

HTTP (--http PORT, stdlib http.server) — POST /generate with the same
request object (response once finished; queue-full = 503), GET /healthz
for liveness + occupancy, GET /stats for the LIVE telemetry registry
snapshot (stats schema v1 — counters/gauges/histogram summaries you can
curl mid-run; the router's version aggregates the whole fleet).
Requests may carry a distributed ``trace_id`` (field or X-Nezha-Trace
header; minted automatically per --trace-sample when a --run-dir run is
active) — ``nezha-telemetry RUN_DIR --trace`` stitches the resulting
per-replica span fragments into per-request timelines.

Lifecycle: SIGTERM/SIGINT triggers a GRACEFUL DRAIN — admission closes
immediately (stdio stops reading stdin; HTTP answers 503 "draining" on
POST /generate and flips /healthz to 503), in-flight requests keep
decoding for up to --drain-timeout seconds, stragglers retire with
finish_reason "deadline", and stdio flushes a final {"event": "drain"}
line before exit. A second signal during the drain is ignored (the
drain is already as fast as the deadline allows). With
--decode-horizon N the drain cutoff lands on a block boundary, so the
drain (like deadlines) is granular to one horizon — up to N tokens
later than the signal. NEZHA_FAULT_PLAN / NEZHA_FAULT_SEED install a
fault-injection plan for chaos drills (docs/RUNBOOK.md §9).

Scale-out (--replicas N, N > 1, requires --http): the process becomes a
ROUTER/SUPERVISOR front end instead of an engine — the supervisor
spawns N worker processes (each this same single-replica stack, via
run_worker(), on its own port), the router probes their /healthz,
load-balances by live queue depth, fails a request over to another
replica when its replica dies before answering, and restarts crashed
workers with capped backoff (circuit breaker after --max-restart-
failures consecutive startup failures). SIGTERM then performs a
ROLLING drain: replicas stop one at a time, each finishing its
in-flight work, so capacity never drops to zero until the end
(docs/RUNBOOK.md §10).

With --run-dir the run writes the standard telemetry artifacts;
`nezha-telemetry RUN_DIR` then renders the serving section (TTFT/TPOT
percentiles, tokens/sec, batch occupancy).

    nezha-serve --ckpt-dir runs/gpt2 --model-preset tiny \
        --max-batch-size 8 --max-len 96 --run-dir /tmp/serve
    nezha-serve --hf-dir /ckpts/gpt2 --http 8000
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time
from typing import Optional


def build_parser() -> argparse.ArgumentParser:
    from nezha_tpu.cli.common import RANDOM_INIT_MODELS, SERVED_MODELS

    p = argparse.ArgumentParser(prog="nezha-serve", description=__doc__)
    src = p.add_mutually_exclusive_group(required=True)
    src.add_argument("--ckpt-dir",
                     help="checkpoint dir written by nezha-train")
    src.add_argument("--hf-dir",
                     help="Hugging Face GPT2LMHeadModel directory")
    src.add_argument("--random-init", action="store_true",
                     help="fresh random weights (smoke/benchmark runs)")
    p.add_argument("--model", choices=list(SERVED_MODELS), default="gpt2",
                   help="the architecture served: gpt2 (every option); "
                        + "; ".join(f"{name} ({what})" for name, (_, _, what)
                                    in RANDOM_INIT_MODELS.items())
                        + ". All but gpt2: --random-init only, "
                        "--model-preset full = one chip's share at the "
                        "published widths, bf16 parameters; refused with "
                        "--mesh, --kv-dtype int8, --kv-host-blocks, "
                        "--speculative, KV migration and peer pulls; a "
                        "model with window or state layers also with "
                        "--prefix-cache on (a trie hit would need the "
                        "window layers' last tokens, or the recurrent "
                        "state at the hit's boundary, as a snapshot)")
    p.add_argument("--model-preset", choices=["full", "tiny"],
                   default="full")
    p.add_argument("--tokenizer", default=None,
                   help="tokenizer dir for text prompts/output (defaults "
                        "to --hf-dir's shipped tokenizer; else text "
                        "prompts use byte-level ids)")
    p.add_argument("--max-batch-size", type=int, default=4,
                   help="decode slots (concurrent in-flight requests)")
    p.add_argument("--max-len", type=int, default=96,
                   help="per-slot KV capacity: prompt + generated tokens")
    p.add_argument("--max-prefill-len", type=int, default=32,
                   help="widest single prefill chunk; longer prompts "
                        "(up to --max-len) prefill in successive chunks")
    p.add_argument("--prefill-buckets", default=None,
                   help="comma-separated static prompt pad widths (one "
                        "compiled prefill program each, last must equal "
                        "--max-prefill-len); default: powers of two up "
                        "to --max-prefill-len")
    p.add_argument("--decode-impl",
                   choices=["auto", "kernel", "xla"], default=None,
                   help="decode attention: auto = Pallas flash-decode "
                        "kernel on TPU / composed elsewhere, kernel = "
                        "force the kernel (interpret off-TPU), xla = "
                        "force the composed masked path; default: the "
                        "model config's choice (auto)")
    p.add_argument("--prefill-impl",
                   choices=["auto", "kernel", "xla"], default=None,
                   help="paged prefill attention: auto = Pallas "
                        "flash-prefill kernel on TPU / composed "
                        "elsewhere, kernel = force the kernel "
                        "(interpret off-TPU; int8 pools fuse the block "
                        "write into its epilogue), xla = force the "
                        "composed masked path; default: the model "
                        "config's choice (auto)")
    p.add_argument("--prefill-mode", choices=["replicated", "sequence"],
                   default="replicated",
                   help="prefill chunk parallelism: replicated = every "
                        "mesh device computes the full chunk (default); "
                        "sequence = shard the chunk over the sequence "
                        "axis of the 1xM mesh (ring/ulysses attention, "
                        "blocks land head-sharded in the paged pool — "
                        "long-context prompts, docs/RUNBOOK.md §8). "
                        "Requires --mesh M > 1")
    p.add_argument("--long-prefill-buckets", default=None,
                   help="comma-separated extra prefill pad widths "
                        "ABOVE --max-prefill-len (one compiled program "
                        "each, still inside --max-len) so an 8k/32k "
                        "prompt prefills in a few wide chunks instead "
                        "of hundreds of --max-prefill-len strides; "
                        "default: none")
    p.add_argument("--seq-prefill-variant",
                   choices=["auto", "ulysses", "ring"], default="auto",
                   help="sequence-mode attention algorithm: ulysses = "
                        "all-to-all head exchange (bitwise-identical "
                        "outputs, needs heads %% mesh == 0); ring = "
                        "ppermute ring hops (greedy-equivalent); auto "
                        "= ulysses (docs/RUNBOOK.md §8 selection "
                        "table)")
    p.add_argument("--decode-horizon", type=int, default=1,
                   help="tokens decoded per compiled step dispatch (the "
                        "device-resident sampling loop): 1 = classic "
                        "per-token stepping; N > 1 amortizes the host "
                        "gap over N tokens — streaming still emits "
                        "per-token events, but deadline/drain "
                        "granularity coarsens to one horizon "
                        "(docs/RUNBOOK.md §8)")
    p.add_argument("--kv-block-size", type=int, default=16,
                   help="tokens per block of the paged KV pool")
    p.add_argument("--kv-num-blocks", type=int, default=None,
                   help="total pool blocks (block 0 is "
                        "scratch); default = every slot can reach "
                        "max_len "
                        "(1 + max_batch_size * ceil(max_len/block)); "
                        "smaller makes resident tokens, not slots, the "
                        "admission limit")
    p.add_argument("--kv-dtype", choices=["bf16", "int8"],
                   default="bf16",
                   help="KV block storage: bf16 = store --cache-dtype "
                        "(bit-identical to the classic engine); int8 = "
                        "int8 blocks + per-block fp32 scales "
                        "— ~2x resident requests at the "
                        "same device budget, dequantized inside the "
                        "flash-decode kernel (docs/RUNBOOK.md §8)")
    p.add_argument("--prefix-cache", choices=["on", "off"], default="on",
                   help="reuse cached blocks for "
                        "requests whose prompt prefix matches (TTFT "
                        "collapses for templated traffic)")
    p.add_argument("--kv-eviction", choices=["lru", "none"],
                   default="lru",
                   help="when the free list runs dry, "
                        "evict LRU prefix-cache blocks (lru) or go "
                        "straight to typed backpressure (none)")
    p.add_argument("--kv-host-blocks", type=int, default=0,
                   help="host KV spill tier (requires --kv-dtype int8 "
                        "+ --kv-eviction lru): evicted prefix-cache "
                        "blocks demote their int8+scales payload into "
                        "a host-RAM LRU of up to N blocks instead of "
                        "being discarded, and a returning prefix hit "
                        "promotes them back with an async host-to-"
                        "device copy ahead of the prefill — turn-N+1 "
                        "chat traffic pays one tail chunk, not a cold "
                        "prefill; /healthz reports the tier's "
                        "occupancy (docs/RUNBOOK.md §8). 0 = off")
    p.add_argument("--speculative", action="store_true",
                   help="speculative decoding: a cheap DRAFT model "
                        "proposes --draft-k tokens per window, one "
                        "batched target forward verifies them all, and "
                        "the longest agreeing prefix is emitted — "
                        ">1 token per verify dispatch at unchanged "
                        "outputs (greedy bit-identical; sampled via "
                        "lossless rejection sampling). Draft KV lives "
                        "in a mirrored paged pool (int8 included); see "
                        "docs/RUNBOOK.md §8 for when a draft pays off")
    p.add_argument("--draft-k", type=int, default=4,
                   help="speculative: draft tokens proposed per verify "
                        "window (a window emits 1..draft_k+1 tokens)")
    p.add_argument("--draft-layers", type=int, default=None,
                   help="speculative: SELF-DRAFT depth — the draft is "
                        "the target's first N layers sharing its "
                        "weights (early-exit drafting, no second "
                        "checkpoint); default: full depth (identity "
                        "draft, accept-rate ~1). Ignored with "
                        "--draft-ckpt-dir/--draft-hf-dir")
    p.add_argument("--draft-ckpt-dir", default=None,
                   help="speculative: load a SEPARATE draft model from "
                        "this nezha-train checkpoint dir (same "
                        "tokenizer/vocab as the target)")
    p.add_argument("--draft-hf-dir", default=None,
                   help="speculative: load the draft model from a "
                        "Hugging Face GPT2LMHeadModel directory")
    p.add_argument("--k-max", type=int, default=64,
                   help="static top-k cap; per-request top_k is clamped "
                        "to it")
    p.add_argument("--queue-capacity", type=int, default=16,
                   help="admission queue bound (backpressure past it)")
    p.add_argument("--priority-weights", default=None, metavar="SPEC",
                   help="WFQ admission-grant weights per priority lane "
                        "as 'interactive=4,batch=2,background=1' (the "
                        "default split): per 7 grants under full "
                        "backlog, 4 go interactive, 2 batch, 1 "
                        "background — lower lanes slow, never starve. "
                        "All three classes required, integer weights "
                        ">= 1")
    p.add_argument("--tenant-queue-cap", type=int, default=None,
                   help="max queued requests any ONE tenant may hold; "
                        "past it the tenant gets a typed "
                        "tenant_over_limit 503 while others keep "
                        "admitting (default: no per-tenant cap — only "
                        "the global --queue-capacity)")
    p.add_argument("--preemption", choices=["on", "off"], default="off",
                   help="under slot/block pressure (or a burning "
                        "interactive --slo), SUSPEND the lowest-"
                        "priority running decode — its KV blocks move "
                        "to the prefix trie (LRU-evictable, host-tier "
                        "demotable) — and resume it bit-identically "
                        "when pressure clears (docs/RUNBOOK.md §10)")
    p.add_argument("--preemption-budget", type=int, default=2,
                   help="times one request may be preempted before it "
                        "becomes unpreemptable (the anti-thrash bound)")
    p.add_argument("--autoscale-min", type=int, default=None,
                   help="with --replicas and --autoscale-max: elastic "
                        "LOWER bound on the replica count — the "
                        "supervisor rolling-drains one replica at a "
                        "time down to it when the fleet goes idle")
    p.add_argument("--autoscale-max", type=int, default=None,
                   help="with --replicas and --autoscale-min: elastic "
                        "UPPER bound — the supervisor spawns one "
                        "replica at a time up to it under sustained "
                        "queue/prefill-wait pressure (hysteresis: "
                        "sustained signal + cooldown between actions)")
    p.add_argument("--max-new-tokens", type=int, default=32,
                   help="default for requests that don't set "
                        "max_new_tokens, and the cap for those that do")
    p.add_argument("--eos-id", type=int, default=None,
                   help="default EOS for requests that don't set one; "
                        "defaults to the tokenizer's EOS when loaded, "
                        "-1 disables even then")
    p.add_argument("--cache-dtype", choices=["bf16", "f32"], default="bf16",
                   help="KV pool dtype (f32 for bit-exact parity checks)")
    p.add_argument("--drain-timeout", type=float, default=30.0,
                   help="graceful-drain budget in seconds after SIGTERM/"
                        "SIGINT: admission closes at the signal, "
                        "in-flight requests may finish within this "
                        "window, stragglers retire with finish_reason "
                        "'deadline'")
    p.add_argument("--http", type=int, default=None, metavar="PORT",
                   help="serve HTTP on PORT instead of stdio JSONL")
    p.add_argument("--role", choices=["prefill", "decode", "both"],
                   default="both",
                   help="this replica's serving tier (surfaced in "
                        "/healthz and the router's replica table): "
                        "'prefill' members take admissions and park "
                        "prompt KV for migration, 'decode' members "
                        "pull migrated KV and stream tokens, 'both' "
                        "(default) does everything — the role is "
                        "routing metadata; every worker keeps the full "
                        "engine so degraded topologies still serve")
    p.add_argument("--prefill-replicas", type=int, default=0,
                   help="with --decode-replicas: run a DISAGGREGATED "
                        "front end of this many role=prefill workers "
                        "plus the decode tier (overrides --replicas; "
                        "requires --http) — admissions land on the "
                        "prefill tier and finished prompts' KV "
                        "migrates to the decode tier "
                        "(docs/RUNBOOK.md §10)")
    p.add_argument("--decode-replicas", type=int, default=0,
                   help="number of role=decode workers of the "
                        "disaggregated front end (see "
                        "--prefill-replicas)")
    p.add_argument("--replicas", type=int, default=1,
                   help="N > 1 turns this process into a router/"
                        "supervisor front end over N engine worker "
                        "processes (requires --http; each worker is "
                        "the single-replica stack on its own port)")
    p.add_argument("--mesh", type=int, default=1,
                   help="M > 1 makes each replica an M-device TENSOR-"
                        "PARALLEL engine (serve/sharded): parameters "
                        "Megatron-sharded and the paged K/V pools "
                        "head-sharded across a 1xM mesh, block tables "
                        "host-side, the frozen program contract "
                        "preserved per mesh. With --ckpt-dir the "
                        "train->serve resharding (nezha-reshard) runs "
                        "implicitly at startup, CRC-verified — a "
                        "corrupt checkpoint refuses to start. Composes "
                        "with --replicas: N routed replicas x M-device "
                        "meshes (docs/RUNBOOK.md §10). Requires "
                        "num_heads %% M == 0")
    p.add_argument("--replica-backend", choices=["process", "thread"],
                   default="process",
                   help="how workers are hosted: 'process' spawns real "
                        "nezha-serve subprocesses (production — an OS "
                        "failure domain each); 'thread' hosts them "
                        "in-process (tests/benchmarks — no spawn cost, "
                        "no OS isolation)")
    p.add_argument("--probe-interval", type=float, default=0.5,
                   help="seconds between per-replica /healthz probes")
    p.add_argument("--probe-misses", type=int, default=3,
                   help="consecutive missed probes that eject a replica "
                        "from routing (one success readmits it)")
    p.add_argument("--route-retries", type=int, default=2,
                   help="times one request may be re-dispatched after "
                        "its replica died before answering (seeded "
                        "backoff between attempts); a committed "
                        "response is never retried")
    p.add_argument("--restart-backoff", type=float, default=0.25,
                   help="base seconds of the capped-exponential restart "
                        "backoff for crashed replicas")
    p.add_argument("--max-restart-failures", type=int, default=5,
                   help="consecutive startup failures after which a "
                        "replica's circuit breaker opens (the "
                        "supervisor stops restarting it)")
    p.add_argument("--affinity-routing", choices=["on", "off"],
                   default=None,
                   help="route multi-replica token-id requests by "
                        "prefix AFFINITY (serve/fleetcache): each "
                        "replica piggybacks a bounded trie digest on "
                        "/healthz, the router scores candidates by "
                        "expected-prefix-hit-length x load and hands "
                        "near-miss picks a peer pull_from hint over "
                        "the /kv_export wire. Default: on when "
                        "--replicas > 1, off otherwise")
    p.add_argument("--digest-interval", type=float, default=2.0,
                   help="seconds between fleet-digest rebuilds on each "
                        "replica (the /healthz digest payload's "
                        "staleness cadence)")
    p.add_argument("--digest-max-entries", type=int, default=256,
                   help="bound on prefix-hash entries one replica "
                        "advertises per digest (recency-first "
                        "truncation)")
    p.add_argument("--trace-sample", type=float, default=1.0,
                   help="fraction of requests that carry a distributed "
                        "trace id (per-request lifecycle spans stitched "
                        "by 'nezha-telemetry RUN_DIR --trace'): 1.0 "
                        "traces every request, 0.0 disables minting — "
                        "the load knob for high-traffic fleets. Only "
                        "meaningful with --run-dir (no run = no spans)")
    p.add_argument("--run-dir", default=None,
                   help="write telemetry artifacts (metrics.jsonl / "
                        "spans.jsonl / events.jsonl / summary.json) "
                        "here")
    p.add_argument("--slo", action="append", default=None,
                   metavar="SPEC",
                   help="declarative SLO evaluated per window, e.g. "
                        "'serve.ttft_s p99 < 0.5 over 60s "
                        "[objective 0.99]' (repeatable, or "
                        "';'-separated). Evaluations and burn-rate "
                        "alerts stream to events.jsonl as typed "
                        "records; 'nezha-telemetry RUN_DIR --slo' "
                        "renders compliance/burn. Implies the "
                        "watchdog thread")
    p.add_argument("--watchdog-interval", type=float, default=0.0,
                   metavar="SECONDS",
                   help="run the anomaly watchdog (sustained queue "
                        "depth, TTFT regression vs trailing baseline, "
                        "replica flap, SLO burn) every SECONDS, "
                        "emitting typed events to events.jsonl; 0 "
                        "disables (default; --slo implies 10s)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--platform", default=None,
                   help="force a jax platform (e.g. cpu)")
    return p


def _build_stack(args):
    """(scheduler, tokenizer, eos_id) from parsed args."""
    import jax.numpy as jnp

    from nezha_tpu.cli.common import (load_gpt2_for_inference,
                                      load_model_for_inference)
    from nezha_tpu.cli.generate import _load_tokenizer
    from nezha_tpu.serve import Engine, ServeConfig, Scheduler

    mesh_m = int(getattr(args, "mesh", 1) or 1)
    if getattr(args, "model", "gpt2") != "gpt2":
        # What is written for per-head K/V only refuses at start-up,
        # typed, for a model that caches something else (the engine and
        # the pool refuse the same from a library call).
        unsupported = [flag for flag, on in (
            ("--mesh", mesh_m > 1),
            ("--kv-dtype int8", args.kv_dtype == "int8"),
            ("--kv-host-blocks", bool(args.kv_host_blocks)),
            ("--speculative", bool(getattr(args, "speculative", False))),
            ("--role prefill/decode (KV migration)",
             getattr(args, "role", "both") != "both"),
            ("--prefill-replicas/--decode-replicas (KV migration)",
             bool(getattr(args, "prefill_replicas", 0)
                  or getattr(args, "decode_replicas", 0))),
            ("--affinity-routing on (peer KV pulls)",
             getattr(args, "affinity_routing", "off") == "on")) if on]
        if unsupported:
            raise SystemExit(
                f"--model {args.model}: not supported with "
                f"{', '.join(unsupported)} (its cache is a latent row a "
                f"token, window layers in a ring of blocks, or a "
                f"recurrent state a slot, not per-head K/V in one "
                f"growing table)")
        # (--prefix-cache on with window or state layers is the pool's
        # own typed refusal, from the model's declaration: "serve
        # engine: ...")
    if mesh_m > 1 and getattr(args, "ckpt_dir", None):
        # The implicit nezha-reshard: build the serve mesh first, then
        # stream the training checkpoint straight into the head-sharded
        # layout (CRC-verified, one leaf of host memory at a time) —
        # the full-gather-then-scatter a naive load would do is exactly
        # what arXiv:2112.01075 exists to avoid. A corrupt or missing
        # checkpoint is a typed REFUSAL to start, never garbage served.
        import jax as _jax

        from nezha_tpu.cli.common import gpt2_for_preset
        from nezha_tpu.parallel.mesh import make_mesh
        from nezha_tpu.serve.sharded import (ReshardError,
                                             reshard_checkpoint)
        model = gpt2_for_preset(args.model_preset)
        # Engine-topology constraints checked BEFORE the (potentially
        # minutes-long) checkpoint load — a doomed mesh must refuse in
        # milliseconds, typed, not traceback after the reshard.
        if model.cfg.num_heads % mesh_m:
            raise SystemExit(
                f"--mesh {mesh_m}: num_heads="
                f"{model.cfg.num_heads} not divisible by the mesh — "
                f"K/V pools shard on the head axis")
        mesh = make_mesh({"tp": mesh_m},
                         devices=_jax.devices()[:mesh_m])
        try:
            variables, step = reshard_checkpoint(args.ckpt_dir, model,
                                                 mesh)
        except ReshardError as e:
            raise SystemExit(f"--mesh {mesh_m}: reshard refused: {e}")
        print(f"resharded step {step} from {args.ckpt_dir} onto a "
              f"1x{mesh_m} serve mesh", file=sys.stderr)
    else:
        model, variables = load_model_for_inference(args)
    tokenizer = _load_tokenizer(args)
    from nezha_tpu.cli.common import resolve_eos_id
    eos_id = resolve_eos_id(
        args.eos_id, tokenizer,
        getattr(model.cfg, "vocab_held", model.cfg.vocab_size))
    max_len = min(args.max_len, model.cfg.max_positions)
    buckets = ()
    if args.prefill_buckets:
        try:
            buckets = tuple(int(b) for b in
                            str(args.prefill_buckets).split(","))
        except ValueError:
            raise SystemExit(
                f"--prefill-buckets must be comma-separated ints, got "
                f"{args.prefill_buckets!r}")
    long_buckets = ()
    if getattr(args, "long_prefill_buckets", None):
        try:
            long_buckets = tuple(
                int(b) for b in
                str(args.long_prefill_buckets).split(","))
        except ValueError:
            raise SystemExit(
                f"--long-prefill-buckets must be comma-separated ints, "
                f"got {args.long_prefill_buckets!r}")
    prefill_mode = getattr(args, "prefill_mode", "replicated")
    if prefill_mode == "sequence" and mesh_m < 2:
        # Typed refusal BEFORE any engine build: sequence sharding
        # splits the chunk over mesh devices, so a 1-device mesh has
        # nothing to shard over.
        raise SystemExit(
            "--prefill-mode sequence requires --mesh M with M > 1 "
            "(the chunk is sharded over the mesh's sequence axis)")
    spec = None
    draft_model = draft_variables = None
    if not getattr(args, "speculative", False) and (
            getattr(args, "draft_ckpt_dir", None)
            or getattr(args, "draft_hf_dir", None)):
        # A draft checkpoint without the knob would silently serve
        # classic — the operator believes their draft is in play.
        raise SystemExit(
            "--draft-ckpt-dir/--draft-hf-dir require --speculative")
    if getattr(args, "speculative", False):
        from nezha_tpu.serve.engine import SpeculativeConfig
        spec = SpeculativeConfig(draft_k=args.draft_k,
                                 draft_layers=args.draft_layers)
        if getattr(args, "draft_ckpt_dir", None) \
                or getattr(args, "draft_hf_dir", None):
            # An explicit draft checkpoint rides the SAME cli/common
            # loader as the target (either nezha-train format or an HF
            # dir); without one the engine builds an early-exit
            # self-draft from the target's own weights.
            dargs = argparse.Namespace(**vars(args))
            dargs.ckpt_dir = args.draft_ckpt_dir
            dargs.hf_dir = args.draft_hf_dir
            dargs.random_init = False
            draft_model, draft_variables = load_gpt2_for_inference(dargs)
    try:
        cfg = ServeConfig(
            max_batch_size=args.max_batch_size, max_len=max_len,
            max_prefill_len=args.max_prefill_len,
            prefill_buckets=buckets,
            long_prefill_buckets=long_buckets,
            prefill_mode=prefill_mode,
            seq_prefill_variant=getattr(args, "seq_prefill_variant",
                                        "auto"),
            k_max=args.k_max,
            queue_capacity=args.queue_capacity,
            cache_dtype=jnp.float32 if args.cache_dtype == "f32"
            else jnp.bfloat16,
            decode_impl=args.decode_impl,
            prefill_impl=args.prefill_impl,
            decode_horizon=args.decode_horizon,
            kv_block_size=args.kv_block_size,
            kv_num_blocks=args.kv_num_blocks,
            prefix_cache=args.prefix_cache == "on",
            kv_eviction=args.kv_eviction,
            kv_dtype=args.kv_dtype,
            kv_host_blocks=args.kv_host_blocks,
            speculative=spec,
            priority_weights=_parse_priority_weights(
                getattr(args, "priority_weights", None)),
            tenant_queue_cap=getattr(args, "tenant_queue_cap", None),
            preemption=getattr(args, "preemption", "off") == "on",
            preemption_budget=getattr(args, "preemption_budget", 2))
    except ValueError as e:
        # ServeConfig's own validation (bucket ordering, long buckets
        # outside (max_prefill_len, max_len], unknown modes) as the
        # CLI's typed refusal.
        raise SystemExit(f"serve config: {e}")
    if mesh_m > 1:
        from nezha_tpu.serve.sharded import ShardedEngine
        try:
            engine = ShardedEngine(model, variables, cfg,
                                   mesh_devices=mesh_m,
                                   draft_model=draft_model,
                                   draft_variables=draft_variables)
        except ValueError as e:
            # Topology constraints (heads %% mesh, paged-only, device
            # count) as the CLI's typed refusal — the non-ckpt paths
            # reach here without the pre-reshard check above.
            raise SystemExit(f"--mesh {mesh_m}: {e}")
    else:
        try:
            engine = Engine(model, variables, cfg, draft_model=draft_model,
                            draft_variables=draft_variables)
        except ValueError as e:
            raise SystemExit(f"serve engine: {e}")
    scheduler = Scheduler(engine)
    if getattr(args, "slo", None):
        # The first serve.ttft_s SLO spec doubles as the scheduler's
        # preemption control signal (PR 16 -> PR 19): its burn rate,
        # fed per interactive first token, widens the preemption quota
        # while the error budget is burning. The watchdog keeps its
        # own independent trackers.
        from nezha_tpu import obs
        for slo_cfg in obs.parse_slo_args(args.slo):
            if slo_cfg.metric == "serve.ttft_s":
                scheduler.slo_tracker = obs.SLOTracker(slo_cfg)
                break
    return scheduler, tokenizer, eos_id


def _parse_priority_weights(spec):
    """'interactive=4,batch=2,background=1' -> dict (None passes
    through — ServeConfig then applies the default split)."""
    if spec is None:
        return None
    out = {}
    for part in str(spec).split(","):
        name, eq, val = part.partition("=")
        try:
            out[name.strip()] = int(val)
        except ValueError:
            raise SystemExit(
                f"--priority-weights must be 'class=int,...' pairs, "
                f"got {part!r}")
        if not eq:
            raise SystemExit(
                f"--priority-weights must be 'class=int,...' pairs, "
                f"got {part!r}")
    return out


def _parse_request(obj: dict, args, tokenizer, eos_id, vocab: int):
    """One wire object -> serve.Request. Raises ValueError on bad input."""
    from nezha_tpu.serve import Request
    if not isinstance(obj, dict):
        raise ValueError("request must be a JSON object")
    if ("prompt_tokens" in obj) == ("prompt" in obj):
        raise ValueError("pass exactly one of prompt_tokens / prompt")
    if "prompt_tokens" in obj:
        prompt = [int(t) for t in obj["prompt_tokens"]]
    else:
        text = obj["prompt"]
        if not isinstance(text, str) or not text:
            raise ValueError("prompt must be a non-empty string")
        if tokenizer is not None:
            from nezha_tpu.data.tokenizer import encode_plain
            prompt = encode_plain(tokenizer, text)
        else:
            prompt = list(text.encode("utf-8"))
    if not prompt:
        raise ValueError("prompt encoded to zero tokens")
    if max(prompt) >= vocab or min(prompt) < 0:
        raise ValueError(f"prompt ids must be in [0, {vocab})")
    def num(key, cast, default=None):
        # Coerce HERE so a malformed field is a per-request error (400 /
        # error event), never an exception inside the decode loop.
        v = obj.get(key, default)
        if v is None:
            return None
        try:
            return cast(v)
        except (TypeError, ValueError):
            raise ValueError(f"{key} must be a number, got {v!r}")

    # --max-new-tokens is both the default and the per-request CAP: the
    # operator's bound on how long one request may monopolize a slot.
    max_new = min(num("max_new_tokens", int, args.max_new_tokens),
                  args.max_new_tokens)
    trace_id = obj.get("trace_id")
    if trace_id is not None and not isinstance(trace_id, str):
        raise ValueError(f"trace_id must be a string, got {trace_id!r}")
    # Multi-tenant scheduling fields (PR 19). Defaults reproduce the
    # pre-priority wire bit for bit: every request lands in the
    # interactive lane of the "default" tenant, where WFQ degenerates
    # to the classic bounded FIFO. Value validation (known class,
    # non-empty tenant) is submit()'s — it owns the typed 400.
    priority = obj.get("priority", "interactive")
    if not isinstance(priority, str):
        raise ValueError(f"priority must be a string, got {priority!r}")
    tenant_id = obj.get("tenant_id", "default")
    if not isinstance(tenant_id, str):
        raise ValueError(
            f"tenant_id must be a string, got {tenant_id!r}")
    return Request(
        priority=priority, tenant_id=tenant_id,
        prompt=prompt, max_new_tokens=max_new,
        temperature=num("temperature", float, 0.0),
        top_k=num("top_k", int), top_p=num("top_p", float),
        eos_id=num("eos_id", int, eos_id),
        seed=num("seed", int, args.seed),
        deadline_s=num("deadline_s", float),
        request_id=obj.get("id"),
        # Disaggregation: prefill and PARK for migration (the router's
        # phase-one dispatch) instead of decoding here.
        prefill_only=bool(obj.get("prefill_only", False)),
        # Distributed tracing: the router-minted id this request's
        # lifecycle spans carry. "" is a real verdict — "routed and
        # sampled out" — which the scheduler honors by NOT minting;
        # only an absent field (None) lets it mint for itself.
        trace_id=trace_id)


def _decode_text(tokens, tokenizer):
    if tokenizer is not None:
        return tokenizer.decode(tokens)
    return bytes(t for t in tokens if t < 256).decode(
        "utf-8", errors="replace")


def _result_obj(res, tokenizer) -> dict:
    out = {"id": res.request_id, "event": "done", "tokens": res.tokens,
           "text": _decode_text(res.tokens, tokenizer),
           "finish_reason": res.finish_reason, "ttft_s": res.ttft_s,
           "latency_s": res.latency_s}
    if res.error is not None:     # finish_reason "error": what broke
        out["error"] = res.error
    return out


def _drain(scheduler, budget_s: float, drive: bool,
           dead: Optional[threading.Event] = None,
           abort: Optional[threading.Event] = None) -> int:
    """Graceful-drain tail shared by both front ends: keep the decode
    loop running (``drive=True`` steps it here; ``drive=False`` trusts a
    live decode thread, passing its death signal as ``dead`` and the
    server's shutdown signal as ``abort``) until in-flight work
    finishes, ``budget_s`` expires, or one of the signals fires —
    nothing will ever finish after the engine dies, so waiting out the
    budget only delays shutdown. Stragglers are cancelled with
    finish_reason "deadline" — or "error" when the engine died, so an
    engine crash at shutdown is never dressed up as a routine deadline.
    Returns how many were cancelled; the whole window is the
    ``serve.drain`` span the telemetry report surfaces."""
    from nezha_tpu import obs
    from nezha_tpu.serve import FinishReason
    reason, error = FinishReason.DEADLINE, None
    with obs.span("serve.drain", budget_s=budget_s) as sp:
        t_end = time.monotonic() + budget_s
        while scheduler.has_work() and time.monotonic() < t_end:
            if dead is not None and dead.is_set():
                reason = FinishReason.ERROR
                error = "decode loop died during drain"
                break
            if abort is not None and abort.is_set():
                break
            if drive:
                if not scheduler.step():
                    time.sleep(0.002)
            else:
                time.sleep(0.005)
        cancelled = scheduler.cancel_remaining(reason, error=error)
        sp.set(cancelled=cancelled, reason=reason)
    return cancelled


# ------------------------------------------------------------- stdio mode
def run_stdio(scheduler, args, tokenizer, eos_id,
              stdin=None, stdout=None, drain=None) -> int:
    """JSONL in, JSONL events out. A reader thread feeds the admission
    queue as lines arrive (QueueFull = wait: stdin IS the backpressure
    channel); the caller's thread drives the decode loop. Setting
    ``drain`` (the signal handlers do) closes admission, finishes
    in-flight work within --drain-timeout, and flushes one final
    ``{"event": "drain"}`` line."""
    stdin = stdin if stdin is not None else sys.stdin
    stdout = stdout if stdout is not None else sys.stdout
    drain = drain if drain is not None else threading.Event()
    vocab = scheduler.engine.vocab
    out_lock = threading.Lock()

    def emit(obj):
        with out_lock:
            stdout.write(json.dumps(obj) + "\n")
            stdout.flush()

    scheduler.on_token = lambda rid, tok: emit(
        {"id": rid, "event": "token", "token": tok})

    def on_finish(res):
        emit(_result_obj(res, tokenizer))
        # The done event IS the delivery — drop the stored result, or a
        # long-lived server leaks every retired request's token list.
        scheduler.results.pop(res.request_id, None)

    scheduler.on_finish = on_finish

    from nezha_tpu.serve import QueueFull
    done_reading = threading.Event()

    def reader():
        try:
            for line in stdin:
                if drain.is_set():
                    # Admission closed with this line already read off
                    # stdin: answer it (the stdio analogue of HTTP's
                    # 503) before stopping, so the client isn't left
                    # waiting for an event that will never come. Lines
                    # never read stay un-accepted — the final drain
                    # event tells the client to stop expecting answers.
                    if line.strip():
                        try:
                            obj = json.loads(line)
                            rid = obj.get("id") \
                                if isinstance(obj, dict) else None
                        except ValueError:
                            rid = None
                        emit({"id": rid, "event": "error",
                              "error": "draining"})
                    break
                line = line.strip()
                if not line:
                    continue
                try:
                    obj = json.loads(line)
                except ValueError:
                    emit({"id": None, "event": "error",
                          "error": "line is not valid JSON"})
                    continue
                try:
                    req = _parse_request(obj, args, tokenizer, eos_id,
                                         vocab)
                except ValueError as e:
                    emit({"id": obj.get("id")
                          if isinstance(obj, dict) else None,
                          "event": "error", "error": str(e)})
                    continue
                while True:
                    if drain.is_set():
                        # Admission closed with this request parsed but
                        # never submitted: answer it (the stdio analogue
                        # of HTTP's 503) so the client isn't left
                        # waiting for an event that will never come.
                        emit({"id": req.request_id, "event": "error",
                              "error": "draining"})
                        break
                    # Wait for queue room rather than spamming submit:
                    # stdin is the backpressure channel, and QueueFull
                    # increments the rejected_total SHED metric.
                    if scheduler.queue_depth >= scheduler.queue_capacity:
                        time.sleep(0.005)
                        continue
                    try:
                        scheduler.submit(req)
                        break
                    except QueueFull:   # raced a burst; keep waiting
                        time.sleep(0.005)
                    except ValueError as e:
                        emit({"id": req.request_id, "event": "error",
                              "error": str(e)})
                        break
        finally:
            done_reading.set()

    t = threading.Thread(target=reader, daemon=True)
    t.start()
    while ((not done_reading.is_set() or scheduler.has_work())
           and not drain.is_set()):
        if not scheduler.step():
            time.sleep(0.002)
    if drain.is_set():
        cancelled = _drain(scheduler, args.drain_timeout, drive=True)
        # The final flushed event: supervisors tailing stdout know the
        # drain ran and whether the deadline cut anything off.
        emit({"id": None, "event": "drain", "cancelled": cancelled})
    return 0


# -------------------------------------------------------------- http mode
def run_http(scheduler, args, tokenizer, eos_id, port: int,
             ready_cb=None, drain=None) -> int:
    """Stdlib http.server front end: POST /generate (blocks until the
    request retires; 503 on queue-full backpressure), GET /healthz.
    Handlers run on server threads; one daemon thread drives decode.
    Setting ``drain`` (the signal handlers do) closes admission (POST ->
    503 "draining", /healthz -> 503), lets in-flight requests finish
    within --drain-timeout, then shuts the server down."""
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    from nezha_tpu.serve import QueueFull, TenantOverLimit

    drain = drain if drain is not None else threading.Event()
    vocab = scheduler.engine.vocab
    events = {}
    events_lock = threading.Lock()

    def on_finish(res):
        with events_lock:
            ev = events.get(res.request_id)
        if ev is not None:
            ev.set()

    scheduler.on_finish = on_finish
    stop = threading.Event()          # server is shutting down (any cause)
    engine_dead = threading.Event()   # the decode loop CRASHED (subset)

    def loop():
        # Fail LOUD and release every waiter: a dead decode thread with
        # handlers parked on ev.wait() would hang the server silently
        # (healthz keeps answering) — instead surface 500s/503s.
        try:
            while not stop.is_set():
                if not scheduler.step():
                    time.sleep(0.002)
        except Exception:
            import traceback
            traceback.print_exc()
            engine_dead.set()
            stop.set()
            with events_lock:
                for ev in events.values():
                    ev.set()

    decode_thread = threading.Thread(target=loop, daemon=True)
    decode_thread.start()

    class Handler(BaseHTTPRequestHandler):
        # Bound the life of a stalled connection (a client that never
        # finishes its upload) so joining handler threads at shutdown
        # can't hang on it.
        timeout = 60

        def log_message(self, *a):  # stderr noise off the request path
            pass

        def _send(self, code: int, obj: dict):
            body = json.dumps(obj).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            if self.path == "/stats":
                # Live registry snapshot (stats schema v1): the
                # counters/gauges/histogram summaries RIGHT NOW,
                # curl-able mid-run without waiting for the run-dir
                # flush. Answered even while draining.
                from nezha_tpu import obs
                payload = obs.stats_snapshot()
                payload["role"] = getattr(args, "role", "both")
                payload["tenants"] = scheduler.tenant_queue_depths()
                return self._send(200, payload)
            if self.path == "/windows":
                # Mergeable rolled-up window views (the router's fleet
                # /metrics scrapes these and merges the sketches).
                from nezha_tpu import obs
                return self._send(200, obs.windows_payload())
            if self.path == "/metrics":
                # Prometheus text exposition: cumulative totals plus
                # window-labeled rates/quantiles.
                from nezha_tpu import obs
                body = obs.render_prometheus(
                    obs.stats_snapshot(), obs.windows_payload()).encode()
                self.send_response(200)
                self.send_header("Content-Type",
                                 "text/plain; version=0.0.4")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
                return
            if self.path != "/healthz":
                return self._send(404, {"error": "unknown path"})
            pool = scheduler.engine.pool
            if stop.is_set():
                status = "decode loop stopped"
            elif drain.is_set():
                # Draining flips healthz FIRST: load balancers stop
                # routing here while in-flight requests finish.
                status = "draining"
            else:
                status = "ok"
            payload = {
                "status": status,
                "active": pool.num_active,
                "capacity": pool.capacity,
                "queued": scheduler.queue_depth,
                "occupancy": pool.occupancy,
                "role": getattr(args, "role", "both"),
                "parked": scheduler.parked_count,
                # Per-tenant queue depths + suspended count (PR 19):
                # the router's autoscale signal reads "queued"; these
                # give operators the fairness view behind it.
                "tenants": scheduler.tenant_queue_depths(),
                "preempted": scheduler.preempted_count,
                # Host spill tier occupancy (0/0 when --kv-host-blocks
                # is off): what the router's
                # replica table and operators size the tier against.
                "host_blocks": pool.host_blocks,
                "host_blocks_used": pool.host_blocks_used}
            if status == "ok":
                # Fleet digest piggyback (PR 17): the router's prober
                # is the digest transport — no extra endpoint.
                payload.update(scheduler.fleet_digest(
                    getattr(args, "digest_interval", 2.0),
                    getattr(args, "digest_max_entries", 256)))
            self._send(200 if status == "ok" else 503, payload)

        def do_POST(self):
            from nezha_tpu.serve import migrate
            if self.path in ("/kv_export", "/kv_ack"):
                # Migration endpoints (docs/RUNBOOK.md §10): the source
                # side of the pull and the two-phase ACK. Allowed
                # during drain — an in-flight migration finishing is
                # strictly better than its park being swept.
                n = int(self.headers.get("Content-Length", 0))
                return self._send(*migrate.dispatch_kv_endpoint(
                    scheduler, self.path, self.rfile.read(n)))
            if self.path != "/generate":
                return self._send(404, {"error": "unknown path"})
            if drain.is_set():   # admission is closed for good
                return self._send(503, {"error": "draining"})
            try:
                n = int(self.headers.get("Content-Length", 0))
                obj = json.loads(self.rfile.read(n))
            except (ValueError, json.JSONDecodeError) as e:
                return self._send(400, {"error": str(e)})
            from nezha_tpu import obs
            obs.adopt_trace_header(self.headers, obj)
            if isinstance(obj, dict) and obj.get("resume"):
                return self._handle_resume(str(obj["resume"]))
            mig_meta = None
            fleet_meta = None
            pull = obj.get("pull_from") if isinstance(obj, dict) else None
            if isinstance(pull, dict) and "tokens" in pull \
                    and "request_id" not in pull:
                # Fleet peer pull (PR 17): fetch covering prefix
                # blocks from the sibling the router named, then fall
                # through to ordinary admission so the submit below
                # prefix-hits them. Failure DEGRADES to a cold prefill
                # — never an HTTP error; the pull is an optimization,
                # not a dependency.
                try:
                    fleet_meta = migrate.pull_prefix_into(scheduler,
                                                          pull)
                except migrate.MigrationError as e:
                    fleet_meta = {"bytes": 0, "blocks": 0,
                                  "installed": 0, "degraded": str(e),
                                  "error_type": e.kind}
            elif pull is not None:
                # Decode side of a migration: pull + install + ACK
                # BEFORE admission so the submit below prefix-hits the
                # installed blocks; failure is the typed 424 the router
                # retries on.
                try:
                    mig_meta = migrate.pull_into(scheduler, pull)
                except migrate.MigrationError as e:
                    return self._send(424, {
                        "error": str(e), "error_type": e.kind})
            try:
                req = _parse_request(obj, args, tokenizer, eos_id, vocab)
            except ValueError as e:
                return self._send(400, {"error": str(e)})
            if stop.is_set():
                return self._send(503, {"error": "decode loop stopped"})
            # Register the event BEFORE submit (the decode thread could
            # retire a short request between submit and a later
            # registration), and never hold events_lock across submit —
            # on_finish runs under the scheduler lock and takes
            # events_lock, so holding both here in the opposite order
            # would deadlock.
            import uuid
            rid = req.request_id or f"http-{uuid.uuid4().hex[:12]}"
            req.request_id = rid
            ev = threading.Event()
            with events_lock:
                if rid in events:
                    # A duplicate would overwrite the first waiter's
                    # event and strand it forever on ev.wait().
                    return self._send(409, {
                        "error": f"request id {rid!r} already in flight"})
                events[rid] = ev
            try:
                scheduler.submit(req)
            except QueueFull as e:
                with events_lock:
                    events.pop(rid, None)
                # Typed like every other client-visible failure: the
                # router sweeps past ANY replica 503, but a direct
                # client must be able to tell "this tenant is over ITS
                # cap" from "the whole queue is full".
                return self._send(503, {
                    "error": str(e),
                    "error_type": ("tenant_over_limit"
                                   if isinstance(e, TenantOverLimit)
                                   else "queue_full")})
            except ValueError as e:
                with events_lock:
                    events.pop(rid, None)
                return self._send(400, {"error": str(e)})
            if stop.is_set():
                # TOCTOU guard: the drain (or a decode-loop death)
                # completed between the admission check above — which
                # ran before this request's body finished uploading —
                # and the submit. Nobody will ever retire this request,
                # so answer 503 now instead of parking on ev.wait()
                # forever.
                with events_lock:
                    events.pop(rid, None)
                return self._send(503, {"error": "draining"})
            ev.wait()
            with events_lock:
                events.pop(rid, None)
            res = scheduler.results.pop(rid, None)
            if res is None:   # decode loop died before retiring us
                return self._send(500, {"error": "decode loop failed"})
            out = _result_obj(res, tokenizer)
            out.pop("event")
            if mig_meta is not None:
                out["migration"] = mig_meta
            if fleet_meta is not None:
                out["fleet_pull"] = fleet_meta
            self._send(200, out)

        def _handle_resume(self, rid: str):
            """Local-decode fallback: move a parked request into the
            live set and answer with its finished result (the
            ``role=both`` degradation)."""
            ev = threading.Event()
            with events_lock:
                if rid in events:
                    return self._send(409, {
                        "error": f"request id {rid!r} already in "
                                 f"flight"})
                events[rid] = ev
            if not scheduler.resume_parked(rid):
                with events_lock:
                    events.pop(rid, None)
                return self._send(404, {
                    "error": f"request {rid!r} is not parked here",
                    "error_type": "migration_failed"})
            if stop.is_set():
                with events_lock:
                    events.pop(rid, None)
                return self._send(503, {"error": "draining"})
            ev.wait()
            with events_lock:
                events.pop(rid, None)
            res = scheduler.results.pop(rid, None)
            if res is None:
                return self._send(500, {"error": "decode loop failed"})
            out = _result_obj(res, tokenizer)
            out.pop("event")
            out["resumed"] = True
            self._send(200, out)

    class Server(ThreadingHTTPServer):
        # Join handler threads on close instead of abandoning them as
        # daemons: a client whose in-flight POST was cancelled at the
        # drain deadline gets its final "deadline" response flushed
        # before the process exits, not a connection reset. The drain
        # sweeps release every parked handler first, and the per-
        # connection timeout above bounds stalled ones.
        daemon_threads = False

    server = Server(("127.0.0.1", port), Handler)

    def drain_watch():
        # Runs the drain off the signal handler: handlers must return
        # immediately, so they only set the event; this thread does the
        # waiting, the straggler cancellation (which releases every
        # parked POST via on_finish), and the server shutdown. With the
        # decode loop already dead there is nothing left to drain, but
        # the signal must STILL stop the server — shutdown() is a no-op
        # if serve_forever already exited.
        from nezha_tpu.serve import FinishReason

        def cancel_stragglers():
            # A request whose body upload straddled the drain can slip
            # past the admission check and submit late; retire it before
            # releasing events, so its handler finds a RESULT (deadline
            # on a healthy shutdown, error on a dead engine), not a
            # spurious 500.
            if engine_dead.is_set():
                scheduler.cancel_remaining(FinishReason.ERROR,
                                           error="decode loop died")
            else:
                scheduler.cancel_remaining()

        drain.wait()
        if not stop.is_set():
            # If the engine dies mid-drain the wait breaks immediately
            # (and the cancellations say "error") instead of idling out
            # the budget over work that can never finish; a server-exit
            # abort (the serve_forever finally) just cuts it short.
            _drain(scheduler, args.drain_timeout, drive=False,
                   dead=engine_dead, abort=stop)
            stop.set()
        cancel_stragglers()
        with events_lock:
            for ev in events.values():
                ev.set()
        server.shutdown()
        # Once more after shutdown: a handler registering later than
        # this sweep sees stop already set and answers 503 itself.
        cancel_stragglers()
        with events_lock:
            for ev in events.values():
                ev.set()

    threading.Thread(target=drain_watch, daemon=True).start()
    if ready_cb is not None:
        ready_cb(server)
    print(f"nezha-serve listening on http://127.0.0.1:"
          f"{server.server_address[1]} (POST /generate, GET /healthz)",
          file=sys.stderr)
    try:
        server.serve_forever(poll_interval=0.1)
    except KeyboardInterrupt:
        pass
    finally:
        stop.set()
        drain.set()    # unblock the watcher thread on non-signal exits
        server.server_close()
    return 0


def _start_watchdog(args):
    """Start the anomaly watchdog thread when ``--watchdog-interval``
    or ``--slo`` asks for one (an SLO implies the watchdog — something
    must evaluate it). Returns the started WatchdogThread or None.
    Spec errors exit with the offending ``--slo`` string."""
    from nezha_tpu import obs
    try:
        slos = obs.parse_slo_args(getattr(args, "slo", None))
    except ValueError as e:
        raise SystemExit(f"--slo: {e}")
    interval = float(getattr(args, "watchdog_interval", 0.0) or 0.0)
    if interval <= 0 and not slos:
        return None
    if interval <= 0:
        interval = 10.0
    wd = obs.Watchdog(slos=slos,
                      config=obs.WatchdogConfig(interval_s=interval))
    return obs.WatchdogThread(wd).start()


def run_worker(args, stdin=None, stdout=None, ready_cb=None,
               drain_event=None) -> int:
    """The single-replica stack — the classic ``--replicas 1`` entry
    AND the worker the supervisor spawns (``--replicas N`` workers run
    exactly this, one per port), so there is one code path to keep
    correct. The ``replica.exec`` fault point fires at entry: the
    crash-at-startup drill behind the supervisor's restart backoff."""
    import signal

    from nezha_tpu import faults
    from nezha_tpu.cli.common import setup_jax
    setup_jax(args)

    # Chaos drills: NEZHA_FAULT_PLAN installs a seeded fault plan for
    # this serve process (restored on exit so embedded callers — tests —
    # don't leak plans across runs; restoring an unchanged plan is a
    # no-op).
    prev_plan = faults.active()
    faults.install_from_env()
    from nezha_tpu.serve.supervisor import replica_exec_point
    try:
        replica_exec_point()
    except BaseException:     # crash-at-startup drill: die loudly, but
        faults.install(prev_plan)   # never leak the plan into embedders
        raise

    drain = drain_event if drain_event is not None else threading.Event()
    old_handlers = {}

    from nezha_tpu import obs
    try:
        obs.set_trace_sample(getattr(args, "trace_sample", 1.0))
    except ValueError as e:
        raise SystemExit(f"--trace-sample: {e}")
    # Watchdog first: a bad --slo spec must exit before a sink opens.
    # Its checks are harmless pre-run (telemetry still disabled).
    watchdog = _start_watchdog(args)
    sink = None
    if args.run_dir:
        sink = obs.start_run(args.run_dir, meta={
            "kind": "serve", "mode": "http" if args.http else "stdio"})
    try:
        scheduler, tokenizer, eos_id = _build_stack(args)
        # SIGTERM/SIGINT = graceful drain, not an exception mid-decode.
        # Installed only AFTER the stack is built: during the (possibly
        # minutes-long) weight load + compile there is nothing to drain,
        # and a wedged startup must stay killable with plain Ctrl-C.
        # The handler only sets the event; the front ends own the drain
        # itself. ``drain_event`` lets embedded callers trigger the same
        # path without a signal (run() off the main thread cannot
        # install handlers — the ValueError guard below).
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                old_handlers[sig] = signal.signal(
                    sig, lambda signum, frame: drain.set())
            except ValueError:
                break   # not the main thread of the main interpreter
        if args.http is not None:
            return run_http(scheduler, args, tokenizer, eos_id, args.http,
                            ready_cb=ready_cb, drain=drain)
        return run_stdio(scheduler, args, tokenizer, eos_id,
                         stdin=stdin, stdout=stdout, drain=drain)
    finally:
        if watchdog is not None:
            watchdog.stop()
        if sink is not None:
            from nezha_tpu import obs
            obs.end_run()
        for sig, handler in old_handlers.items():
            signal.signal(sig, handler)
        faults.install(prev_plan)


# ------------------------------------------------------- multi-replica
def _worker_argv(args, rid: int, port: int, role: Optional[str] = None
                 ) -> list:
    """The argv for one spawned worker process: the front end's own
    flags minus the router-only ones, plus the worker's port, its tier
    role (disaggregated topologies), and a per-replica run-dir
    subdirectory when telemetry is on."""
    argv = [sys.executable, "-m", "nezha_tpu.cli.serve",
            "--role", role or getattr(args, "role", "both")]
    if args.random_init:
        argv.append("--random-init")
    elif args.ckpt_dir:
        argv += ["--ckpt-dir", args.ckpt_dir]
    elif args.hf_dir:
        argv += ["--hf-dir", args.hf_dir]
    argv += ["--model", getattr(args, "model", "gpt2"),
             "--model-preset", args.model_preset,
             "--max-batch-size", str(args.max_batch_size),
             "--max-len", str(args.max_len),
             "--max-prefill-len", str(args.max_prefill_len),
             "--k-max", str(args.k_max),
             "--queue-capacity", str(args.queue_capacity),
             "--max-new-tokens", str(args.max_new_tokens),
             "--cache-dtype", args.cache_dtype,
             "--decode-horizon", str(args.decode_horizon),
             "--kv-block-size", str(args.kv_block_size),
             "--kv-dtype", args.kv_dtype,
             "--prefix-cache", args.prefix_cache,
             "--kv-eviction", args.kv_eviction,
             "--kv-host-blocks", str(args.kv_host_blocks),
             # Multi-tenant scheduling knobs (PR 19) ride into every
             # worker: admission, WFQ, and preemption are replica-side
             # (the router only routes; autoscale stays router-side).
             "--preemption", getattr(args, "preemption", "off"),
             "--preemption-budget",
             str(getattr(args, "preemption_budget", 2)),
             # Digest knobs ride into every worker: the /healthz
             # digest payload is built replica-side (PR 17).
             "--digest-interval",
             str(getattr(args, "digest_interval", 2.0)),
             "--digest-max-entries",
             str(getattr(args, "digest_max_entries", 256)),
             "--drain-timeout", str(args.drain_timeout),
             "--trace-sample", str(getattr(args, "trace_sample", 1.0)),
             "--watchdog-interval",
             str(getattr(args, "watchdog_interval", 0.0) or 0.0),
             "--seed", str(args.seed),
             "--mesh", str(getattr(args, "mesh", 1) or 1),
             # Long-context prefill knobs ride into every worker: the
             # router is chunk-blind — sequence sharding happens on
             # each worker's own mesh (PR 20).
             "--prefill-mode",
             getattr(args, "prefill_mode", "replicated"),
             "--seq-prefill-variant",
             getattr(args, "seq_prefill_variant", "auto"),
             "--http", str(port)]
    # SLOs ride into every worker: each process-backend replica
    # evaluates them against its own registry and streams typed events
    # to its replica run-dir (the router evaluates the fleet's).
    for spec in getattr(args, "slo", None) or []:
        argv += ["--slo", str(spec)]
    if args.kv_num_blocks is not None:
        argv += ["--kv-num-blocks", str(args.kv_num_blocks)]
    if getattr(args, "priority_weights", None):
        argv += ["--priority-weights", str(args.priority_weights)]
    if getattr(args, "tenant_queue_cap", None) is not None:
        argv += ["--tenant-queue-cap", str(args.tenant_queue_cap)]
    if getattr(args, "speculative", False):
        # Speculation rides into every worker: the router is
        # draft-blind (accept/verify is engine-internal).
        argv += ["--speculative", "--draft-k", str(args.draft_k)]
        if args.draft_layers is not None:
            argv += ["--draft-layers", str(args.draft_layers)]
        if getattr(args, "draft_ckpt_dir", None):
            argv += ["--draft-ckpt-dir", args.draft_ckpt_dir]
        if getattr(args, "draft_hf_dir", None):
            argv += ["--draft-hf-dir", args.draft_hf_dir]
    if args.tokenizer:
        argv += ["--tokenizer", args.tokenizer]
    if args.prefill_buckets:
        argv += ["--prefill-buckets", str(args.prefill_buckets)]
    if getattr(args, "long_prefill_buckets", None):
        argv += ["--long-prefill-buckets",
                 str(args.long_prefill_buckets)]
    if args.decode_impl:
        argv += ["--decode-impl", args.decode_impl]
    if args.prefill_impl:
        argv += ["--prefill-impl", args.prefill_impl]
    if args.eos_id is not None:
        argv += ["--eos-id", str(args.eos_id)]
    if args.platform:
        argv += ["--platform", args.platform]
    if args.run_dir:
        import os
        argv += ["--run-dir", os.path.join(args.run_dir,
                                           f"replica{rid}")]
    return argv


def run_multi(args, ready_cb=None, drain_event=None) -> int:
    """The ``--replicas N`` front end: supervisor spawns N workers,
    router serves HTTP over them, SIGTERM/SIGINT rolls the drain
    through the replicas one at a time. This process never initializes
    a jax backend or compiles a program — the workers own the engines
    (the parent package import itself is still paid once at CLI
    startup). With ``--replica-backend thread`` the workers share this
    process instead, trading OS isolation for spawn cost
    (tests/benchmarks)."""
    import copy
    import signal

    from nezha_tpu import faults
    from nezha_tpu.serve.router import Router, run_front_end
    from nezha_tpu.serve.supervisor import (ProcessBackend, RouterConfig,
                                            Supervisor, ThreadBackend)
    if args.http is None:
        raise SystemExit("--replicas N > 1 (or --prefill-replicas/"
                         "--decode-replicas) requires --http PORT "
                         "(the router is an HTTP front end)")
    prev_plan = faults.active()
    faults.install_from_env()

    roles: tuple = ()
    total = args.replicas
    if args.prefill_replicas or args.decode_replicas:
        # Disaggregated tiers: N prefill workers + M decode workers;
        # admissions land on the prefill tier and finished prompts'
        # KV migrates to the decode tier (RUNBOOK §10).
        if args.prefill_replicas < 1 or args.decode_replicas < 1:
            raise SystemExit("--prefill-replicas and --decode-replicas "
                             "must both be >= 1 for a disaggregated "
                             "front end")
        roles = (("prefill",) * args.prefill_replicas
                 + ("decode",) * args.decode_replicas)
        total = len(roles)

    def role_of(rid: int) -> str:
        return roles[rid] if roles else args.role

    # Affinity routing defaults ON for a genuine multi-replica fleet
    # (that is where cross-replica reuse exists to win) and OFF for a
    # single replica, unless the flag pins it either way.
    affinity = getattr(args, "affinity_routing", None) \
        or ("on" if total > 1 else "off")
    cfg = RouterConfig(
        replicas=total, roles=roles,
        probe_interval_s=args.probe_interval,
        probe_misses=args.probe_misses,
        route_retries=args.route_retries,
        restart_backoff_base_s=args.restart_backoff,
        max_restart_failures=args.max_restart_failures,
        drain_timeout_s=args.drain_timeout,
        seed=args.seed,
        affinity_routing=(affinity == "on"),
        digest_interval_s=getattr(args, "digest_interval", 2.0),
        digest_max_entries=getattr(args, "digest_max_entries", 256),
        autoscale_min=getattr(args, "autoscale_min", None),
        autoscale_max=getattr(args, "autoscale_max", None))
    from nezha_tpu import obs
    try:
        # The router is the trace-minting edge: the sample knob lives
        # here (workers inherit it via argv passthrough so a replica
        # minting for a direct request agrees with the router).
        obs.set_trace_sample(getattr(args, "trace_sample", 1.0))
    except ValueError as e:
        raise SystemExit(f"--trace-sample: {e}")
    # The fleet-level watchdog: sees the router registry (replica-flap
    # rule) — and, in thread mode, the shared registry every member
    # writes, so the per-replica rules cover the whole fleet too.
    # Started first so a bad --slo spec exits before a sink opens.
    watchdog = _start_watchdog(args)
    sink = None
    if args.run_dir:
        from nezha_tpu.serve.router import register_router_instruments
        sink = obs.start_run(args.run_dir, meta={
            "kind": "serve_router", "replicas": total,
            "roles": ",".join(roles) if roles else "both",
            "backend": args.replica_backend})
        register_router_instruments()
    if args.replica_backend == "thread":
        wargs = copy.copy(args)
        wargs.replicas, wargs.http, wargs.run_dir = 1, None, None
        wargs.prefill_replicas = wargs.decode_replicas = 0
        backend = ThreadBackend(wargs,
                                drain_timeout_s=args.drain_timeout,
                                roles=roles)
    else:
        import os
        backend = ProcessBackend(
            lambda rid, port: _worker_argv(args, rid, port,
                                           role_of(rid)),
            log_dir=(os.path.join(args.run_dir, "logs")
                     if args.run_dir else None))
    sup = Supervisor(backend, cfg)
    router = Router(sup, cfg)
    drain = drain_event if drain_event is not None else threading.Event()
    old_handlers = {}
    try:
        sup.start()
        router.start()
        # Same contract as the worker: handlers only set the event; the
        # front end owns the rolling drain. Installed after the
        # supervisor is up so a wedged spawn stays Ctrl-C-able.
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                old_handlers[sig] = signal.signal(
                    sig, lambda signum, frame: drain.set())
            except ValueError:
                break   # not the main thread of the main interpreter
        return run_front_end(router, sup, args.http, ready_cb=ready_cb,
                             drain=drain,
                             drain_timeout_s=args.drain_timeout)
    finally:
        router.stop()
        sup.shutdown()
        if watchdog is not None:
            watchdog.stop()
        if sink is not None:
            from nezha_tpu import obs
            obs.end_run()
        for sig, handler in old_handlers.items():
            signal.signal(sig, handler)
        faults.install(prev_plan)


def run(args, stdin=None, stdout=None, ready_cb=None,
        drain_event=None) -> int:
    if (getattr(args, "replicas", 1) > 1
            or getattr(args, "autoscale_min", None) is not None
            or getattr(args, "autoscale_max", None) is not None
            or getattr(args, "prefill_replicas", 0)
            or getattr(args, "decode_replicas", 0)):
        # Autoscale bounds force router mode even at --replicas 1: an
        # elastic fleet that STARTS at one replica still needs the
        # supervisor/router pair to grow past it.
        return run_multi(args, ready_cb=ready_cb,
                         drain_event=drain_event)
    return run_worker(args, stdin=stdin, stdout=stdout,
                      ready_cb=ready_cb, drain_event=drain_event)


def main(argv=None) -> int:
    return run(build_parser().parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
