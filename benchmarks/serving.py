"""Serving load generator: offered load vs TTFT/TPOT percentiles.

Drives the in-process continuous-batching stack (`nezha_tpu.serve`) the
way EQuARX-style training benchmarks drive collectives: measure the REAL
hot path (admission -> slot prefill -> batched decode) rather than a
proxy, and write the same run-dir telemetry artifacts `nezha-train`
produces, so `nezha-telemetry RUN_DIR` renders the serving report and
`tools/check_telemetry_schema.py` validates it.

Two load models:

- **closed** loop (--concurrency N): N requests always outstanding —
  measures capacity (tokens/sec at full batch occupancy).
- **open** loop (--rate R): Poisson arrivals at R req/s wall-clock —
  measures latency under offered load; queue-full arrivals are DROPPED
  and counted (that is the backpressure behaving, not an error).

With ``--replicas N`` (closed loop only) the same load drives the
multi-replica ROUTER instead of one scheduler — N thread-hosted
replicas, each its own engine behind a real HTTP socket — and
``--kill-rate R`` hard-kills live replicas on a seeded Poisson schedule
while the load runs: the record pins ``lost == 0`` (every request gets
a 200 or a typed error) next to kills / restarts / failovers and
clean-finish percentiles (docs/RUNBOOK.md §10).

Usage::

    python benchmarks/serving.py --requests 32 --concurrency 4 \
        --run-dir /tmp/serve_bench --json
    python benchmarks/serving.py --mode open --rate 20 --requests 64
    python benchmarks/serving.py --replicas 3 --kill-rate 0.5 \
        --requests 64 --concurrency 8 --json
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--mode", choices=["closed", "open"], default="closed")
    p.add_argument("--requests", type=int, default=16,
                   help="total requests to issue")
    p.add_argument("--concurrency", type=int, default=4,
                   help="closed loop: requests kept outstanding")
    p.add_argument("--rate", type=float, default=8.0,
                   help="open loop: offered arrivals per second")
    p.add_argument("--prompt-len", type=int, default=8)
    p.add_argument("--prompt-len-mix", default=None,
                   help="comma-separated prompt lengths cycled across "
                        "requests (overrides --prompt-len) — a mixed-"
                        "length load exercises the prefill buckets, and "
                        "the record reports TTFT percentiles per bucket")
    p.add_argument("--max-new-tokens", type=int, default=16)
    p.add_argument("--sample-fraction", type=float, default=0.5,
                   help="fraction of requests that sample at temperature "
                        "0.8 / top-k 40 (rest decode greedy) — a mixed "
                        "batch exercises the per-row sampling path")
    p.add_argument("--max-batch-size", type=int, default=4)
    p.add_argument("--max-len", type=int, default=64)
    p.add_argument("--max-prefill-len", type=int, default=16)
    p.add_argument("--prefill-buckets", default=None,
                   help="comma-separated static prefill pad widths "
                        "(default: powers of two up to --max-prefill-len)")
    p.add_argument("--decode-impl",
                   choices=["auto", "kernel", "xla"], default=None,
                   help="decode attention: flash-decode kernel vs the "
                        "composed masked path (the before/after knob)")
    p.add_argument("--prefill-impl",
                   choices=["auto", "kernel", "xla"], default=None,
                   help="paged prefill attention: flash-prefill kernel "
                        "(int8 pools fuse the block write into its "
                        "epilogue) vs the composed masked path (the "
                        "TTFT before/after knob)")
    p.add_argument("--prefill-mode",
                   choices=["replicated", "sequence"],
                   default="replicated",
                   help="prefill chunk parallelism: replicated = every "
                        "mesh device computes the full chunk; sequence "
                        "= shard the chunk over the 1xM mesh's "
                        "sequence axis (needs --mesh M > 1 — the "
                        "long-context before/after knob)")
    p.add_argument("--long-prefill-buckets", default=None,
                   help="comma-separated extra prefill pad widths "
                        "above --max-prefill-len (inside --max-len) so "
                        "long prompts prefill in a few wide chunks")
    p.add_argument("--seq-prefill-variant",
                   choices=["auto", "ulysses", "ring"], default="auto",
                   help="sequence-mode attention algorithm (auto = "
                        "ulysses)")
    p.add_argument("--decode-horizon", default="1",
                   help="tokens decoded per compiled step dispatch; a "
                        "comma-separated list (e.g. 1,4,8) sweeps the "
                        "horizon — one engine + fresh warmup per value, "
                        "with per-horizon sub-records (and per-horizon "
                        "run-dir subdirectories h<N>/) in the output")
    p.add_argument("--kv-block-size", type=int, default=16,
                   help="tokens per KV block")
    p.add_argument("--kv-num-blocks", type=int, default=None,
                   help="total pool blocks (block 0 scratch); "
                        "default = every slot can reach max_len. Set it "
                        "BELOW that to measure "
                        "block-budget admission: concurrency then "
                        "tracks resident tokens, not slots")
    p.add_argument("--kv-dtype", choices=["bf16", "int8"],
                   default="bf16",
                   help="KV block storage: int8 stores blocks as int8 "
                        "+ per-block fp32 scales — the "
                        "capacity-at-equal-memory knob; the record "
                        "reports peak resident bytes so equal-byte "
                        "budgets compare directly")
    p.add_argument("--kv-host-blocks", type=int, default=0,
                   help="paged int8: host KV spill tier budget in "
                        "blocks — evicted prefix-cache blocks demote "
                        "to host RAM and promote back on a returning "
                        "prefix hit (0 = off); the record reports "
                        "demotions/promotions and host-tier peaks")
    p.add_argument("--churn-users", type=int, default=0,
                   help="multi-tenant churn scenario (the kv_churn "
                        "suite's traffic shape): N > 0 cycles requests "
                        "over N 'users', each with a fixed block-"
                        "aligned prompt prefix + a fresh per-visit "
                        "tail — sized so device blocks CYCLE between "
                        "a user's visits, a revisit is served from "
                        "the host tier (promote) when --kv-host-blocks "
                        "is set and from a cold re-prefill when not; "
                        "the record splits TTFT by first visit vs "
                        "revisit. Run with --concurrency 1: the "
                        "scenario's eviction cadence assumes visits "
                        "issue sequentially (nothing enforces it)")
    p.add_argument("--churn-prefix-len", type=int, default=None,
                   help="churn: per-user prefix length in tokens "
                        "(default 4 KV blocks); must be block-aligned "
                        "for the full prefix to be cacheable")
    p.add_argument("--prefix-cache", choices=["on", "off"], default="on",
                   help="shared-prefix prefill reuse on/off")
    p.add_argument("--shared-prefix-frac", type=float, default=0.0,
                   help="templated traffic: this fraction of requests "
                        "share one common prompt prefix — with the "
                        "paged pool + prefix cache they take block "
                        "REFERENCES instead of re-prefilling, and the "
                        "record reports prefix-hit-rate, "
                        "blocks-resident, and TTFT split by hit/miss")
    p.add_argument("--shared-prefix-len", type=int, default=None,
                   help="shared prefix length in tokens (default: 2 KV "
                        "blocks); non-shared requests are padded to "
                        "the same total length so hit/miss TTFT "
                        "compares like for like")
    p.add_argument("--speculative", action="store_true",
                   help="speculative decoding: a draft model proposes "
                        "--draft-k tokens per window, one target "
                        "forward verifies them — the record gains "
                        "spec{draft_k, accept_rate, tokens_per_verify} "
                        "and the headline tokens/sec reflects >1 token "
                        "emitted per verify dispatch")
    p.add_argument("--draft-k", type=int, default=4,
                   help="speculative: draft tokens per verify window")
    p.add_argument("--draft-layers", type=int, default=None,
                   help="speculative: early-exit self-draft depth "
                        "(default: full depth — identity draft, accept "
                        "rate ~1, the machinery-overhead measurement)")
    p.add_argument("--fault-rate", type=float, default=0.0,
                   help="probability per prefill / per decode step of an "
                        "injected fault (prefill errors + NaN logit "
                        "bursts, seeded by --seed) — measures resilience "
                        "overhead: errored requests retire with "
                        "finish_reason 'error' while the run keeps "
                        "serving, and the error/retry counters land in "
                        "the run-dir artifact next to TTFT/TPOT")
    p.add_argument("--queue-capacity", type=int, default=16)
    p.add_argument("--priority-mix", default=None,
                   help="multi-tenant storm traffic: 'class=weight,...' "
                        "over {interactive,batch,background} — each "
                        "request draws its priority class from this "
                        "seeded distribution (default: everything "
                        "interactive, the classic single-lane load). "
                        "The record gains a per-class TTFT split")
    p.add_argument("--priority-scheduling", choices=["on", "off"],
                   default="on",
                   help="'off' SUBMITS every request in the default "
                        "lane (exact pre-WFQ FIFO — the overload_storm "
                        "suite's control) while the record still "
                        "splits TTFT by each request's DRAWN class "
                        "from --priority-mix")
    p.add_argument("--preemption", choices=["on", "off"], default="off",
                   help="preempt lower-priority live decodes to the "
                        "trie/host tier when a higher-priority request "
                        "cannot get a slot or its blocks")
    p.add_argument("--preemption-budget", type=int, default=2,
                   help="max suspensions per request (anti-thrash)")
    p.add_argument("--replicas", type=int, default=1,
                   help="N > 1 drives the multi-replica router "
                        "(supervisor + N in-process replicas, each its "
                        "own engine, reached over real HTTP) instead "
                        "of one scheduler — closed loop only")
    p.add_argument("--affinity-routing", choices=["on", "off"],
                   default="on",
                   help="--replicas > 1: prefix-affinity routing — the "
                        "router scores live replicas by how much of "
                        "the prompt their advertised trie digest "
                        "covers (discounted by load) instead of pure "
                        "least-loaded, and hands near-miss picks a "
                        "peer pull_from pointer; off = the "
                        "least-loaded control")
    p.add_argument("--digest-interval", type=float, default=2.0,
                   help="--replicas > 1: seconds between replica trie-"
                        "digest rebuilds (advertised over /healthz)")
    p.add_argument("--digest-max-entries", type=int, default=256,
                   help="--replicas > 1: bound on advertised digest "
                        "entries per replica (recency-first)")
    p.add_argument("--disaggregate", action="store_true",
                   help="drive the DISAGGREGATED router: "
                        "--prefill-replicas role=prefill workers take "
                        "admissions and park prompt KV, "
                        "--decode-replicas role=decode workers pull "
                        "the migrated blocks (int8+scales wire) and "
                        "stream — the record gains migration GB/s and "
                        "the prefill-wait/decode-wait queueing split "
                        "(closed loop only)")
    p.add_argument("--prefill-replicas", type=int, default=1,
                   help="disaggregated: prefill-tier size")
    p.add_argument("--decode-replicas", type=int, default=1,
                   help="disaggregated: decode-tier size")
    p.add_argument("--kill-rate", type=float, default=0.0,
                   help="expected replica kills per second (seeded "
                        "Poisson schedule) while the measured load "
                        "runs — requires --replicas > 1 (or "
                        "--disaggregate, where kills are AIMED AT THE "
                        "PREFILL TIER: the mid-migration crash drill); "
                        "killed replicas are restarted by the "
                        "supervisor and the record reports kills / "
                        "restarts / failovers / typed errors next to "
                        "the clean-finish percentiles")
    p.add_argument("--model-preset", choices=["tiny", "full"],
                   default="tiny")
    p.add_argument("--mesh", type=int, default=1,
                   help="M > 1 runs the engine TENSOR-SHARDED over a "
                        "1xM device mesh (serve/sharded): params "
                        "Megatron-sharded, paged K/V head-sharded, "
                        "frozen program contract per mesh — requires M "
                        "visible devices and num_heads %% M == 0 "
                        "(single-replica closed/open loop only)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--run-dir", default=None,
                   help="write telemetry artifacts here")
    p.add_argument("--obs-windows", choices=["on", "off"], default="on",
                   help="install the rolling-window tap during a "
                        "--run-dir capture (off = the capture-only "
                        "baseline of nezha-bench's scrape_overhead "
                        "suite)")
    p.add_argument("--scrape-interval", type=float, default=0.0,
                   help="when > 0, a background thread renders the "
                        "Prometheus /metrics exposition from the live "
                        "registry every N seconds during the measured "
                        "load — what a 1s scraper costs the serving "
                        "path (needs --run-dir)")
    p.add_argument("--json", action="store_true",
                   help="print the result record as JSON")
    p.add_argument("--platform", default=None)
    return p


def _percentiles(values):
    from nezha_tpu.obs.registry import percentile_of
    s = sorted(values)
    return {"p50": percentile_of(s, 50), "p90": percentile_of(s, 90),
            "p99": percentile_of(s, 99)}


def _parse_priority_mix(spec: str):
    """``'class=weight,...'`` -> ``[(class, cumulative_fraction)]``
    draw table (SystemExit on malformed specs, like every knob)."""
    from nezha_tpu.serve import PRIORITIES
    weights = []
    for part in str(spec).split(","):
        part = part.strip()
        if not part:
            continue
        cls, eq, w = part.partition("=")
        cls = cls.strip()
        try:
            val = float(w)
        except ValueError:
            val = -1.0
        if not eq or cls not in PRIORITIES or val <= 0:
            raise SystemExit(
                f"--priority-mix entries must be 'class=weight' with "
                f"class in {PRIORITIES} and weight > 0, got {part!r}")
        weights.append((cls, val))
    if not weights:
        raise SystemExit("--priority-mix must name at least one class")
    total = sum(w for _, w in weights)
    table, cum = [], 0.0
    for cls, w in weights:
        cum += w / total
        table.append((cls, cum))
    return table


def run(args) -> dict:
    # Argv validation BEFORE the (expensive) model build + warmup.
    if not 0.0 <= args.fault_rate < 1.0:
        raise SystemExit(f"--fault-rate must be in [0, 1), got "
                         f"{args.fault_rate}")
    try:
        horizons = [int(h) for h in str(args.decode_horizon).split(",")]
        if not horizons or min(horizons) < 1:
            raise ValueError
    except ValueError:
        raise SystemExit(f"--decode-horizon must be comma-separated "
                         f"ints >= 1, got {args.decode_horizon!r}")
    if args.replicas < 1:
        raise SystemExit(f"--replicas must be >= 1, got {args.replicas}")
    if args.kill_rate < 0:
        raise SystemExit(f"--kill-rate must be >= 0, got "
                         f"{args.kill_rate}")
    if args.disaggregate:
        if args.prefill_replicas < 1 or args.decode_replicas < 1:
            raise SystemExit("--disaggregate needs --prefill-replicas "
                             "and --decode-replicas both >= 1")
    elif args.kill_rate > 0 and args.replicas < 2:
        raise SystemExit("--kill-rate needs --replicas > 1 (killing "
                         "the only replica measures a blackout, not "
                         "failover)")
    from nezha_tpu.cli.common import setup_jax
    setup_jax(args)

    if (getattr(args, "prefill_mode", "replicated") == "sequence"
            and int(getattr(args, "mesh", 1) or 1) < 2):
        raise SystemExit("--prefill-mode sequence requires --mesh M "
                         "with M > 1 (the chunk is sharded over the "
                         "mesh's sequence axis)")
    if getattr(args, "mesh", 1) > 1 and (args.replicas > 1
                                         or args.disaggregate):
        raise SystemExit("--mesh > 1 applies to the single-replica "
                         "loops (the router benches compose meshes "
                         "via nezha-serve --replicas --mesh)")
    if args.replicas > 1 or args.disaggregate:
        if len(horizons) != 1:
            raise SystemExit("--replicas > 1 takes a single "
                             "--decode-horizon value, not a sweep")
        if args.mode != "closed":
            raise SystemExit("--replicas > 1 supports closed-loop "
                             "load only (open-loop arrivals belong to "
                             "the single-replica latency study)")
        if getattr(args, "churn_users", 0) and not args.disaggregate:
            record = _run_fleet(args, horizons[0])
        else:
            record = _run_replicas(args, horizons[0])
        if args.json:
            print(json.dumps(record, indent=2, sort_keys=True))
        elif "fleet" in record:
            fl = record["fleet"]
            peer = fl.get("peer_pull") or {}
            print(f"fleet replicas={record['replicas']} "
                  f"affinity={fl['affinity_routing']}: "
                  f"{fl['users']} users x {fl['visits']} visits, "
                  f"revisit/first ttft p50 "
                  f"{fl['revisit_vs_first_ttft_p50']:.2f}, "
                  f"{fl['affinity_wins']} affinity wins, "
                  f"{fl['kv_pulls']} pulls "
                  f"({fl['kv_pull_bytes'] / 1024:.1f} KiB), hits "
                  f"{fl['fleet_hits']}, peer installed "
                  f"{peer.get('installed', 0)}")
        else:
            lat = record["latency_s"]
            mig = record.get("migration") or {}
            mig_s = (f", {mig['count']} migrations "
                     f"{mig['gb_per_s'] * 1e3:.2f} MB/s "
                     f"({mig['fallbacks']} fallbacks)"
                     if mig.get("count") is not None else "")
            print(f"replicas={record['replicas']} closed load "
                  f"{record['offered']}: "
                  f"{record['finished_clean']}/{record['requests']} "
                  f"clean ({record['answered']} answered, "
                  f"{record['lost']} lost), "
                  f"{record['kills']} kills {record['restarts']} "
                  f"restarts {record['failovers']} failovers "
                  f"{record['retries']} retries, "
                  f"latency p50 {lat['p50'] * 1e3:.1f} ms "
                  f"p99 {lat['p99'] * 1e3:.1f} ms{mig_s}")
        return record

    import jax

    if args.model_preset == "tiny":
        from nezha_tpu.cli.train import TINY_GPT2_KW
        from nezha_tpu.models.gpt2 import GPT2, GPT2Config
        model = GPT2(GPT2Config(**TINY_GPT2_KW))
    else:
        from nezha_tpu.models.gpt2 import gpt2_124m
        model = gpt2_124m()
    variables = model.init(jax.random.PRNGKey(args.seed))
    if len(horizons) == 1:
        record = _run_one(args, model, variables, horizons[0],
                          args.run_dir)
    else:
        # Horizon sweep: one engine + warmup + (optional) run-dir
        # capture per value, same offered load — the dispatch-
        # amortization record ISSUE 5 establishes.
        by_horizon = {}
        for h in horizons:
            sub = (os.path.join(args.run_dir, f"h{h}")
                   if args.run_dir else None)
            by_horizon[str(h)] = _run_one(args, model, variables, h, sub)
        record = {"sweep": "decode_horizon",
                  "horizons": horizons,
                  "mode": args.mode,
                  "by_horizon": by_horizon}
    if args.json:
        print(json.dumps(record, indent=2, sort_keys=True))
    else:
        for rec in (record["by_horizon"].values()
                    if "by_horizon" in record else [record]):
            gap = rec.get("host_gap_s") or {}
            gap_s = (f", host gap p50 {gap['p50'] * 1e3:.2f} ms"
                     if gap else "")
            sp = rec.get("spec")
            sp_s = (f", spec k={sp['draft_k']} "
                    f"{sp['tokens_per_verify']:.2f} tok/verify "
                    f"({sp['accept_rate']:.0%} accept)" if sp else "")
            print(f"h={rec['decode_horizon']} {rec['mode']} load: "
                  f"{rec['offered']} -> "
                  f"{rec['tokens_per_sec']:.1f} tok/s "
                  f"({rec['steps_per_sec']:.1f} steps/s, "
                  f"{rec['dispatches_per_token']:.3f} disp/tok), "
                  f"ttft p50 {rec['ttft_s']['p50'] * 1e3:.1f} ms, "
                  f"tpot p50 {rec['tpot_s']['p50'] * 1e3:.1f} ms, "
                  f"{rec['dropped_queue_full']} dropped{gap_s}{sp_s}")
    return record


def _run_one(args, model, variables, decode_horizon: int,
             run_dir) -> dict:
    import jax.numpy as jnp

    from nezha_tpu import obs
    from nezha_tpu.serve import (Engine, QueueFull, Request, Scheduler,
                                 ServeConfig)

    buckets = tuple(int(b) for b in args.prefill_buckets.split(",")) \
        if args.prefill_buckets else ()
    spec = None
    if getattr(args, "speculative", False):
        from nezha_tpu.serve.engine import SpeculativeConfig
        spec = SpeculativeConfig(draft_k=args.draft_k,
                                 draft_layers=args.draft_layers)
    cfg = ServeConfig(
        max_batch_size=args.max_batch_size, max_len=args.max_len,
        max_prefill_len=args.max_prefill_len, prefill_buckets=buckets,
        queue_capacity=args.queue_capacity, cache_dtype=jnp.bfloat16,
        decode_impl=args.decode_impl, decode_horizon=decode_horizon,
        prefill_impl=getattr(args, "prefill_impl", None),
        kv_block_size=args.kv_block_size,
        kv_num_blocks=args.kv_num_blocks,
        prefix_cache=args.prefix_cache == "on",
        kv_dtype=args.kv_dtype,
        kv_host_blocks=getattr(args, "kv_host_blocks", 0),
        prefill_mode=getattr(args, "prefill_mode", "replicated"),
        long_prefill_buckets=tuple(
            int(b) for b in
            str(args.long_prefill_buckets).split(","))
        if getattr(args, "long_prefill_buckets", None) else (),
        seq_prefill_variant=getattr(args, "seq_prefill_variant",
                                    "auto"),
        preemption=getattr(args, "preemption", "off") == "on",
        preemption_budget=getattr(args, "preemption_budget", 2),
        speculative=spec)
    mesh_m = int(getattr(args, "mesh", 1) or 1)
    if mesh_m > 1:
        from nezha_tpu.serve.sharded import ShardedEngine
        engine = ShardedEngine(model, variables, cfg,
                               mesh_devices=mesh_m)
    else:
        engine = Engine(model, variables, cfg)
    sched = Scheduler(engine)
    rng = random.Random(args.seed)
    vocab = engine.vocab

    prompt_lens = ([int(x) for x in str(args.prompt_len_mix).split(",")]
                   if args.prompt_len_mix else [args.prompt_len])
    prompt_len_of = {}                 # request_id -> prompt length
    # Templated traffic: one seeded common prefix; shared requests are
    # prefix + a short random tail, and NON-shared requests draw a fully
    # random prompt of the SAME total length, so hit-vs-miss TTFT
    # compares equal prefill spans. The cache seeder (first shared
    # arrival to actually PREFILL — a miss by construction) is
    # classified with the misses: classification reads the live trie,
    # so a would-be seeder that never ran (queue-full drop, injected
    # prefill error before registration) doesn't misfile its successor.
    # Multi-tenant churn (the kv_churn scenario): U users, each with a
    # fixed block-aligned prefix, revisited round-robin — request i is
    # user i % U on visit i // U. The pool is expected to be sized so
    # device blocks cycle between a user's visits (the bench harness
    # picks kv_num_blocks ~ 2 users' prefixes): with a host tier the
    # revisit PROMOTES its demoted blocks and prefills one tail chunk;
    # without one it re-prefills cold. TTFT splits by first visit vs
    # revisit are the record.
    churn_users = int(getattr(args, "churn_users", 0) or 0)
    churn_round = {}                   # request_id -> visit index
    churn_plen = 0
    churn_prefixes = []
    if churn_users:
        if args.shared_prefix_frac > 0:
            raise SystemExit("--churn-users and --shared-prefix-frac "
                             "are separate scenarios — pick one")
        churn_plen = args.churn_prefix_len or 4 * args.kv_block_size
        if churn_plen % args.kv_block_size:
            raise SystemExit(
                f"--churn-prefix-len {churn_plen} must be a multiple "
                f"of --kv-block-size {args.kv_block_size} (only full "
                f"blocks are cacheable/demotable)")
        if churn_plen + 2 + args.max_new_tokens > args.max_len:
            raise SystemExit(
                f"--churn-prefix-len {churn_plen} + tail 2 + "
                f"max_new_tokens {args.max_new_tokens} exceeds "
                f"--max-len {args.max_len}")
        churn_prefixes = [[rng.randrange(vocab)
                           for _ in range(churn_plen)]
                          for _ in range(churn_users)]

    shared_prefix = []
    expected_hit = {}                  # request_id -> bool
    if args.shared_prefix_frac > 0:
        plen = args.shared_prefix_len or 2 * args.kv_block_size
        if plen + 2 + args.max_new_tokens > args.max_len:
            raise SystemExit(
                f"--shared-prefix-len {plen} + tail 2 + max_new_tokens "
                f"{args.max_new_tokens} exceeds --max-len {args.max_len}")
        shared_prefix = [rng.randrange(vocab) for _ in range(plen)]

    def _prefix_cached() -> bool:
        trie = getattr(engine.pool, "trie", None)
        return bool(trie and trie.match(shared_prefix))

    # A shared request expects a hit once a prior shared request was
    # actually SUBMITTED (closed-loop bursts create several before the
    # seeder prefills) or the prefix is already in the trie (the
    # backstop that survives a dropped/errored would-be seeder).
    _shared_rids = set()
    _seeder_submitted = {"done": False}

    def note_submitted(rid: str) -> None:
        if rid in _shared_rids:
            _seeder_submitted["done"] = True

    # Multi-tenant storm traffic (the overload_storm suite): each
    # request draws its priority class from the seeded --priority-mix
    # distribution. With --priority-scheduling off the drawn class is
    # RECORDED (the per-class TTFT split still lands in the record) but
    # every submit rides the default lane — the exact pre-WFQ bounded
    # FIFO, the storm suite's head-of-line-blocking control.
    pri_mix = (_parse_priority_mix(args.priority_mix)
               if getattr(args, "priority_mix", None) else None)
    pri_of = {}                        # request_id -> drawn class
    pri_sched = getattr(args, "priority_scheduling", "on") == "on"

    def _draw_priority(rid: str) -> str:
        x = rng.random()
        cls = next((c for c, cum in pri_mix if x < cum),
                   pri_mix[-1][0])
        pri_of[rid] = cls
        return cls if pri_sched else "interactive"

    def make_request(i: int) -> Request:
        sampled = rng.random() < args.sample_fraction
        rid = f"bench-{i}"
        pri = _draw_priority(rid) if pri_mix else "interactive"
        if churn_users:
            u = i % churn_users
            prompt = churn_prefixes[u] + [rng.randrange(vocab),
                                          rng.randrange(vocab)]
            churn_round[rid] = i // churn_users
            prompt_len_of[rid] = len(prompt)
            return Request(prompt=prompt,
                           max_new_tokens=args.max_new_tokens,
                           temperature=0.8 if sampled else 0.0,
                           top_k=40 if sampled else None,
                           seed=i, request_id=rid, priority=pri)
        if shared_prefix and rng.random() < args.shared_prefix_frac:
            prompt = shared_prefix + [rng.randrange(vocab),
                                      rng.randrange(vocab)]
            expected_hit[rid] = (_seeder_submitted["done"]
                                 or _prefix_cached())
            _shared_rids.add(rid)
        elif shared_prefix:
            prompt = [rng.randrange(vocab)
                      for _ in range(len(shared_prefix) + 2)]
            expected_hit[rid] = False
        else:
            n = prompt_lens[i % len(prompt_lens)]
            prompt = [rng.randrange(vocab) for _ in range(n)]
        prompt_len_of[rid] = len(prompt)
        return Request(
            prompt=prompt,
            max_new_tokens=args.max_new_tokens,
            temperature=0.8 if sampled else 0.0,
            top_k=40 if sampled else None,
            seed=i, request_id=rid, priority=pri)

    # Warm EVERY program off the clock — serving steady state never pays
    # trace+compile, and neither should the measurement: one request per
    # prefill bucket (chunked prompts reuse the bucket programs, so this
    # covers long prompts too) plus the shared decode step. Warmup
    # prompts use DISTINCT tokens per bucket: identical prompts would
    # prefix-hit each other in the paged pool, the wider bucket would
    # prefill only its un-cached suffix through a NARROWER program, and
    # the wide program's compile would land inside the measured ttft
    # p99 — the exact spike warmup exists to keep off the clock. The
    # telemetry run starts AFTER warmup so the artifacts hold
    # steady-state percentiles only.
    for j, w in enumerate(engine.cfg.prefill_buckets):
        n = min(w, args.max_len - 1)
        sched.submit(Request(
            prompt=[(131 * j + 7 * i + 1) % vocab for i in range(n)],
            max_new_tokens=1, request_id=f"warmup-{j}"))
    sched.run_until_idle()
    # Warmup must not leak into the measured record: drop its
    # cached blocks (and any host-demoted ones) and zero the reuse
    # counters so prefix_hit_rate, blocks-resident peaks, and the
    # demote/promote ledgers describe the measured load only.
    engine.pool.clear_prefix_cache()
    engine.pool.prefix_hits = 0
    engine.pool.cow_copies = 0
    if engine.pool.host_blocks:
        # Warm the demote/promote maintenance programs too — the
        # first eviction-demotion or promote-hit of the measured
        # load must not pay their compiles inside a TTFT window.
        engine.pool.warm_host_tier_programs()
        engine.pool.clear_host_tier()
        engine.pool.demotions = 0
        engine.pool.promotions = 0
        engine.pool.promote_failures = 0

    # Chaos mode: a seeded probabilistic plan armed AFTER warmup (a
    # faulted warmup would skip compiling a bucket program) injecting
    # the two request-isolated failure modes — prefill errors and NaN
    # logit bursts. Step crashes are excluded on purpose: their bounded
    # retry means back-to-back coin-flip failures would kill the whole
    # run, which is a different experiment than measuring overhead.
    from nezha_tpu import faults
    prev_plan = faults.active()
    plan = None
    if args.fault_rate > 0:
        plan = faults.FaultPlan.parse(
            f"serve.prefill:error%{args.fault_rate};"
            f"serve.step.logits:nan%{args.fault_rate}", seed=args.seed)
        faults.install(plan)

    sink = None
    if run_dir:
        from nezha_tpu.serve.scheduler import register_serve_instruments
        sink = obs.start_run(run_dir, meta={
            "kind": "serve_bench", "mode": args.mode,
            "requests": args.requests,
            "decode_horizon": decode_horizon,
            "offered": (args.concurrency if args.mode == "closed"
                        else args.rate)},
            windows=getattr(args, "obs_windows", "on") == "on")
        register_serve_instruments()
    # The scrape-overhead measurement (nezha-bench scrape_overhead
    # suite): a background thread rendering the full windowed /metrics
    # exposition from the live registry at --scrape-interval, exactly
    # what an external Prometheus scraper costs the serving path.
    scrape_interval = float(getattr(args, "scrape_interval", 0.0) or 0.0)
    scrape_stop = scrape_thread = None
    scrape_count = [0]
    if scrape_interval > 0 and sink is not None:
        import threading

        from nezha_tpu.obs import timeseries as _ts
        scrape_stop = threading.Event()

        def _scraper():
            while not scrape_stop.wait(scrape_interval):
                windows = (_ts.windows_payload()
                           if _ts.current_windows() is not None else None)
                _ts.render_prometheus(obs.stats_snapshot(), windows)
                scrape_count[0] += 1

        scrape_thread = threading.Thread(target=_scraper, daemon=True,
                                         name="bench-scraper")
        scrape_thread.start()
    steps_before = engine.step_calls      # exclude warmup dispatches
    spec_before = ((engine.spec_verifies, engine.spec_draft_tokens,
                    engine.spec_accepted) if spec else (0, 0, 0))

    # (Occupancy percentiles come from the scheduler itself — it folds
    # per-decode occupancy into the metric.batch_occupancy histogram.)
    t0 = time.monotonic()
    issued = finished = dropped = 0
    peak_resident = peak_blocks = peak_host_blocks = 0

    def _track_peaks():
        # The paged-pool occupancy claim: how many requests were
        # RESIDENT (decoding concurrently) and how many KV blocks that
        # took — a worst-case reservation holds max_len rows a
        # request, the paged pool only what's
        # written, so at equal device memory it peaks strictly
        # higher on under-max_len traffic. The host-tier peak rides
        # along (0 without a tier).
        nonlocal peak_resident, peak_blocks, peak_host_blocks
        peak_resident = max(peak_resident, len(sched._live))
        peak_blocks = max(peak_blocks, engine.pool.blocks_used)
        peak_host_blocks = max(peak_host_blocks,
                               engine.pool.host_blocks_used)

    try:
        if args.mode == "closed":
            while finished < args.requests:
                # Pace by queue room: a closed-loop client waits, it does
                # not shed — hammering submit would inflate rejected_total.
                while (issued < args.requests
                       and issued - finished < args.concurrency
                       and sched.queue_depth < sched.queue_capacity):
                    req = make_request(issued)
                    sched.submit(req)
                    note_submitted(req.request_id)
                    issued += 1
                sched.step()
                _track_peaks()
                # Preempted requests hold no slot and no queue spot but
                # are NOT finished — without this term a preemption-on
                # closed loop would overfeed the queue.
                finished = (issued - sched.queue_depth
                            - len(sched._live) - sched.preempted_count)
        else:
            # Poisson arrivals: exponential inter-arrival gaps at --rate.
            # Arrivals hitting a full queue are DROPPED (open-loop clients
            # don't wait) — the genuine load-shed rejected_total measures.
            arrivals = []
            t = 0.0
            for _ in range(args.requests):
                t += rng.expovariate(args.rate)
                arrivals.append(t)
            while finished + dropped < args.requests:
                now = time.monotonic() - t0
                while issued + dropped < args.requests \
                        and arrivals[issued + dropped] <= now:
                    req = make_request(issued + dropped)
                    try:
                        sched.submit(req)
                        note_submitted(req.request_id)
                        issued += 1
                    except QueueFull:
                        dropped += 1
                if sched.has_work():
                    sched.step()
                    _track_peaks()
                else:
                    time.sleep(0.001)
                finished = (issued - sched.queue_depth
                            - len(sched._live) - sched.preempted_count)
    finally:
        faults.install(prev_plan)
        if scrape_stop is not None:
            scrape_stop.set()
            scrape_thread.join(timeout=2.0)
    wall = time.monotonic() - t0
    decode_steps = engine.step_calls - steps_before

    results = [r for rid, r in sched.results.items()
               if not rid.startswith("warmup")]
    errored = [r for r in results if r.finish_reason == "error"]
    # Error retirements carry partial decodes (or nothing): keep the
    # latency percentiles clean by computing them over CLEAN finishes,
    # while the record reports the error count alongside.
    clean = [r for r in results if r.finish_reason != "error"]
    ttfts = [r.ttft_s for r in clean if r.ttft_s is not None]
    lats = [r.latency_s for r in clean]
    total_tokens = sum(len(r.tokens) for r in results)
    tpots = [(r.latency_s - r.ttft_s) / max(len(r.tokens) - 1, 1)
             for r in clean if r.ttft_s is not None]
    # TTFT per prefill bucket: mixed-length loads show whether short
    # prompts actually get the short-bucket TTFT or queue behind wide
    # prefills (keys are the TAIL-chunk pad widths; chunked prompts
    # group under their tail bucket with chunk count in the label).
    by_bucket = {}
    for r in clean:   # same population as the headline ttft_s above
        n = prompt_len_of.get(r.request_id)
        if n is None or r.ttft_s is None:
            continue
        chunks = -(-n // args.max_prefill_len)  # ceil
        key = f"{engine.bucket_for(n)}" if chunks == 1 \
            else f"{engine.bucket_for(n)}x{chunks}"
        by_bucket.setdefault(key, []).append(r.ttft_s)
    # Host-gap percentiles straight from the live registry (it is only
    # populated while a run is active — the histogram is the same
    # serve.host_gap_s the run-dir summary carries).
    host_gap = None
    if sink is not None:
        hg = obs.histogram("serve.host_gap_s").summary()
        if hg["count"]:
            host_gap = {k: hg[k] for k in ("count", "p50", "p90", "p99")}
    record = {
        "mode": args.mode,
        "offered": (args.concurrency if args.mode == "closed"
                    else args.rate),
        "requests": args.requests, "finished": len(results),
        "dropped_queue_full": dropped,
        "wall_s": wall,
        "tokens": total_tokens,
        "tokens_per_sec": total_tokens / wall if wall else 0.0,
        # The dispatch-amortization record: compiled step dispatches
        # for the measured load (warmup excluded) — horizon H should
        # show ~1/H the dispatches per token of horizon 1.
        "decode_horizon": decode_horizon,
        "decode_steps": decode_steps,
        "steps_per_sec": decode_steps / wall if wall else 0.0,
        "dispatches_per_token": (decode_steps / total_tokens
                                 if total_tokens else 0.0),
        "host_gap_s": host_gap,
        "ttft_s": _percentiles(ttfts),
        "ttft_by_bucket": {k: _percentiles(v)
                           for k, v in sorted(by_bucket.items())},
        "tpot_s": _percentiles(tpots),
        "latency_s": _percentiles(lats),
        "prefill_buckets": list(engine.cfg.prefill_buckets),
        "decode_impl": args.decode_impl or "auto",
        "prefill_impl": getattr(args, "prefill_impl", None) or "auto",
        "mesh_devices": getattr(engine, "mesh_devices", 1),
        "compile_cache": engine.compile_stats(),
        # Paged-pool occupancy record: resident-request and
        # blocks-resident peaks are THE concurrency-at-equal-memory
        # comparison (what the block budget admits against the
        # budget // max_len a worst-case reservation would).
        "kv": {
            "layout": "paged",
            "dtype": args.kv_dtype,
            "block_size": args.kv_block_size,
            "num_blocks": engine.pool.num_blocks,
            "bytes_per_block": engine.pool.bytes_per_block,
            "prefix_cache": args.prefix_cache == "on",
            "prefix_hits": engine.pool.prefix_hits,
            "cow_copies": engine.pool.cow_copies,
            # Host spill tier (all 0 when --kv-host-blocks is off):
            # the demote/promote ledgers plus the tier's peak
            # occupancy — "promotions tracking demotions" is the
            # churn scenario's health signature.
            "host_blocks": engine.pool.host_blocks,
            "demotions": engine.pool.demotions,
            "promotions": engine.pool.promotions,
            "promote_failures": engine.pool.promote_failures,
            "peak_host_blocks_used": peak_host_blocks,
            "peak_resident_requests": peak_resident,
            "peak_blocks_used": peak_blocks,
            # Peak device bytes the resident KV held — the number the
            # int8-vs-bf16 equal-memory comparison is actually about
            # (blocks are not comparable across dtypes; bytes are).
            "peak_bytes_resident":
                peak_blocks * engine.pool.bytes_per_block,
        },
        "faults": {
            "rate": args.fault_rate,
            "injected": plan.num_injected if plan else 0,
            "by_point": plan.injected_counts if plan else {},
            "errored": len(errored),
        },
        # What the telemetry plane itself cost this record: whether the
        # rolling-window tap was installed, and how many /metrics
        # expositions the in-process scraper rendered during the load.
        "telemetry": {
            "windows": (run_dir is not None
                        and getattr(args, "obs_windows", "on") == "on"),
            "scrape_interval_s": scrape_interval,
            "scrapes": scrape_count[0],
        },
    }
    if spec:
        # The speculative headline (ISSUE 13 acceptance): tokens
        # EMITTED per verify dispatch (> 1 means the draft is paying
        # for itself) and the realized draft accept rate, measured
        # over the post-warmup load only.
        verifies = engine.spec_verifies - spec_before[0]
        drafted = engine.spec_draft_tokens - spec_before[1]
        accepted = engine.spec_accepted - spec_before[2]
        record["spec"] = {
            "draft_k": spec.draft_k,
            "draft_layers": spec.draft_layers,
            "verifies": verifies,
            "draft_tokens": drafted,
            "accepted_tokens": accepted,
            "accept_rate": accepted / drafted if drafted else 0.0,
            "tokens_per_verify": ((accepted + verifies) / verifies
                                  if verifies else 0.0),
        }
    if pri_mix:
        # TTFT split by DRAWN class over clean finishes — with
        # --priority-scheduling off this shows what FIFO head-of-line
        # blocking costs each class; with it on (+ preemption) it is
        # the overload_storm suite's gated record. Preempt/resume
        # ledgers ride along (always 0 when --preemption off).
        by_class = {}
        for cls in ("interactive", "batch", "background"):
            rs = [r for r in clean if pri_of.get(r.request_id) == cls]
            ts = [r.ttft_s for r in rs if r.ttft_s is not None]
            by_class[cls] = {
                "drawn": sum(1 for p in pri_of.values() if p == cls),
                "finished": len(rs),
                "tokens": sum(len(r.tokens) for r in rs),
                "ttft_s": _percentiles(ts or [0.0]),
                "latency_s": _percentiles(
                    [r.latency_s for r in rs] or [0.0]),
            }
        record["priorities"] = {
            "mix": args.priority_mix,
            "priority_scheduling": pri_sched,
            "preemption": getattr(args, "preemption", "off") == "on",
            "preemption_budget": getattr(args, "preemption_budget", 2),
            "preemptions": sched.preemptions,
            "resumes": sched.resumes,
            "by_class": by_class,
        }
    if churn_users:
        # TTFT by first visit vs revisit over clean finishes: a first
        # visit is a cold prefill by construction; a revisit is served
        # from whatever tier still holds the user's prefix — device
        # trie (fast), host tier via promote (the tentpole's win), or
        # nothing (cold again — the no-host-tier control). The
        # revisit/first p50 ratio is the kv_churn suite's gated
        # number, and promotions > 0 is what proves the host tier (not
        # lucky device residency) served the revisits.
        first = [r.ttft_s for r in clean
                 if churn_round.get(r.request_id) == 0
                 and r.ttft_s is not None]
        revisit = [r.ttft_s for r in clean
                   if churn_round.get(r.request_id, 0) > 0
                   and r.ttft_s is not None]
        p_first = _percentiles(first or [0.0])
        p_revisit = _percentiles(revisit or [0.0])
        record["kv_churn"] = {
            "users": churn_users,
            "visits_per_user": -(-args.requests // churn_users),
            "prefix_len": churn_plen,
            "host_blocks": engine.pool.host_blocks,
            "demotions": engine.pool.demotions,
            "promotions": engine.pool.promotions,
            "promote_failures": engine.pool.promote_failures,
            "prefix_hits": getattr(engine.pool, "prefix_hits", 0),
            "ttft_first_visit_s": p_first,
            "ttft_revisit_s": p_revisit,
            "revisit_vs_first_ttft_p50": (
                p_revisit["p50"] / max(p_first["p50"], 1e-9)),
        }
    if shared_prefix:
        # TTFT by hit/miss over clean finishes: the prefix-reuse win is
        # the GAP between these two (a hit skips the shared span's
        # prefill entirely; its TTFT is queue wait + one short tail
        # chunk + its first block slice).
        ttft_hit = [r.ttft_s for r in clean
                    if expected_hit.get(r.request_id)
                    and r.ttft_s is not None]
        ttft_miss = [r.ttft_s for r in clean
                     if not expected_hit.get(r.request_id)
                     and r.ttft_s is not None]
        record["shared_prefix"] = {
            "frac": args.shared_prefix_frac,
            "len": len(shared_prefix),
            "expected_hits": sum(expected_hit.values()),
            "prefix_hit_rate": (getattr(engine.pool, "prefix_hits", 0)
                                / len(results) if results else 0.0),
            "ttft_hit_s": _percentiles(ttft_hit or [0.0]),
            "ttft_miss_s": _percentiles(ttft_miss or [0.0]),
        }
    if sink is not None:
        obs.end_run()
        # The stitched-trace block (ISSUE 12): per-segment TTFT
        # decomposition percentiles from this run's own spans — every
        # measured request carried a trace id (the scheduler mints at
        # submit while the run is active), so nezha-bench can gate
        # each timeline segment, not just the total.
        from nezha_tpu.obs.report import trace_summary
        record["trace"] = trace_summary(run_dir)
    return record


def _run_replicas(args, decode_horizon: int) -> dict:
    """Closed-loop load against the multi-replica router, optionally
    under a seeded replica-kill schedule (``--kill-rate``): measures
    what scale-out is FOR — the service keeps answering while members
    die and restart. Every request gets exactly one answer (200 or a
    typed error object); the record pins ``lost == 0`` alongside
    kills / restarts / failovers / retries and clean-finish
    percentiles. Replicas are thread-backed (each its own engine,
    reached over real HTTP sockets, killable mid-decode) so the bench
    pays one process.

    With ``--disaggregate`` the topology is ``--prefill-replicas``
    role=prefill members + ``--decode-replicas`` role=decode members:
    admissions park prompt KV on the prefill tier, finished prompts
    migrate over the int8+scales wire, and the record adds migration
    GB/s, the prefill-wait/decode-wait queueing split, and fallback
    counts; ``--kill-rate`` then AIMS at the prefill tier — the
    SIGKILL-mid-migration chaos drill."""
    import threading

    from nezha_tpu import faults, obs
    from nezha_tpu.cli.serve import build_parser as serve_parser
    from nezha_tpu.serve.router import Router, register_router_instruments
    from nezha_tpu.serve.scheduler import register_serve_instruments
    from nezha_tpu.serve.supervisor import (RouterConfig, Supervisor,
                                            ThreadBackend)

    wargv = ["--random-init", "--model-preset", args.model_preset,
             "--max-batch-size", str(args.max_batch_size),
             "--max-len", str(args.max_len),
             "--max-prefill-len", str(args.max_prefill_len),
             "--queue-capacity", str(args.queue_capacity),
             "--decode-horizon", str(decode_horizon),
             "--max-new-tokens", str(args.max_new_tokens),
             # KV-pool shape rides into every worker (the fleet KV
             # scenarios need paged pools with pinned block geometry;
             # plain replica runs get the same defaults they always
             # did), and the digest knobs ride along so /healthz
             # advertises what the affinity scorer consumes.
             "--kv-block-size", str(args.kv_block_size),
             "--kv-dtype", args.kv_dtype,
             "--kv-host-blocks", str(getattr(args, "kv_host_blocks", 0)),
             "--prefix-cache", args.prefix_cache,
             "--digest-interval", str(args.digest_interval),
             "--digest-max-entries", str(args.digest_max_entries),
             "--seed", str(args.seed)]
    if args.kv_num_blocks:
        wargv += ["--kv-num-blocks", str(args.kv_num_blocks)]
    if args.prefill_buckets:
        wargv += ["--prefill-buckets", str(args.prefill_buckets)]
    if args.decode_impl:
        wargv += ["--decode-impl", args.decode_impl]
    if getattr(args, "prefill_impl", None):
        wargv += ["--prefill-impl", args.prefill_impl]
    if args.platform:
        wargv += ["--platform", args.platform]
    if getattr(args, "speculative", False):
        # Speculation rides into every replica worker, exactly as the
        # nezha-serve front end forwards it (the router is draft-blind).
        wargv += ["--speculative", "--draft-k", str(args.draft_k)]
        if args.draft_layers is not None:
            wargv += ["--draft-layers", str(args.draft_layers)]
    wargs = serve_parser().parse_args(wargv)
    roles: tuple = ()
    total = args.replicas
    if args.disaggregate:
        roles = (("prefill",) * args.prefill_replicas
                 + ("decode",) * args.decode_replicas)
        total = len(roles)
    cfg = RouterConfig(
        replicas=total, roles=roles,
        probe_interval_s=0.1, probe_misses=3,
        restart_backoff_base_s=0.05, restart_backoff_max_s=0.5,
        drain_timeout_s=5.0, seed=args.seed,
        affinity_routing=args.affinity_routing == "on",
        digest_interval_s=args.digest_interval,
        digest_max_entries=args.digest_max_entries)
    sup = Supervisor(ThreadBackend(wargs, drain_timeout_s=5.0,
                                   roles=roles), cfg)
    router = Router(sup, cfg)

    rng = random.Random(args.seed)
    vocab = 512 if args.model_preset == "tiny" else 50257
    prompt_lens = ([int(x) for x in str(args.prompt_len_mix).split(",")]
                   if args.prompt_len_mix else [args.prompt_len])
    payloads = []
    for i in range(args.requests):
        sampled = rng.random() < args.sample_fraction
        n = prompt_lens[i % len(prompt_lens)]
        p = {"id": f"bench-{i}",
             "prompt_tokens": [rng.randrange(vocab) for _ in range(n)],
             "max_new_tokens": args.max_new_tokens, "seed": i}
        if sampled:
            p.update(temperature=0.8, top_k=40)
        payloads.append(p)

    sink = plan = None
    prev_plan = faults.active()
    try:
        sup.start()
        router.start()
        if not router.wait_live(total, timeout_s=600):
            raise SystemExit(f"replicas never became live: "
                             f"{sup.describe()}")
        # Warm EVERY replica's programs off the clock — every prompt
        # length in the mix, posted DIRECTLY to each replica's port
        # (router balancing could race two warmups onto one replica
        # and leave another cold; a cold bucket or step program would
        # then compile inside the measured percentiles). Mirrors
        # _run_one's per-bucket warmup.
        import http.client

        def _warm_one(port, j, n):
            conn = http.client.HTTPConnection("127.0.0.1", port,
                                              timeout=600)
            try:
                # Distinct tokens per warmup (see _run_one): identical
                # prompts would prefix-hit in a replica's paged pool
                # and leave wider bucket programs cold.
                conn.request("POST", "/generate", body=json.dumps(
                    {"id": f"warmup-{port}-{j}",
                     "prompt_tokens": [(131 * j + 7 * i + 1) % vocab
                                       for i in range(n)],
                     "max_new_tokens": 1}).encode())
                conn.getresponse().read()
            finally:
                conn.close()

        warm = [threading.Thread(target=_warm_one, args=(r.port, j, n))
                for r in sup.live_replicas()
                for j, n in enumerate(sorted(set(prompt_lens)))]
        for t in warm:
            t.start()
        for t in warm:
            t.join()
        if args.fault_rate > 0:
            plan = faults.FaultPlan.parse(
                f"serve.prefill:error%{args.fault_rate};"
                f"serve.step.logits:nan%{args.fault_rate}",
                seed=args.seed)
            faults.install(plan)
        if args.run_dir:
            sink = obs.start_run(args.run_dir, meta={
                "kind": "serve_router_bench", "mode": "closed",
                "replicas": total, "kill_rate": args.kill_rate,
                "roles": ",".join(roles) if roles else "both",
                "requests": args.requests,
                "decode_horizon": decode_horizon,
                "offered": args.concurrency})
            register_router_instruments()
            register_serve_instruments()
        retries0, failovers0 = router.retries, router.failovers
        restarts0 = sup.restarts
        migrations0, mig_bytes0 = router.migrations, router.migration_bytes
        mig_secs0 = router.migration_seconds
        fallbacks0 = router.migrate_fallbacks

        lock = threading.Lock()
        next_idx = {"n": 0}
        results = []

        def client():
            while True:
                with lock:
                    i = next_idx["n"]
                    if i >= args.requests:
                        return
                    next_idx["n"] += 1
                t_req = time.monotonic()
                code, obj = router.route(payloads[i])
                with lock:
                    results.append(
                        (i, code, obj, time.monotonic() - t_req))

        kills = []
        stop_kill = threading.Event()

        def killer():
            # Seeded Poisson kill schedule; never kills the LAST live
            # replica (that measures a blackout, not failover). On a
            # disaggregated topology the kills are AIMED at the
            # prefill tier — the SIGKILL-mid-migration drill the
            # acceptance pins (decode members survive to prove the
            # failover; the local-decode fallback covers the window
            # where the whole prefill tier is down).
            krng = random.Random(args.seed + 1)
            while not stop_kill.is_set():
                if stop_kill.wait(min(krng.expovariate(args.kill_rate),
                                      5.0)):
                    return
                live = sup.live_replicas()
                pool = ([r for r in live if r.role == "prefill"]
                        if args.disaggregate else live)
                if len(live) >= 2 and pool:
                    victim = pool[krng.randrange(len(pool))].rid
                    sup.kill(victim)
                    kills.append(victim)

        t0 = time.monotonic()
        clients = [threading.Thread(target=client)
                   for _ in range(args.concurrency)]
        for t in clients:
            t.start()
        kt = None
        if args.kill_rate > 0:
            kt = threading.Thread(target=killer, daemon=True)
            kt.start()
        for t in clients:
            t.join()
        stop_kill.set()
        if kt is not None:
            kt.join(timeout=10)
        wall = time.monotonic() - t0
        # Recovery check: the supervisor should restart every kill;
        # give backoff a moment before reading the final live count.
        router.wait_live(total, timeout_s=120)
        recovered_live = sup.live_count()
    finally:
        faults.install(prev_plan)
        if sink is not None:
            obs.end_run()
        router.stop()
        sup.shutdown()

    ok = [(i, c, o, lat) for i, c, o, lat in results if c == 200]
    clean = [(i, c, o, lat) for i, c, o, lat in ok
             if o.get("finish_reason") in ("length", "eos")]
    errors_typed = {}
    for i, c, o, lat in results:
        if c != 200:
            kind = (o.get("error_type") if isinstance(o, dict)
                    else None) or f"http_{c}"
            errors_typed[kind] = errors_typed.get(kind, 0) + 1
    tokens = sum(len(o.get("tokens", [])) for _, _, o, _ in ok)
    # Per-token decode latency from the SERVING replica's own clock
    # (worker-reported latency_s/ttft_s pair — route latency would
    # fold admission hops and the migration transfer into "decode"
    # time): the decode-tier steady-state number the disaggregation
    # acceptance compares against the co-located baseline. Falls back
    # to the route latency for stub replicas that report none.
    tpots = [((o["latency_s"] if o.get("latency_s") is not None
               else lat) - o["ttft_s"])
             / max(len(o.get("tokens", [])) - 1, 1)
             for _, _, o, lat in clean if o.get("ttft_s") is not None]
    migs = [o["migration"] for _, _, o, _ in ok
            if isinstance(o.get("migration"), dict)]
    mig_secs = router.migration_seconds - mig_secs0
    mig_bytes = router.migration_bytes - mig_bytes0
    record_mig = None
    if args.disaggregate:
        record_mig = {
            "count": router.migrations - migrations0,
            "bytes": mig_bytes,
            "seconds": mig_secs,
            # Mean PER-PULL wire rate: total bytes over the SUM of the
            # individual pull windows (export + install + ACK each) —
            # what one migration sustains on the wire. Concurrent pulls
            # overlap, so this deliberately is NOT aggregate fleet
            # throughput; divide `bytes` by the record's `wall_s` for
            # a (load-diluted) aggregate bound.
            "gb_per_s": (mig_bytes / mig_secs / 1e9) if mig_secs else 0.0,
            "fallbacks": router.migrate_fallbacks - fallbacks0,
        }
    trace_block = None
    if args.run_dir:
        # Stitched fleet traces: with the thread backend every
        # replica's fragments land in this one capture, so the
        # decomposition covers the router hop, the migration transfer,
        # and both tiers' queue waits.
        from nezha_tpu.obs.report import trace_summary
        trace_block = trace_summary(args.run_dir)
    return {
        "mode": "closed",
        "replicas": total,
        "trace": trace_block,
        "disaggregate": bool(args.disaggregate),
        "roles": list(roles),
        "kill_rate": args.kill_rate,
        "decode_horizon": decode_horizon,
        "offered": args.concurrency,
        "requests": args.requests,
        "answered": len(results),
        # The zero-silently-lost pin: every issued request produced
        # exactly one answer — a 200 or a typed error object.
        "lost": args.requests - len(results),
        "finished_clean": len(clean),
        "clean_finish_fraction": (len(clean) / args.requests
                                  if args.requests else 0.0),
        "errors_typed": errors_typed,
        "kills": len(kills), "killed_rids": kills,
        "restarts": sup.restarts - restarts0,
        "failovers": router.failovers - failovers0,
        "retries": router.retries - retries0,
        "recovered_live": recovered_live,
        "wall_s": wall,
        "tokens": tokens,
        "tokens_per_sec": tokens / wall if wall else 0.0,
        "latency_s": _percentiles(
            [lat for _, _, _, lat in clean] or [0.0]),
        "ttft_s": _percentiles(
            [o["ttft_s"] for _, _, o, _ in clean
             if o.get("ttft_s") is not None] or [0.0]),
        "tpot_s": _percentiles(tpots or [0.0]),
        "migration": record_mig,
        # The queueing-delay split per tier (disaggregated runs only:
        # time to the parked prefill answer vs the decode replica's
        # TTFT for the migrated request).
        "prefill_wait_s": _percentiles(
            [m["prefill_wait_s"] for m in migs
             if m.get("prefill_wait_s") is not None] or [0.0]),
        "decode_wait_s": _percentiles(
            [m["decode_wait_s"] for m in migs
             if m.get("decode_wait_s") is not None] or [0.0]),
        "faults": {"rate": args.fault_rate,
                   "injected": plan.num_injected if plan else 0,
                   "errored": sum(1 for _, _, o, _ in ok
                                  if o.get("finish_reason") == "error")},
    }


def _run_fleet(args, decode_horizon: int) -> dict:
    """The fleet-wide KV reuse scenario (``--replicas N
    --churn-users U``): U users with distinct block-aligned prompt
    prefixes revisit a ROUTED fleet sequentially, against per-replica
    pools each deliberately too small to hold every user's prefix.

    With ``--affinity-routing on``, visit 0 lands by consistent-hash
    cold placement (users SPREAD across the fleet, so the aggregate
    device cache holds every prefix), trie digests propagate over the
    /healthz probes, and each revisit routes back to its owner's warm
    trie — the fleet serves from cache what no single pool could hold.
    The ``off`` control routes least-loaded: sequential traffic piles
    every user onto one replica, whose pool cycles, so revisits
    re-prefill cold. A peer-pull phase (affinity runs only) then
    saturates one owner's admission queue and routes a revisit — the
    router must place it on a sibling with a ``pull_from`` pointer to
    the full owner, and the blocks arrive over the ``/kv_export``
    int8 wire instead of being re-prefilled.

    The record splits TTFT by first visit / revisit / peer-pull hit
    and carries the affinity-win and pull ledgers; ``nezha-bench``'s
    fleet_kv suite gates it."""
    import http.client
    import threading

    from nezha_tpu import obs
    from nezha_tpu.cli.serve import build_parser as serve_parser
    from nezha_tpu.serve import fleetcache
    from nezha_tpu.serve.router import Router, register_router_instruments
    from nezha_tpu.serve.scheduler import register_serve_instruments
    from nezha_tpu.serve.supervisor import (RouterConfig, Supervisor,
                                            ThreadBackend)

    users = int(args.churn_users)
    churn_plen = args.churn_prefix_len or 4 * args.kv_block_size
    if churn_plen % args.kv_block_size:
        raise SystemExit(
            f"--churn-prefix-len {churn_plen} must be a multiple of "
            f"--kv-block-size {args.kv_block_size} (only full blocks "
            f"are cacheable/advertisable)")
    if churn_plen + 2 + args.max_new_tokens > args.max_len:
        raise SystemExit(
            f"--churn-prefix-len {churn_plen} + tail 2 + "
            f"max_new_tokens {args.max_new_tokens} exceeds "
            f"--max-len {args.max_len}")
    if args.prefix_cache != "on":
        raise SystemExit("the fleet scenario needs "
                         "--prefix-cache on (digests summarize "
                         "the prefix trie)")
    visits = max(2, -(-args.requests // users))
    affinity = args.affinity_routing == "on"
    blocks_per_user = churn_plen // args.kv_block_size

    wargv = ["--random-init", "--model-preset", args.model_preset,
             "--max-batch-size", str(args.max_batch_size),
             "--max-len", str(args.max_len),
             "--max-prefill-len", str(args.max_prefill_len),
             "--queue-capacity", str(args.queue_capacity),
             "--decode-horizon", str(decode_horizon),
             "--max-new-tokens", str(args.max_new_tokens),
             "--kv-block-size", str(args.kv_block_size),
             "--kv-dtype", args.kv_dtype,
             "--kv-host-blocks", str(getattr(args, "kv_host_blocks", 0)),
             "--prefix-cache", args.prefix_cache,
             "--digest-interval", str(args.digest_interval),
             "--digest-max-entries", str(args.digest_max_entries),
             "--seed", str(args.seed)]
    if args.kv_num_blocks:
        wargv += ["--kv-num-blocks", str(args.kv_num_blocks)]
    if args.platform:
        wargv += ["--platform", args.platform]
    wargs = serve_parser().parse_args(wargv)
    cfg = RouterConfig(
        replicas=args.replicas,
        probe_interval_s=0.1, probe_misses=3,
        restart_backoff_base_s=0.05, restart_backoff_max_s=0.5,
        drain_timeout_s=5.0, seed=args.seed,
        affinity_routing=affinity,
        digest_interval_s=args.digest_interval,
        digest_max_entries=args.digest_max_entries)
    sup = Supervisor(ThreadBackend(wargs, drain_timeout_s=5.0), cfg)
    router = Router(sup, cfg)

    rng = random.Random(args.seed)
    vocab = 512 if args.model_preset == "tiny" else 50257
    prefixes = [[rng.randrange(vocab) for _ in range(churn_plen)]
                for _ in range(users)]
    hashes = [fleetcache.prefix_hashes(p, args.kv_block_size)
              for p in prefixes]

    def payload(u: int, v, seed: int) -> dict:
        # Fixed per-user prefix + a fresh 2-token tail per visit: the
        # prefix is the reusable span, the tail forces a real (if
        # tiny) prefill on every visit so TTFT is never zero-work.
        return {"id": f"fleet-u{u}-v{v}",
                "prompt_tokens": prefixes[u] + [rng.randrange(vocab),
                                                rng.randrange(vocab)],
                "max_new_tokens": args.max_new_tokens, "seed": seed}

    def _post(port, obj, timeout=600):
        conn = http.client.HTTPConnection("127.0.0.1", port,
                                          timeout=timeout)
        try:
            conn.request("POST", "/generate",
                         body=json.dumps(obj).encode())
            resp = conn.getresponse()
            return resp.status, json.loads(resp.read() or b"{}")
        finally:
            conn.close()

    def _owner_of(hs):
        for r in sup.replicas():
            parsed = fleetcache.digest_entries_of(r.last_health)
            if parsed and fleetcache.coverage(parsed[1], hs)[0] \
                    >= blocks_per_user:
                return r
        return None

    sink = None
    ttft_first, ttft_revisit = [], []
    peer = None
    try:
        sup.start()
        router.start()
        if not router.wait_live(args.replicas, timeout_s=600):
            raise SystemExit(f"replicas never became live: "
                             f"{sup.describe()}")
        # Warm every replica's programs off the clock: the full churn
        # prompt covers every chunk program a cold prefill runs; a
        # 2-token prompt covers the tail-only program a digest-hit
        # revisit (or a pulled prefill) runs.
        warm = [threading.Thread(target=_post, args=(
                    r.port,
                    {"id": f"warmup-{r.rid}-{j}",
                     "prompt_tokens": [(131 * j + 7 * i + 1) % vocab
                                       for i in range(n)],
                     "max_new_tokens": 1}))
                for r in sup.live_replicas()
                for j, n in enumerate(sorted({churn_plen + 2, 2}))]
        for t in warm:
            t.start()
        for t in warm:
            t.join()
        # Warmup must not leak into the measured record: every pool
        # drops its cached blocks and zeroes the reuse ledgers.
        for r in sup.replicas():
            sched = r.handle.worker._sched
            with sched._lock:
                pool = sched.engine.pool
                pool.clear_prefix_cache()
                pool.prefix_hits = 0
                pool.cow_copies = 0
                pool.fleet_hits = {"device": 0, "host": 0, "peer": 0}
                if pool.host_blocks:
                    pool.warm_host_tier_programs()
                    pool.clear_host_tier()
                    pool.demotions = 0
                    pool.promotions = 0
                    pool.promote_failures = 0
        if args.run_dir:
            sink = obs.start_run(args.run_dir, meta={
                "kind": "serve_fleet_bench", "mode": "closed",
                "replicas": args.replicas,
                "requests": users * visits, "offered": 1,
                "decode_horizon": decode_horizon,
                "affinity": args.affinity_routing})
            register_router_instruments()
            register_serve_instruments()
        wins0 = router.affinity_wins
        pulls0, pbytes0 = router.kv_pulls, router.kv_pull_bytes

        # Phase 1 — first visits, sequential: cold placement spreads
        # users across the fleet (affinity) or piles them onto the
        # least-loaded member (control).
        for u in range(users):
            code, obj = router.route(payload(u, 0, u))
            if code == 200 and obj.get("ttft_s") is not None:
                ttft_first.append(obj["ttft_s"])

        # Phase 2 — let the digests propagate. The affinity run waits
        # until the ROUTER's own probe snapshots advertise every
        # user's full prefix (that snapshot is exactly what revisits
        # route on); the control — whose single serving pool cycles,
        # so full fleet coverage never materializes — waits a fixed
        # digest+probe interval instead, equalizing cache age across
        # the two runs.
        if affinity:
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                if all(_owner_of(hs) is not None for hs in hashes):
                    break
                time.sleep(0.05)
        else:
            time.sleep(2 * args.digest_interval + 0.5)

        # Phase 3 — revisits, sequential rounds.
        for v in range(1, visits):
            for u in range(users):
                code, obj = router.route(payload(u, v, v * users + u))
                if code == 200 and obj.get("ttft_s") is not None:
                    ttft_revisit.append(obj["ttft_s"])

        # Phase 4 — the peer-pull drill (affinity runs only): clamp
        # user 0's owner to a zero-capacity admission queue (the
        # deterministic stand-in for a saturated replica — the
        # ThreadBackend's workers are in-process, so the clamp is one
        # attribute write) and route a revisit.  The router forwards
        # to the owner first (best score), eats its queue-full 503,
        # re-picks the sibling, and — the whole point — hands it a
        # ``pull_from`` pointer at the still-exporting owner, so the
        # prefix arrives over the int8 wire instead of a cold
        # prefill.  ``/kv_export`` needs no admission, which is why a
        # full owner's cache keeps paying off.
        if affinity:
            owner = _owner_of(hashes[0])
            peer = {"owner_rid": owner.rid if owner else None,
                    "saturated": False, "attempts": 0,
                    "ttft_s": None, "pull_s": None, "installed": 0,
                    "bytes": 0, "degraded": None}
            if owner is not None:
                owner_sched = owner.handle.worker._sched
                cap = owner_sched.queue_capacity
                try:
                    owner_sched.queue_capacity = 0
                    code, _ = _post(owner.port,
                                    {"id": "probe-full",
                                     "prompt_tokens": [1, 2, 3],
                                     "max_new_tokens": 1})
                    peer["saturated"] = code == 503
                    attempts = 0
                    while peer["saturated"] and attempts < 5:
                        attempts += 1
                        code, obj = router.route(
                            payload(0, f"pull{attempts}",
                                    9000 + attempts))
                        fp = (obj.get("fleet_pull")
                              if code == 200 and isinstance(obj, dict)
                              else None)
                        if isinstance(fp, dict):
                            peer["degraded"] = fp.get("degraded")
                            if fp.get("installed"):
                                peer["ttft_s"] = obj.get("ttft_s")
                                peer["pull_s"] = fp.get("seconds")
                                peer["installed"] = fp.get(
                                    "installed", 0)
                                peer["bytes"] = fp.get("bytes", 0)
                                break
                    peer["attempts"] = attempts
                finally:
                    owner_sched.queue_capacity = cap

        wins = router.affinity_wins - wins0
        pulls = router.kv_pulls - pulls0
        pull_bytes = router.kv_pull_bytes - pbytes0
        fleet_hits = {"device": 0, "host": 0, "peer": 0}
        prefix_hits = 0
        for r in sup.replicas():
            w = getattr(r.handle, "worker", None)
            if w is None or w.dead.is_set():
                continue
            pool = w._sched.engine.pool
            for k in fleet_hits:
                fleet_hits[k] += pool.fleet_hits.get(k, 0)
            prefix_hits += getattr(pool, "prefix_hits", 0)
    finally:
        if sink is not None:
            obs.end_run()
        router.stop()
        sup.shutdown()

    p_first = _percentiles(ttft_first or [0.0])
    p_revisit = _percentiles(ttft_revisit or [0.0])
    return {
        "mode": "closed",
        "replicas": args.replicas,
        "decode_horizon": decode_horizon,
        "offered": 1,
        "requests": users * visits,
        "fleet": {
            "users": users, "visits": visits,
            "prefix_len": churn_plen,
            "affinity_routing": args.affinity_routing,
            "digest_interval_s": args.digest_interval,
            "digest_max_entries": args.digest_max_entries,
            "affinity_wins": wins,
            "kv_pulls": pulls,
            "kv_pull_bytes": pull_bytes,
            "fleet_hits": fleet_hits,
            "prefix_hits": prefix_hits,
            "ttft_first_visit_s": p_first,
            "ttft_revisit_s": p_revisit,
            "revisit_vs_first_ttft_p50": (
                p_revisit["p50"] / max(p_first["p50"], 1e-9)),
            "peer_pull": peer,
        },
    }


def main(argv=None) -> int:
    run(build_parser().parse_args(argv))
    return 0


if __name__ == "__main__":
    sys.exit(main())
