"""Disaggregated prefill/decode tiers with fault-tolerant paged-block
migration (the ISSUE 11 acceptance suite).

Layers under test, bottom up: the int8+scales wire codec
(serve/migrate.py), pool-level block export/install (ref == 1 writes,
trie registration), the scheduler's park/export/ack/resume lifecycle
(two-phase handoff with a TTL backstop), the router's disaggregated
pipeline over role-tagged replicas (admission -> migrate -> decode,
bounded seeded-backoff retries, local-decode degradation), and the
chaos acceptance: SIGKILL a prefill replica mid-migration under load
and prove zero silently-lost requests, zero block/scale leaks on BOTH
pools (the ``leak_check`` oracle runs on every surviving replica after
every drill), and the frozen program contract on every engine. Fault
points drilled here: ``router.migrate``, ``replica.kv_export``,
``replica.kv_install`` (plus ``serve.kv.bind`` via install exhaustion).
"""

import json
import os
import sys
import threading
import time

import pytest

import jax

from nezha_tpu import faults, obs
from nezha_tpu.faults import FaultPlan
from nezha_tpu.serve import (Engine, FinishReason, MigrationError,
                             Request, Scheduler, ServeConfig, migrate)
from nezha_tpu.serve.router import Router, register_router_instruments
from nezha_tpu.serve.supervisor import (RouterConfig, Supervisor,
                                        ThreadBackend)

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "tools"))


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    faults.clear()
    yield
    faults.clear()


@pytest.fixture(scope="module")
def tiny_model():
    from nezha_tpu.cli.train import TINY_GPT2_KW
    from nezha_tpu.models.gpt2 import GPT2, GPT2Config
    model = GPT2(GPT2Config(**TINY_GPT2_KW))
    return model, model.init(jax.random.PRNGKey(0))


def _engine(tiny_model, **kw):
    model, variables = tiny_model
    base = dict(max_batch_size=2, max_len=64, max_prefill_len=16,
                kv_block_size=8, queue_capacity=8)
    base.update(kw)
    return Engine(model, variables, ServeConfig(**base))


def _prompt(n, vocab=512, salt=0):
    return [(7 * i + 3 + 11 * salt) % vocab for i in range(n)]


# ----------------------------------------------------------- wire codec
def test_wire_codec_roundtrip_and_validation(tiny_model):
    import numpy as np
    eng = _engine(tiny_model)
    sched = Scheduler(eng)
    prompt = _prompt(21)
    sched.submit(Request(prompt=prompt, max_new_tokens=4,
                         request_id="w", prefill_only=True))
    sched.run_until_idle()
    wire = sched.export_parked("w")
    assert wire["nblocks"] == 2 and wire["block_size"] == 8
    tokens, layers, nbytes = migrate.decode_wire(wire)
    assert tokens == prompt[:16]
    assert nbytes == wire["nbytes"] > 0
    assert layers[0]["k"].dtype == np.int8
    assert layers[0]["k_scale"].dtype == np.float32
    # corrupt geometry fails typed, before any pool state is touched
    bad = dict(wire, nblocks=3)
    with pytest.raises(MigrationError):
        migrate.decode_wire(bad)
    with pytest.raises(MigrationError):
        migrate.decode_wire({"v": 99})
    sched.ack_parked("w")
    eng.pool.leak_check()


def test_wire_format_is_the_parents_and_round_trips_bit_exactly(tiny_model):
    """The pool is lane-dense rows (``[N, bs, H*D]``, PR 27); the WIRE is
    what ``serve/migrate.py`` wrote before: per-head tiles
    ``[n, heads, bs, d]`` int8 + ``[n, heads]`` scales. Held against a
    payload RECORDED from the parent commit's code (PR 26: same tiny
    model, seed, prompt, int8 pool; ``tests/data/kv_wire_pr26_int8.json``):
    today's export writes the same header and the same bytes; the old
    payload installs into a fresh pool of today's layout; and a block
    exported from that pool is the installed payload again, bit for
    bit, as are the pool's own rows."""
    import numpy as np
    from nezha_tpu.ops.quant import merge_heads

    with open(os.path.join(_ROOT, "tests", "data",
                           "kv_wire_pr26_int8.json")) as f:
        recorded = json.load(f)
    assert (recorded["heads"], recorded["head_dim"], recorded["nblocks"],
            recorded["block_size"]) == (4, 16, 2, 8)

    src = _engine(tiny_model, kv_dtype="int8")
    ss = Scheduler(src)
    prompt = _prompt(21)
    ss.submit(Request(prompt=prompt, max_new_tokens=4, request_id="w",
                      prefill_only=True))
    ss.run_until_idle()
    wire = ss.export_parked("w")
    assert wire == recorded            # header AND payload bytes
    ss.ack_parked("w")
    src.pool.leak_check()

    # The parent's payload into a fresh pool of today's layout ...
    tokens, layers, nbytes = migrate.decode_wire(recorded)
    assert layers[0]["k"].shape == (2, 4, 8, 16)
    assert layers[0]["k_scale"].shape == (2, 4)
    dst = _engine(tiny_model, kv_dtype="int8")
    sd = Scheduler(dst)
    assert sd.install_migrated(tokens, layers, nbytes) == 2
    pool = dst.pool
    assert pool.caches[0]["k"].shape == (pool.num_blocks, 8, 4 * 16)
    # ... lands as rows: head h of a position in lanes h*16 .. (h+1)*16
    blocks = np.asarray(pool.trie.match(tokens))
    for layer, pay in zip(pool.caches, layers):
        for kv in ("k", "v"):
            assert np.array_equal(np.asarray(layer[kv])[blocks],
                                  np.asarray(merge_heads(pay[kv])))
            assert np.array_equal(
                np.asarray(layer[f"{kv}_scale"])[blocks],
                pay[f"{kv}_scale"])
    # ... and comes out again as the same wire, bit for bit.
    covered, out, out_bytes = pool.export_prefix_payload(tokens)
    assert covered == tokens and out_bytes == nbytes
    again = migrate.encode_wire(covered, out, pool.block_size)
    assert again == recorded
    pool.leak_check()


# ------------------------------------------------- scheduler lifecycle
def test_park_export_install_ack_bf16(tiny_model):
    """The two-phase handoff at scheduler level: park on A, pull into
    B's prefix cache, ACK releases A — leak_check clean on BOTH pools,
    and B's admission takes prefix-cache references (a genuine hit)."""
    a, b = _engine(tiny_model), _engine(tiny_model)
    sa, sb = Scheduler(a), Scheduler(b)
    prompt = _prompt(21)
    sa.submit(Request(prompt=prompt, max_new_tokens=6,
                      request_id="m", prefill_only=True))
    sa.run_until_idle()
    assert sa.results["m"].finish_reason == FinishReason.PREFILLED
    assert sa.parked_count == 1
    tokens, layers, nbytes = migrate.decode_wire(sa.export_parked("m"))
    assert sb.install_migrated(tokens, layers, nbytes) == 2
    assert sa.ack_parked("m") is True
    assert sa.ack_parked("m") is False          # idempotent, no double free
    assert sa.parked_count == 0
    a.pool.leak_check()
    sb.submit(Request(prompt=prompt, max_new_tokens=6, request_id="m"))
    sb.run_until_idle()
    res = sb.results["m"]
    assert res.finish_reason == "length" and len(res.tokens) == 6
    assert b.pool.prefix_hits == 1
    b.pool.leak_check()


def test_int8_migration_is_bit_identical(tiny_model):
    """int8 pools ship their blocks verbatim (the wire IS the storage
    format), so a migrated request's greedy decode matches a local
    int8 decode token for token."""
    kw = dict(kv_dtype="int8")
    src, dst, ref = (_engine(tiny_model, **kw) for _ in range(3))
    ss, sd, sr = Scheduler(src), Scheduler(dst), Scheduler(ref)
    prompt = _prompt(29)
    sr.submit(Request(prompt=prompt, max_new_tokens=8, request_id="r"))
    sr.run_until_idle()
    ss.submit(Request(prompt=prompt, max_new_tokens=8,
                      request_id="p", prefill_only=True))
    ss.run_until_idle()
    tokens, layers, nbytes = migrate.decode_wire(ss.export_parked("p"))
    sd.install_migrated(tokens, layers, nbytes)
    ss.ack_parked("p")
    sd.submit(Request(prompt=prompt, max_new_tokens=8, request_id="p"))
    sd.run_until_idle()
    assert sd.results["p"].tokens == sr.results["r"].tokens
    src.pool.leak_check()
    dst.pool.leak_check()


def test_resume_parked_local_decode(tiny_model):
    """The role=both degradation: a parked request resumes and decodes
    locally on its source — same result shape, no leak."""
    eng = _engine(tiny_model)
    sched = Scheduler(eng)
    sched.submit(Request(prompt=_prompt(21), max_new_tokens=6,
                         request_id="loc", prefill_only=True))
    sched.run_until_idle()
    assert sched.resume_parked("loc") is True
    assert sched.resume_parked("loc") is False
    sched.run_until_idle()
    res = sched.results["loc"]
    assert res.finish_reason == "length" and len(res.tokens) == 6
    assert sched.parked_count == 0
    eng.pool.leak_check()


def test_parked_ttl_expiry_frees_blocks(tiny_model):
    """The leak-proofing backstop: a park nobody pulls, ACKs, or
    resumes (decode replica died post-pull, ACK lost on the wire) is
    reclaimed at its TTL — blocks return to the pool."""
    eng = _engine(tiny_model)
    sched = Scheduler(eng)
    sched.parked_ttl_s = 0.02
    sched.submit(Request(prompt=_prompt(21), max_new_tokens=4,
                         request_id="exp", prefill_only=True))
    sched.run_until_idle()
    assert sched.parked_count == 1
    time.sleep(0.05)
    sched.step()
    assert sched.parked_count == 0
    with pytest.raises(KeyError):
        sched.export_parked("exp")
    eng.pool.leak_check()
    # every remaining block is held by the prefix cache alone (the
    # prompt's full blocks stay cached, evictable — not a leak)
    assert eng.pool.blocks_used == eng.pool.trie_only_blocks


def test_cancel_remaining_sweeps_parked(tiny_model):
    """Drain sweeps parked migrations: a drained source stops being
    pullable (typed 404 at the router's next /kv_export) and leaks
    nothing."""
    eng = _engine(tiny_model)
    sched = Scheduler(eng)
    sched.submit(Request(prompt=_prompt(21), max_new_tokens=4,
                         request_id="d", prefill_only=True))
    sched.run_until_idle()
    assert sched.parked_count == 1
    sched.cancel_remaining()
    assert sched.parked_count == 0
    eng.pool.leak_check()
    # every remaining block is held by the prefix cache alone (the
    # prompt's full blocks stay cached, evictable — not a leak)
    assert eng.pool.blocks_used == eng.pool.trie_only_blocks


def test_install_exhaustion_is_typed_and_leak_free(tiny_model):
    """An install the destination pool cannot hold raises the typed
    KVBlocksExhausted (wrapped as MigrationError by the pull client)
    and releases every block it allocated — the retryable-failure half
    of the crash-leaves-one-owner contract."""
    from nezha_tpu.serve.slots import KVBlocksExhausted
    src = _engine(tiny_model)
    # destination with almost no blocks (1 scratch + 2 usable)
    dst = _engine(tiny_model, kv_num_blocks=3)
    ss, sd = Scheduler(src), Scheduler(dst)
    prompt = _prompt(33)                        # 4 full blocks of 8
    ss.submit(Request(prompt=prompt, max_new_tokens=4,
                      request_id="x", prefill_only=True))
    ss.run_until_idle()
    tokens, layers, nbytes = migrate.decode_wire(ss.export_parked("x"))
    with pytest.raises(KVBlocksExhausted):
        sd.install_migrated(tokens, layers, nbytes)
    dst.pool.leak_check()
    assert dst.pool.blocks_used == 0            # partial alloc released
    ss.ack_parked("x")
    src.pool.leak_check()


# --------------------------------------------------- router, role-aware
def _worker_args(extra=()):
    from nezha_tpu.cli.serve import build_parser
    return build_parser().parse_args(
        ["--random-init", "--model-preset", "tiny", "--max-batch-size",
         "2", "--max-len", "64", "--max-prefill-len", "8",
         "--kv-block-size", "8", "--queue-capacity", "8",
         "--platform", "cpu", *extra])


def _cfg(**kw):
    base = dict(replicas=2, roles=("prefill", "decode"),
                probe_interval_s=0.1, probe_misses=3, route_retries=2,
                retry_backoff_base_s=0.01, retry_backoff_max_s=0.05,
                restart_backoff_base_s=0.05, restart_backoff_max_s=0.5,
                drain_timeout_s=20.0, seed=0)
    base.update(kw)
    return RouterConfig(**base)


def _cluster(cfg):
    sup = Supervisor(ThreadBackend(_worker_args(), drain_timeout_s=20.0,
                                   roles=cfg.roles), cfg)
    router = Router(sup, cfg)
    sup.start()
    assert router.wait_live(cfg.replicas, timeout_s=600), sup.describe()
    return sup, router


def _worker_sched(sup, rid):
    return sup.replicas()[rid].handle.worker._sched


def _leak_check_all(sup):
    """The both-pools oracle: every live replica's pool balances its
    ref-count books and holds no parked leftovers once traffic ends."""
    for r in sup.replicas():
        worker = getattr(r.handle, "worker", None)
        if worker is None or worker.dead.is_set():
            continue
        sched = worker._sched
        sched.engine.pool.leak_check()


def test_roles_config_validation():
    with pytest.raises(ValueError):
        RouterConfig(replicas=2, roles=("prefill",))
    with pytest.raises(ValueError):
        RouterConfig(replicas=2, roles=("prefill", "chef"))
    with pytest.raises(ValueError):
        RouterConfig(replicas=2, roles=("prefill", "prefill"))
    cfg = RouterConfig(replicas=2, roles=("prefill", "decode"))
    assert cfg.disaggregated and cfg.role_of(1) == "decode"
    assert not RouterConfig(replicas=2).disaggregated


@pytest.fixture(scope="module")
def disagg2(tiny_model):
    """1 prefill + 1 decode thread-hosted replicas + router (module
    scoped; chaos tests that consume clusters build their own)."""
    cfg = _cfg()
    sup, router = _cluster(cfg)
    yield sup, router
    router.stop()
    sup.shutdown()


def test_disaggregated_route_end_to_end(disagg2):
    """Admission lands on the prefill tier, the prompt's KV migrates
    over the int8 wire, the decode replica answers — and the response
    carries the migration meta (bytes, queueing split)."""
    sup, router = disagg2
    assert router.wait_live(2, timeout_s=600)
    assert [r["role"] for r in sup.describe()] == ["prefill", "decode"]
    migrations0 = router.migrations
    for i in range(3):
        code, obj = router.route(
            {"id": f"e2e-{i}", "prompt_tokens": _prompt(21, salt=i),
             "max_new_tokens": 5})
        assert code == 200, obj
        assert obj["finish_reason"] == "length"
        assert len(obj["tokens"]) == 5
        mig = obj["migration"]
        assert mig["bytes"] > 0 and mig["blocks"] == 2
        assert mig["acked"] is True
        assert mig["prefill_wait_s"] >= 0
        assert mig["decode_wait_s"] >= 0
    assert router.migrations == migrations0 + 3
    # the decode tier did the decoding: its pool saw the prefix hits
    assert _worker_sched(sup, 1).engine.pool.prefix_hits >= 3
    # two-phase handoff completed: nothing left parked anywhere
    for rid in (0, 1):
        assert _worker_sched(sup, rid).parked_count == 0
    _leak_check_all(sup)


def test_healthz_reports_role_and_parked(disagg2):
    import urllib.request
    sup, router = disagg2
    assert router.wait_live(2, timeout_s=600)
    r0 = sup.replicas()[0]
    with urllib.request.urlopen(
            f"http://127.0.0.1:{r0.port}/healthz", timeout=10) as resp:
        obj = json.loads(resp.read())
    assert obj["role"] == "prefill" and obj["parked"] == 0


def test_router_migrate_fault_is_typed(disagg2):
    """The router.migrate fault point: chaos at the orchestrator itself
    surfaces as the typed injected_fault response, never a dropped
    request; the next request sails through."""
    sup, router = disagg2
    assert router.wait_live(2, timeout_s=600)
    faults.install(FaultPlan.parse("router.migrate:error@1"))
    code, obj = router.route({"id": "rm", "prompt_tokens": _prompt(21),
                              "max_new_tokens": 2})
    assert code == 500 and obj["error_type"] == "injected_fault"
    faults.clear()
    code, obj = router.route({"id": "rm2", "prompt_tokens": _prompt(21),
                              "max_new_tokens": 2})
    assert code == 200, obj
    _leak_check_all(sup)


def test_export_install_faults_retry_to_success(disagg2):
    """replica.kv_export / replica.kv_install drills: a one-shot
    injected failure on either side of the pull surfaces as the typed
    424 the router retries on — the request still finishes 200 and
    neither pool leaks."""
    sup, router = disagg2
    assert router.wait_live(2, timeout_s=600)
    for point in ("replica.kv_export", "replica.kv_install"):
        faults.install(FaultPlan.parse(f"{point}:error@1"))
        retries0 = router.retries + router.migrate_fallbacks
        code, obj = router.route(
            {"id": f"f-{point}", "prompt_tokens": _prompt(21, salt=7),
             "max_new_tokens": 3})
        assert code == 200, (point, obj)
        assert faults.active().injected_counts.get(point) == 1
        # the failure was absorbed by a retry or the local fallback
        assert router.retries + router.migrate_fallbacks > retries0
        faults.clear()
        for rid in (0, 1):
            assert _worker_sched(sup, rid).parked_count == 0
    _leak_check_all(sup)


def test_pull_of_lost_park_is_typed_park_lost(disagg2):
    """A live source whose park is GONE (acked away / TTL / drain)
    answers the pull with 404; the client raises the distinct
    ``park_lost`` kind — the router's restart-immediately signal (no
    doomed sweep of the decode tier)."""
    import urllib.request
    sup, router = disagg2
    assert router.wait_live(2, timeout_s=600)
    port = sup.replicas()[0].port
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate",
        data=json.dumps({"id": "gone", "prompt_tokens": _prompt(21),
                         "max_new_tokens": 4,
                         "prefill_only": True}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as resp:
        assert json.loads(resp.read())["finish_reason"] == "prefilled"
    sched0 = _worker_sched(sup, 0)
    assert sched0.ack_parked("gone") is True      # park released
    dst = _worker_sched(sup, 1)
    with pytest.raises(MigrationError) as ei:
        migrate.pull_into(dst, {"port": port, "request_id": "gone"})
    assert ei.value.kind == "park_lost"
    _leak_check_all(sup)


def test_empty_install_does_not_count_a_migration(tiny_model):
    """serve.kv.migrations_total counts COMMITTED installs: an empty
    sub-block payload (or an already-cached prefix) increments
    nothing."""
    eng = _engine(tiny_model)
    sched = Scheduler(eng)
    sub = _prompt(5)                   # shorter than one 8-token block
    sched.submit(Request(prompt=sub, max_new_tokens=2,
                         request_id="tiny", prefill_only=True))
    sched.run_until_idle()
    wire = sched.export_parked("tiny")
    assert wire["nblocks"] == 0
    dst = _engine(tiny_model)
    sd = Scheduler(dst)
    run_dir_ctr = obs.counter("serve.kv.migrations_total")
    tokens, layers, nbytes = migrate.decode_wire(wire)
    assert sd.install_migrated(tokens, layers, nbytes) == 0
    # no telemetry run is active here, so assert via a second install
    # of a REAL payload double-counting nothing: install the same
    # full-block payload twice — only the first counts.
    sched.ack_parked("tiny")
    sched.submit(Request(prompt=_prompt(21), max_new_tokens=2,
                         request_id="full", prefill_only=True))
    sched.run_until_idle()
    tokens, layers, nbytes = migrate.decode_wire(
        sched.export_parked("full"))
    assert sd.install_migrated(tokens, layers, nbytes) == 2
    assert sd.install_migrated(tokens, layers, nbytes) == 0  # cached
    sched.ack_parked("full")
    eng.pool.leak_check()
    dst.pool.leak_check()
    del run_dir_ctr


def test_no_live_decode_tier_degrades_to_local_decode(tiny_model):
    """Zero live decode replicas: the router falls back to LOCAL decode
    on the prefill replica (resume — the role=both degradation),
    counted in router.migrate_fallbacks_total, and the request still
    answers 200."""
    cfg = _cfg(restart_backoff_base_s=60.0, restart_backoff_max_s=120.0)
    sup, router = _cluster(cfg)
    try:
        sup.kill(1)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and any(
                r.rid == 1 for r in sup.live_replicas()):
            router.probe_all()
            time.sleep(0.02)
        assert all(r.rid != 1 for r in sup.live_replicas())
        fallbacks0 = router.migrate_fallbacks
        code, obj = router.route(
            {"id": "deg", "prompt_tokens": _prompt(21),
             "max_new_tokens": 4})
        assert code == 200, obj
        assert obj.get("resumed") is True
        assert obj["migration"]["fallback"] == "no live decode replica"
        assert router.migrate_fallbacks == fallbacks0 + 1
        sched = _worker_sched(sup, 0)
        assert sched.parked_count == 0
        sched.engine.pool.leak_check()
    finally:
        router.stop()
        sup.shutdown()


def test_prefill_kill_mid_migration_chaos(tiny_model, tmp_path):
    """THE acceptance drill: 2 prefill + 1 decode replicas under
    concurrent load while the prefill tier is killed MID-TRANSFER
    (slowed exports guarantee in-flight migrations at the kill). Every
    request gets exactly one answer — 200 or a typed error — zero
    silently lost; the killed member restarts; leak_check passes on
    every surviving pool (source AND destination); the frozen program
    contract holds on every engine; and the run-dir record carrying
    the migration instruments is schema-valid."""
    import random

    cfg = _cfg(replicas=3, roles=("prefill", "prefill", "decode"),
               drain_timeout_s=20.0)
    sup, router = _cluster(cfg)
    run_dir = str(tmp_path / "mig_chaos")
    obs.start_run(run_dir, meta={"kind": "migration_chaos_test"})
    register_router_instruments()
    from nezha_tpu.serve.scheduler import register_serve_instruments
    register_serve_instruments()
    # Slow the export so the seeded kill provably lands mid-transfer.
    faults.install(FaultPlan.parse("replica.kv_export:delay=0.05x*"))
    try:
        N = 18
        results = []
        lock = threading.Lock()
        next_idx = {"n": 0}

        def client():
            while True:
                with lock:
                    i = next_idx["n"]
                    if i >= N:
                        return
                    next_idx["n"] += 1
                code, obj = router.route(
                    {"id": f"mc-{i}", "prompt_tokens": _prompt(21, salt=i),
                     "max_new_tokens": 4, "seed": i})
                with lock:
                    results.append((i, code, obj))

        threads = [threading.Thread(target=client) for _ in range(4)]
        for t in threads:
            t.start()
        # Kill a prefill replica once a third of the load has answered
        # — exports are slowed, so migrations are in flight.
        krng = random.Random(11)
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            with lock:
                if len(results) >= N // 3:
                    break
            time.sleep(0.005)
        live_prefill = [r for r in sup.live_replicas()
                        if r.role == "prefill"]
        assert live_prefill
        sup.kill(live_prefill[krng.randrange(len(live_prefill))].rid)
        for t in threads:
            t.join(timeout=600)
        assert not any(t.is_alive() for t in threads)

        # Zero silently-lost: one answer per request, typed or 200.
        assert sorted(i for i, _, _ in results) == list(range(N))
        typed = {"no_live_replicas", "queue_full", "replica_lost",
                 "replica_timeout", "injected_fault", "migration_failed"}
        for i, code, obj in results:
            if code == 200:
                assert obj["finish_reason"] in ("length", "eos"), obj
            else:
                assert obj.get("error_type") in typed, (code, obj)
        assert router.migrations >= 1      # the tier genuinely migrated
        assert router.wait_live(3, timeout_s=600), sup.describe()

        # Both-pools leak oracle + frozen program contract on every
        # surviving engine (parks drain via ack/resume or the sweep).
        faults.clear()
        for r in sup.replicas():
            worker = getattr(r.handle, "worker", None)
            if worker is None or worker.dead.is_set():
                continue
            sched = worker._sched
            deadline = time.monotonic() + 90
            while sched.parked_count and time.monotonic() < deadline:
                time.sleep(0.05)
            if sched.parked_count:
                # a park whose puller died rides out its TTL; reclaim
                # deterministically rather than waiting a minute
                sched.parked_ttl_s = 0.0
                sched.step()
            assert sched.parked_count == 0
            sched.engine.pool.leak_check()
            stats = sched.engine.compile_stats()
            buckets = len(sched.engine.cfg.prefill_buckets)
            assert stats["entries"] <= 1 + buckets, stats
    finally:
        faults.clear()
        obs.end_run()
        router.stop()
        sup.shutdown()
    from check_telemetry_schema import check_run_dir
    assert check_run_dir(run_dir) == []
    with open(os.path.join(run_dir, "summary.json")) as f:
        summary = json.load(f)
    for name in ("serve.kv.migrations_total", "serve.kv.migration_bytes",
                 "router.migrate_fallbacks_total"):
        assert name in summary["counters"], name
    assert summary["counters"]["serve.kv.migrations_total"] >= 1
    for name in ("router.prefill_wait_s", "router.decode_wait_s"):
        assert name in summary["histograms"], name
    # the orchestration span is pinned and present
    with open(os.path.join(run_dir, "spans.jsonl")) as f:
        spans = [json.loads(ln) for ln in f if ln.strip()]
    assert any(sp.get("name") == "router.migrate" for sp in spans)
    from nezha_tpu.obs.report import render_report
    report = render_report(run_dir)
    assert "migration:" in report and "queue split:" in report


def test_rolling_drain_with_parked_migrations(tiny_model):
    """Rolling drain of a prefill replica with migrations in flight:
    parked entries are swept (nothing pullable afterwards, nothing
    leaked) and capacity steps down one replica at a time."""
    cfg = _cfg()
    sup, router = _cluster(cfg)
    try:
        # Park two requests directly on the prefill replica (phase one
        # of the pipeline), then drain with the pulls never issued.
        import urllib.request
        port = sup.replicas()[0].port
        for i in range(2):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/generate",
                data=json.dumps(
                    {"id": f"park-{i}", "prompt_tokens": _prompt(21, salt=i),
                     "max_new_tokens": 4, "prefill_only": True}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as resp:
                obj = json.loads(resp.read())
            assert obj["finish_reason"] == "prefilled", obj
        sched0 = _worker_sched(sup, 0)
        assert sched0.parked_count == 2
        progress = []
        sup.rolling_drain(timeout_s=20.0, progress=progress.append)
        assert progress == [1, 0]          # never zero before the end
        assert sched0.parked_count == 0    # swept at the drain cutoff
        sched0.engine.pool.leak_check()
        assert (sched0.engine.pool.blocks_used
                == sched0.engine.pool.trie_only_blocks)
    finally:
        router.stop()
        sup.shutdown()


# ------------------------------------------------------------ benchmark
def test_bench_disaggregate_with_prefill_kills(tmp_path):
    """benchmarks/serving.py --disaggregate --kill-rate aimed at the
    prefill tier: the record pins lost == 0 under kills, carries the
    migration GB/s block and the prefill/decode queueing split, and
    the run-dir artifacts are schema-valid."""
    sys.path.insert(0, os.path.join(_ROOT, "benchmarks"))
    import serving as bench

    faults.install(FaultPlan.parse("replica.kv_export:delay=0.02x*"))
    run_dir = str(tmp_path / "disbench")
    rec = bench.run(bench.build_parser().parse_args(
        ["--disaggregate", "--prefill-replicas", "2",
         "--decode-replicas", "1", "--kill-rate", "8",
         "--requests", "12", "--concurrency", "4",
         "--prompt-len-mix", "6,21", "--max-new-tokens", "6",
         "--max-batch-size", "2", "--max-len", "64",
         "--max-prefill-len", "8", "--kv-block-size", "8",
         "--seed", "5", "--run-dir", run_dir]))
    assert rec["disaggregate"] is True
    assert rec["roles"] == ["prefill", "prefill", "decode"]
    assert rec["answered"] == 12 and rec["lost"] == 0
    assert rec["kills"] >= 1
    # kills were aimed at the prefill tier
    assert all(rid in (0, 1) for rid in rec["killed_rids"])
    mig = rec["migration"]
    assert mig["count"] >= 1 and mig["bytes"] > 0
    assert mig["gb_per_s"] >= 0
    assert rec["prefill_wait_s"]["p50"] >= 0
    assert rec["decode_wait_s"]["p50"] >= 0
    assert rec["tpot_s"]["p50"] > 0
    from check_telemetry_schema import check_run_dir
    assert check_run_dir(run_dir) == []
