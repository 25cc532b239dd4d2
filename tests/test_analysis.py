"""nezha-lint: the static-analysis framework (nezha_tpu/analysis/).

Three layers of proof:

1. **fixture mini-packages per rule** — each rule detects its seeded
   violation (positive) and stays quiet on the compliant twin
   (negative); fixtures are PARSED, never imported, so they reference
   jax freely without running it;
2. **baseline round-trip** — findings suppress via line-free keys,
   stale/placeholder entries fail, regeneration preserves
   justifications;
3. **the real tree** — ``nezha-lint`` over this repo exits 0 with the
   committed baseline (THE tier-1 wire: a new host sync, unguarded
   write, post-donation read, unpinned instrument, or registry drift
   fails here), the legacy ``tools/check_*.py`` entry points still
   pass standalone, and the whole lint stays under its 10 s budget.
"""

import json
import os
import subprocess
import sys
import textwrap
import time

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TOOLS = os.path.join(_ROOT, "tools")
if _TOOLS not in sys.path:
    sys.path.insert(0, _TOOLS)

from nezha_tpu.analysis import (SourceIndex, apply_baseline,  # noqa: E402
                                load_baseline, load_rules, run_rules,
                                write_baseline)
from nezha_tpu.analysis.baseline import BaselineError  # noqa: E402
from nezha_tpu.cli import lint  # noqa: E402

load_rules()


def _tree(tmp_path, files):
    """Materialize {relpath: source} under tmp_path/pkg and index it."""
    for rel, src in files.items():
        p = tmp_path / "pkg" / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return SourceIndex(str(tmp_path), roots=("pkg",), extra_files=())


def _rule_findings(index, name):
    return [f for f in run_rules(index, [name]) if f.rule == name]


# ------------------------------------------------------ host-sync rule
def test_host_sync_rule_fixture(tmp_path):
    index = _tree(tmp_path, {"hot.py": """
        import jax, time
        import jax.numpy as jnp

        @jax.jit
        def bad_sync(x):
            y = jnp.sum(x)
            y.block_until_ready()          # finding: sync in jit body
            print("trace-time only")       # finding: host IO
            time.sleep(0.1)                # finding: host effect
            return float(y)                # finding: concretize tracer

        @jax.jit
        def good(x, scale=2.0):
            return jnp.sum(x) * float(scale)   # static float(): legal

        def host_side(arr):
            arr.block_until_ready()        # NOT traced: no finding
            return float(arr.sum())
    """})
    found = _rule_findings(index, "host-sync-in-hot-path")
    details = sorted(f.detail for f in found)
    assert details == [".block_until_ready()", "float() on a traced value",
                       "print()", "time.sleep()"]
    assert all(f.symbol == "bad_sync" for f in found)


def test_host_sync_builder_convention_and_scan(tmp_path):
    """The serve-engine idioms: a `_build_*`-returned closure and a
    lax.scan body are both in scope."""
    index = _tree(tmp_path, {"engine.py": """
        import numpy as np
        import jax.numpy as jnp
        from jax import lax

        def _build_step(model):
            def body(carry, _):
                tok = jnp.argmax(carry)
                np.asarray(tok)            # finding: host materialize
                return carry, tok
            def step(carry):
                return lax.scan(body, carry, None, length=4)
            return step

        def _build_prefill(model):
            def prefill(tokens):
                tokens.item()              # finding: concretize
                return tokens
            return prefill
    """})
    found = _rule_findings(index, "host-sync-in-hot-path")
    assert {f.detail for f in found} == {"np.asarray()", ".item()"}
    assert {f.symbol for f in found} == {"_build_step.body",
                                         "_build_prefill.prefill"}


def test_host_sync_host_tier_buffer_fixture(tmp_path):
    """The tiered-KV extension: any touch of the pool's host-tier
    buffers (`_host_tier` and friends) inside a traced body is a
    finding — promotion/demotion are host-side pool maintenance by
    contract — while host-side code uses them freely."""
    index = _tree(tmp_path, {"tier.py": """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def bad_read(pool, caches, key):
            entry = pool._host_tier[key]          # finding
            used = pool.host_blocks_used          # finding
            return caches, entry, used

        def _build_step(model):
            def step(pool, caches):
                pool._promote(0, [], 0)           # finding
                return caches
            return step

        def host_side(pool):
            pool._host_tier.clear()               # NOT traced: fine
            return pool.host_bytes_resident
    """})
    found = _rule_findings(index, "host-sync-in-hot-path")
    assert {f.detail for f in found} == {"._host_tier",
                                         ".host_blocks_used",
                                         "._promote"}
    assert {f.symbol for f in found} == {"bad_read", "_build_step.step"}


def test_host_sync_pallas_partial_binding(tmp_path):
    """Kernels bound through `kernel = functools.partial(...)` then
    `pallas_call(kernel, ...)` are in scope; a def whose RESULT is
    bound (`mesh = _mesh(devs)`) is not."""
    index = _tree(tmp_path, {"kern.py": """
        import functools
        import jax
        from jax.experimental import pallas as pl

        def _kernel(x_ref, o_ref):
            print("dbg")                   # finding: IO in kernel body

        def call(x, quant):
            kernel = functools.partial(_kernel)
            return pl.pallas_call(kernel, out_shape=None)(x)

        def _mesh(devs):
            print("host-side is fine")     # must NOT be marked traced
            return devs

        def host(devs, f):
            mesh = _mesh(devs)
            return jax.jit(f, device=mesh)
    """})
    found = _rule_findings(index, "host-sync-in-hot-path")
    assert [f.symbol for f in found] == ["_kernel"]


def test_host_sync_collective_ring_bodies(tmp_path):
    """The sequence-sharded prefill extension (ISSUE 20): a function
    that ISSUES lax.ppermute / lax.all_to_all is a traced body even
    when no in-module shard_map references it (the ring-attention
    library helpers are handed to shard_map cross-module), and the
    ring hop loop it builds is in scope transitively — a host sync
    inside a hop is a finding. Collective-free host code stays out of
    scope."""
    index = _tree(tmp_path, {"ring.py": """
        import time
        import jax.numpy as jnp
        from jax import lax

        def ring_attend(q, k, axis):
            def hop(i, carry):
                q_cur, acc = carry
                time.time()                # finding: host clock in hop
                q_cur = lax.ppermute(q_cur, axis, [(0, 1), (1, 0)])
                return q_cur, acc + q_cur
            return lax.fori_loop(0, 2, hop, (q, jnp.zeros_like(q)))

        def ulysses_exchange(x, axis):
            y = lax.all_to_all(x, axis, 1, 2, tiled=True)
            print("trace-time only")       # finding: IO in a2a body
            return y

        def host_plan(widths):
            print("host-side is fine")     # no collectives: NOT traced
            return sorted(widths)
    """})
    found = _rule_findings(index, "host-sync-in-hot-path")
    assert {f.detail for f in found} == {"time.time()", "print()"}
    assert {f.symbol for f in found} == {"ring_attend.hop",
                                         "ulysses_exchange"}


# -------------------------------------------- mesh-host-side-tables rule
def test_mesh_host_side_tables_rule_fixture(tmp_path):
    """The sharded-serving split: host-side pool bookkeeping
    (block tables / free list / trie) must never mutate inside a
    shard_map-lowered body — including transitively-called helpers —
    while reads of an uploaded copy, host-side mutation, and mutation
    inside a PLAIN jit body stay legal."""
    index = _tree(tmp_path, {"sharded.py": """
        import jax.numpy as jnp
        from jax.experimental.shard_map import shard_map

        class Pool:
            def bind(self, slot):                # host-side: legal
                self.tables_host[slot, 0] = 1
                self._free_blocks.pop()

        def _helper(pool, b):
            pool._refs[b] += 1                   # finding (transitive)

        def run(pool, mesh, caches, tables):
            def body(c, t):
                pool.tables_host[0, 0] = 9       # finding: table write
                pool._free_blocks.append(3)      # finding: mutator call
                pool.trie.insert([1], [2], None) # finding: trie mutate
                _helper(pool, 0)
                row = t[0]                       # READ of upload: legal
                return c + row
            f = shard_map(body, mesh=mesh, in_specs=None,
                          out_specs=None)
            return f(caches, tables)
    """})
    found = _rule_findings(index, "mesh-host-side-tables")
    assert {f.detail for f in found} == {"tables_host", "_free_blocks",
                                         "trie", "_refs"}
    assert {f.symbol for f in found} == {"run.body", "_helper"}
    # Negative twin: the same mutations outside any shard_map body.
    clean = _tree(tmp_path / "neg", {"host.py": """
        import jax

        class Pool:
            def free(self, slot):
                self.tables_host[slot, :] = 0
                self._free_blocks.append(slot)

        @jax.jit
        def step(caches, tables):
            return caches                        # jit body, no mutation
    """})
    assert _rule_findings(clean, "mesh-host-side-tables") == []


def test_mesh_host_side_tables_collective_bodies(tmp_path):
    """The sequence-sharded prefill extension (ISSUE 20): a helper
    that issues mesh collectives (the seq_prefill ring/ulysses shard
    bodies — handed to shard_map cross-module, so no in-module
    shard_map call roots them) is still in scope: a block-table or
    free-list mutation inside one is a finding."""
    index = _tree(tmp_path, {"seq.py": """
        import jax.numpy as jnp
        from jax import lax

        def _ring_shard(pool, q, kd, axis):
            pool._free_blocks.append(3)    # finding: fork per shard
            def hop(i, carry):
                return lax.ppermute(carry, axis, [(0, 1), (1, 0)])
            return lax.fori_loop(0, 2, hop, kd)

        def _ulysses_shard(pool, q, tab, axis):
            qh = lax.all_to_all(q, axis, 1, 2, tiled=True)
            pool.tables_host[0, 0] = 9     # finding: table write
            return qh + tab

        def host_rebind(pool, slot):
            pool.tables_host[slot, :] = 0  # host-side: legal
            pool._free_blocks.append(slot)
    """})
    found = _rule_findings(index, "mesh-host-side-tables")
    assert {f.detail for f in found} == {"_free_blocks", "tables_host"}
    assert {f.symbol for f in found} == {"_ring_shard", "_ulysses_shard"}


def test_mesh_host_side_tables_real_tree_clean():
    """The real serving tree honors the split: the engine's shard_map
    surfaces (nested flash kernels, the sharded engine's programs)
    never touch the host bookkeeping."""
    index = SourceIndex(_ROOT, roots=("nezha_tpu",), extra_files=())
    assert _rule_findings(index, "mesh-host-side-tables") == []


# -------------------------------------------------- traced-branch rule
def test_traced_branch_rule_fixture(tmp_path):
    index = _tree(tmp_path, {"branchy.py": """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def bad(x):
            y = jnp.sum(x)
            if y > 0:                      # finding
                x = x + 1
            while jnp.any(x):              # finding (device call test)
                x = x - 1
            assert y != 0                  # finding
            return x

        @jax.jit
        def good(x, flag=True, k=None):
            y = jnp.sum(x)
            if flag:                       # static closure value: legal
                x = x + 1
            if k is None:                  # identity test: legal
                x = x * 2
            if jnp.issubdtype(x.dtype, jnp.floating):   # static: legal
                x = x + 0.0
            assert x.shape[0] == 1         # shape is static: legal
            return x + y
    """})
    found = _rule_findings(index, "traced-value-branch")
    assert sorted(f.detail for f in found) == [
        "assert y != 0", "if y > 0", "while jnp.any(x)"]
    assert all(f.symbol == "bad" for f in found)


# ------------------------------------------------------- donation rule
def test_donation_rule_fixture(tmp_path):
    index = _tree(tmp_path, {"donate.py": """
        import jax

        def update(state, x):
            return state

        step = jax.jit(update, donate_argnums=(0,))

        def bad_loop(state, xs):
            out = step(state, xs)
            return state                   # finding: donated, then read

        def good_loop(state, xs):
            state = step(state, xs)        # rebound in-statement: legal
            return state

        class Engine:
            def __init__(self):
                from runtime import Executor
                self.executor = Executor(donate_argnums=(1,))

            def bad_step(self):
                out = self.executor.run(self.fn, self.variables,
                                        self.pool.caches)
                return self.pool.caches    # finding: read after donate

            def good_step(self):
                out = self.executor.run(self.fn, self.variables,
                                        self.pool.caches)
                self.pool.caches = out[0]  # rebind revives the path
                return self.pool.caches

            def branched(self, paged):
                if paged:
                    out = self.executor.run(self.fn, self.variables,
                                            self.pool.caches)
                else:
                    out = self.fallback(self.pool.caches)   # sibling arm: legal
                self.pool.caches = out[0]
                return out
    """})
    found = _rule_findings(index, "use-after-donate")
    assert sorted((f.symbol, f.detail) for f in found) == [
        ("Engine.bad_step", "self.pool.caches"),
        ("bad_loop", "state"),
    ]


# ---------------------------------------------------------- locks rule
def test_lock_discipline_rule_fixture(tmp_path):
    index = _tree(tmp_path, {"locked.py": """
        import threading

        class Pool:
            _LOCK_GUARDED = {"_free": "_lock", "_ledger": "_ledger_lock"}

            def __init__(self):
                self._lock = threading.Lock()
                self._ledger_lock = threading.Lock()
                self._free = []            # __init__ is exempt
                self._ledger = {}

            def good(self, x):
                with self._lock:
                    self._free.append(x)
                with self._ledger_lock:
                    self._ledger[x] = 1

            def bad(self, x):
                self._free.append(x)       # finding: no lock
                with self._lock:
                    self._ledger[x] = 1    # finding: WRONG lock held

            def internal(self, x):
                '''[holds: _lock] — caller locks.'''
                self._free.pop()           # marker: legal
                del self._free[0]          # marker: legal

            def nested_ok(self, xs):
                for x in xs:
                    with self._lock:
                        self._free.append(x)   # nested with: legal

        class Undeclared:
            def anything(self, x):
                self._free.append(x)       # no declaration: not checked
    """})
    found = _rule_findings(index, "lock-discipline")
    assert sorted((f.symbol, f.detail) for f in found) == [
        ("Pool.bad", "_free"), ("Pool.bad", "_ledger")]


def test_lock_discipline_real_declarations_present():
    """The serve/obs classes actually declare their guarded state — the
    rule has teeth on the real tree, not just fixtures."""
    from nezha_tpu.obs.registry import Histogram, Registry
    from nezha_tpu.serve.router import Router
    from nezha_tpu.serve.scheduler import Scheduler
    from nezha_tpu.serve.supervisor import Supervisor, _ThreadWorker
    for cls in (Scheduler, Router, Supervisor, _ThreadWorker,
                Histogram, Registry):
        assert getattr(cls, "_LOCK_GUARDED"), cls.__name__
    assert Scheduler._LOCK_GUARDED["_lanes"] == "_lock"
    assert Scheduler._LOCK_GUARDED["_preempted"] == "_lock"
    assert Supervisor._LOCK_GUARDED["_as_target"] == "_lock"
    assert Router._LOCK_GUARDED["retries"] == "_ledger_lock"


# ------------------------------------------------- registry-port rules
def test_fault_points_rule_fixture(tmp_path):
    from nezha_tpu.analysis.rules.fault_points import check_index
    for rel, src in {
        "nezha_tpu/a.py": """
            from nezha_tpu import faults

            def f():
                faults.point("serve.test")

            def g():
                faults.point("serve.undocumented")
        """,
        "nezha_tpu/b.py": """
            from nezha_tpu import faults

            def h():
                faults.point("serve.test")   # duplicate site
        """,
        "nezha_tpu/faults/injector.py": """
            # Excluded dir: examples here never register.
            def point(name):
                'call like faults.point("serve.fake")'
        """,
        "docs/RUNBOOK.md": "| serve.test | documented |\n",
        "tests/test_x.py": "PLAN = 'serve.test:error'\n",
    }.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    index = SourceIndex(str(tmp_path), roots=("nezha_tpu",),
                        extra_files=())
    msgs = [f.message for f in check_index(
        index, expected=frozenset({"serve.test", "serve.missing"}))]
    assert any("2 call sites" in m for m in msgs)            # duplicate
    assert any("'serve.undocumented' is not in EXPECTED" in m
               for m in msgs)
    assert any("'serve.missing' has no" in m for m in msgs)  # lost pin
    assert any("'serve.undocumented' is not documented" in m
               for m in msgs)
    assert any("'serve.undocumented' is not covered" in m for m in msgs)
    # The documented+tested+pinned point raises nothing about itself.
    assert not any("'serve.test' is not" in m for m in msgs)


def test_telemetry_schema_rule_fixture(tmp_path):
    index = _tree(tmp_path, {"instrumented.py": """
        from nezha_tpu import obs

        def ok():
            obs.counter("serve.admitted_total").inc()
            obs.histogram("router.route_s").observe(0.1)
            obs.counter("compile_cache.hits").inc()   # unpinned ns: free
            obs.counter(f"serve.dynamic_total").inc() # non-literal: skip

        def bad():
            obs.counter("serve.bogus_total").inc()    # unknown name
            obs.counter("serve.ttft_s").inc()         # kind mismatch
            with obs.span("serve.mystery"):           # unpinned span
                pass
    """})
    found = _rule_findings(index, "telemetry-schema")
    assert sorted(f.detail for f in found) == [
        "serve.bogus_total", "serve.mystery", "serve.ttft_s"]
    kind_mismatch = [f for f in found if f.detail == "serve.ttft_s"]
    assert "histogram" in kind_mismatch[0].message


def test_bench_records_rule_fixture(tmp_path):
    (tmp_path / "nezha_tpu").mkdir()
    (tmp_path / "BENCH_crash.json").write_text(json.dumps(
        {"n": 1, "cmd": "x", "rc": 1, "tail": "boom", "parsed": None}))
    (tmp_path / "BENCH_ok.json").write_text(json.dumps(
        {"n": 1, "cmd": "x", "rc": 0, "tail": "",
         "parsed": {"metric": "m", "value": 1.0}, "platform": "cpu"}))
    index = SourceIndex(str(tmp_path), roots=("nezha_tpu",),
                        extra_files=())
    found = _rule_findings(index, "bench-records")
    assert len(found) == 1 and "CRASH RECORD" in found[0].message
    assert found[0].file == "BENCH_crash.json"
    # No exemption: notes cannot excuse it, only dropping the file does.
    (tmp_path / "BENCH_NOTES.md").write_text(
        "## Superseded records\n- BENCH_crash.json — crash\n")
    assert len(_rule_findings(index, "bench-records")) == 1
    (tmp_path / "BENCH_crash.json").unlink()
    assert _rule_findings(index, "bench-records") == []


# ------------------------------------------------------------ baseline
def test_baseline_round_trip(tmp_path):
    index = _tree(tmp_path, {"hot.py": """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            y = jnp.sum(x)
            y.block_until_ready()
            return y
    """})
    findings = run_rules(index)
    assert findings
    path = tmp_path / "baseline.json"
    write_baseline(findings, str(path),
                   default_justification="fixture: accepted on purpose")
    baseline = load_baseline(str(path))
    kept, stale = apply_baseline(findings, baseline)
    assert kept == [] and stale == []
    # Keys are line-free: shifting the violation down a line still
    # suppresses; deleting it makes the entry STALE.
    # (run_rules ran EVERY rule — the registry rules report the bare
    # fixture tree's missing artifacts too, and those baseline the
    # same way.)
    assert any(k.startswith("host-sync-in-hot-path:pkg/hot.py:f:")
               for k in baseline)
    kept, stale = apply_baseline([], baseline)
    assert stale == sorted(baseline)


def test_baseline_rejects_placeholder_and_garbage(tmp_path):
    path = tmp_path / "b.json"
    path.write_text(json.dumps({"version": 1, "suppressions": [
        {"key": "x:y:z:w", "justification": "TODO: justify"}]}))
    with pytest.raises(BaselineError):
        load_baseline(str(path))
    path.write_text("{torn")
    with pytest.raises(BaselineError):
        load_baseline(str(path))
    path.write_text(json.dumps({"version": 99, "suppressions": []}))
    with pytest.raises(BaselineError):
        load_baseline(str(path))
    # Missing file = empty baseline, not an error.
    assert load_baseline(str(tmp_path / "absent.json")) == {}


def test_update_baseline_preserves_justifications(tmp_path, capsys):
    """Regeneration keeps human-written reasons — even when the file
    currently holds a placeholder entry a strict load rejects — and
    refuses both partial (--rule) rewrites and unreadable files."""
    from nezha_tpu.analysis.baseline import PLACEHOLDER_JUSTIFICATION
    root = tmp_path / "repo"
    (root / "pkg").mkdir(parents=True)
    (root / "pkg" / "hot.py").write_text(textwrap.dedent("""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            y = jnp.sum(x)
            y.block_until_ready()
            return y
    """))
    index = SourceIndex(str(root), roots=("pkg",), extra_files=())
    [finding] = _rule_findings(index, "host-sync-in-hot-path")
    path = tmp_path / "baseline.json"
    # A human-justified entry for the real finding + a placeholder one
    # (the state a previous --update-baseline leaves behind).
    path.write_text(json.dumps({"version": 1, "suppressions": [
        {"key": finding.key, "justification": "reviewed: intentional"},
        {"key": "other-rule:gone.py::x",
         "justification": PLACEHOLDER_JUSTIFICATION}]}))
    existing = load_baseline(str(path), strict=False)
    write_baseline([finding], str(path), justifications=existing)
    saved = json.loads(path.read_text())
    [entry] = saved["suppressions"]
    assert entry["key"] == finding.key
    assert entry["justification"] == "reviewed: intentional"
    # A NEW finding regenerated without a human reason gets the
    # placeholder, and the placeholder fails the next strict load.
    write_baseline([finding], str(path), justifications={})
    with pytest.raises(BaselineError):
        load_baseline(str(path))
    # --update-baseline + --rule would delete other rules' entries.
    assert lint.main(["--root", str(root), "--rule", "bench-records",
                      "--update-baseline",
                      "--baseline", str(path)]) == 2
    # Structural damage aborts the rewrite instead of wiping the file.
    path.write_text("{torn")
    assert lint.main(["--root", str(root), "--update-baseline",
                      "--baseline", str(path)]) == 2
    assert path.read_text() == "{torn"


def test_shims_run_without_jax(tmp_path):
    """The standalone checkers keep their original no-dependencies
    promise: with jax import-blocked they fall back to the namespace
    stub and still validate the real tree."""
    blocker = tmp_path / "runner.py"
    blocker.write_text(textwrap.dedent("""
        import sys
        class _Block:
            def find_module(self, name, path=None):
                if name.split(".")[0] in ("jax", "jaxlib"):
                    return self
                return None
            def load_module(self, name):
                raise ImportError(f"{name} blocked (simulated)")
        sys.meta_path.insert(0, _Block())
        import runpy
        sys.argv = sys.argv[1:]
        runpy.run_path(sys.argv[0], run_name="__main__")
    """))
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    for tool in ("check_fault_points.py", "check_bench_record.py"):
        p = subprocess.run(
            [sys.executable, str(blocker),
             os.path.join(_ROOT, "tools", tool)],
            capture_output=True, text=True, env=env, cwd="/")
        assert p.returncode == 0, (tool, p.stdout, p.stderr)
        assert p.stdout.startswith("OK:"), (tool, p.stdout)


def test_stale_baseline_fails_cli(tmp_path):
    index_dir = tmp_path / "repo"
    (index_dir / "pkg").mkdir(parents=True)
    (index_dir / "pkg" / "clean.py").write_text("x = 1\n")
    stale = tmp_path / "stale.json"
    stale.write_text(json.dumps({"version": 1, "suppressions": [
        {"key": "host-sync-in-hot-path:gone.py:f:.item()",
         "justification": "the code this excused was deleted"}]}))
    rc = lint.main(["--root", str(index_dir), "--baseline", str(stale)])
    assert rc == 1


# ----------------------------------------------------------------- CLI
def test_cli_json_and_rule_selection(tmp_path, capsys):
    (tmp_path / "nezha_tpu").mkdir()
    (tmp_path / "nezha_tpu" / "m.py").write_text(textwrap.dedent("""
        import threading

        class C:
            _LOCK_GUARDED = {"_state": "_lock"}

            def __init__(self):
                self._lock = threading.Lock()
                self._state = []

            def bad(self):
                self._state.append(1)
    """))
    rc = lint.main(["--root", str(tmp_path), "--json", "--no-baseline",
                    "--rule", "lock-discipline"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert out["rules"] == ["lock-discipline"]
    [f] = out["findings"]
    assert f["rule"] == "lock-discipline" and f["detail"] == "_state"
    assert f["key"].startswith("lock-discipline:nezha_tpu/m.py:C.bad:")
    # Selecting only another rule ignores the violation.
    rc = lint.main(["--root", str(tmp_path), "--no-baseline",
                    "--rule", "bench-records", "--rule",
                    "fault-points"])
    assert rc == 1   # fault-points: no sites found in this tiny tree
    rc = lint.main(["--root", str(tmp_path), "--no-baseline",
                    "--rule", "use-after-donate"])
    assert rc == 0


def test_single_rule_run_ignores_other_rules_suppressions():
    """`nezha-lint --rule X` on the clean tree must NOT report the
    committed baseline's other-rule entries as stale (a single-rule
    run only produces X's findings, so only X's suppressions can be
    judged) — the RUNBOOK §11 invocation exits 0."""
    assert lint.main(["--root", _ROOT, "--rule", "lock-discipline"]) == 0
    assert lint.main(["--root", _ROOT, "--rule",
                      "traced-value-branch"]) == 0


def test_cli_unknown_rule_and_list(capsys):
    assert lint.main(["--rule", "no-such-rule",
                      "--root", _ROOT]) == 2
    assert lint.main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for name in ("host-sync-in-hot-path", "traced-value-branch",
                 "use-after-donate", "lock-discipline", "fault-points",
                 "telemetry-schema", "bench-records"):
        assert name in out


def test_syntax_error_is_a_finding(tmp_path):
    (tmp_path / "nezha_tpu").mkdir()
    (tmp_path / "nezha_tpu" / "broken.py").write_text("def f(:\n")
    rc = lint.main(["--root", str(tmp_path), "--no-baseline",
                    "--rule", "use-after-donate"])
    assert rc == 1   # parse failures surface regardless of selection


# ---------------------------------------------------- the real tree
def test_nezha_lint_real_tree_exits_zero_under_budget():
    """THE tier-1 wire: all rules over the real repo, committed
    baseline applied, exit 0 — and within the 10 s CPU budget the
    RUNBOOK promises (index once, parse once)."""
    t0 = time.monotonic()
    rc = lint.main(["--root", _ROOT])
    dt = time.monotonic() - t0
    assert rc == 0
    assert dt < 10.0, f"nezha-lint took {dt:.1f}s (budget 10s)"


def test_real_tree_runs_all_seven_rules(capsys):
    rc = lint.main(["--root", _ROOT, "--json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert len(out["rules"]) >= 7
    assert out["files_indexed"] > 100
    # The committed baseline suppresses only justified findings; every
    # justification is real (load_baseline rejects placeholders).
    baseline = load_baseline(os.path.join(_ROOT, "tools",
                                          "lint_baseline.json"))
    assert out["suppressed"] == len(baseline)


def test_legacy_shims_standalone():
    """The three tools/check_*.py entry points survive the migration:
    same argv contract, same rc, now over the shared analysis index."""
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)   # shims must bootstrap sys.path alone
    for tool in ("check_fault_points.py", "check_bench_record.py"):
        p = subprocess.run(
            [sys.executable, os.path.join(_ROOT, "tools", tool)],
            capture_output=True, text=True, env=env, cwd="/")
        assert p.returncode == 0, (tool, p.stdout, p.stderr)
        assert p.stdout.startswith("OK:"), (tool, p.stdout)
    p = subprocess.run(
        [sys.executable,
         os.path.join(_ROOT, "tools", "check_telemetry_schema.py")],
        capture_output=True, text=True, env=env, cwd="/")
    assert p.returncode == 2    # usage: needs a run dir
    # And a bad run dir still fails through the shim import path.
    from check_telemetry_schema import check_run_dir
    assert check_run_dir("/nonexistent-run-dir") != []
