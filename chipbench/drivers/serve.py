"""Serving cells: the program's own stack (``cli/serve.py::_build_stack``)
under one seeded traffic mix, timed from the benchmark's side.

One process, two threads. The main thread drives ``Scheduler.step`` as the
program's front ends do. An open-loop mix has a generator thread that
sleeps until each request is due and hands it over; requests are timed from
when they were DUE. A backlog mix keeps the admission queue topped up from
the main thread. Spans and counters are taken here, around the calls into
each layer (``Scheduler.step``, ``Engine.prefill``, ``Engine.step``,
``PagedSlotPool.bind_for_prompt``) and in the scheduler's ``on_token`` /
``on_finish`` callbacks; nothing inside the program is edited or read
beyond those calls' arguments and results.

Set-up, in order: build the stack (weights on the device from the seed),
warm each prefill width and the step program, compare the program with the
plain reference on a few requests of the cell's own mix, then warm-up
traffic until slot occupancy is steady. The window starts there.
"""

from __future__ import annotations

import collections
import contextlib
import os
import shutil
import threading
import time
from typing import Optional

import numpy as np

from chipbench import checks, traffic as traffic_lib
from chipbench.obs import Obs
from chipbench.stats import percentile

clock = time.perf_counter

ANNOTATIONS = ("submit", "scheduler.step", "engine.step", "engine.prefill",
               "generator.sleep")
CHECK_REQUESTS = 8
CHECK_STEPS = 3          # decode steps whose logits are compared


class _Rec:
    __slots__ = ("rid", "due", "first_prefill", "first_token",
                 "last_token", "tokens_in_window", "check")

    def __init__(self, rid, due):
        self.rid, self.due = rid, due
        self.first_prefill = self.first_token = self.last_token = None
        self.tokens_in_window = 0
        self.check = None


class ServeRun:
    def __init__(self, cell: dict, seed: int, seconds: float, trace: bool,
                 work_dir: str):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.trace, self.work_dir = trace, work_dir
        self.traffic = cell["traffic"]
        self.obs = Obs()
        self.recs = {}              # rid -> _Rec
        self.by_prompt = {}         # id(prompt list) -> _Rec
        self.slot_rec = {}          # slot -> _Rec (check phase only)
        self.win = (float("inf"), float("inf"))   # measured window
        self.sample_until = float("inf")  # host samples stop here (trace)
        self.arrivals = collections.deque()
        self.lateness = []
        self._bind_start = 0
        self.step_log = []
        self.prefixes_warm = False
        self.trace_span = None
        self.epoch = 0
        self._annotate = trace
        self.facts = {}

    # ------------------------------------------------------------ build
    def build(self):
        from nezha_tpu.cli import serve as cli

        argv = list(self.cell["config"]["serve"]["argv"]) \
            + ["--seed", str(self.seed)]
        self.sched, _, _ = cli._build_stack(cli.build_parser().parse_args(argv))
        self.engine = self.sched.engine
        self.slots = self.engine.cfg.max_batch_size
        self.vocab = self.engine.vocab
        mcfg = self.engine.model.cfg
        self.obs.model = {
            "num_layers": mcfg.num_layers, "num_heads": mcfg.num_heads,
            "head_dim": mcfg.hidden_size // mcfg.num_heads,
            "kv_bytes": 1 if self.engine.kv_quant else 2}
        self.sched.on_token = self._on_token
        self.sched.on_finish = self._on_finish
        eng, pool = self.engine, self.engine.pool
        self._prefill0, self._step0 = eng.prefill, eng.step
        self._bind0 = pool.bind_for_prompt
        eng.prefill, eng.step = self._prefill, self._step
        pool.bind_for_prompt = self._bind

    def _span(self, name):
        if not self._annotate:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def _sampling(self, t: float) -> bool:
        return self.win[0] <= t < min(self.win[1], self.sample_until)

    # --------------------------------------------------------- wrappers
    def _bind(self, slot, tokens):
        self._bind_start = self._bind0(slot, tokens)
        return self._bind_start

    def _prefill(self, slot, tokens, **kw):
        rec = self.by_prompt.pop(id(tokens), None)
        t0 = clock()
        if rec is not None and rec.first_prefill is None:
            rec.first_prefill = t0
            if self._sampling(t0):
                self.obs.sample("queue_wait_ms", (t0 - rec.due) * 1e3)
        self._bind_start = 0
        with self._span("engine.prefill"):
            self._prefill0(slot, tokens, **kw)
        t1 = clock()
        if self._sampling(t1):
            self.obs.count("prefill_calls")
            self.obs.count("prefill_host_ms", (t1 - t0) * 1e3)
            self.obs.count("prompt_tokens", len(tokens))
            self.obs.count("prompt_tokens_cached", self._bind_start)
            self.obs.count("prompt_tokens_uncached",
                           len(tokens) - self._bind_start)
            self.obs.count("prefill_tokens_padded",
                           self.engine.last_prefill_tokens)
        if rec is not None and rec.check is not None:
            self.slot_rec[slot] = rec
            rec.check["logits"].append(
                np.asarray(self.engine.last_logits[slot]))
            rec.check["cached"] = self._bind_start

    def _step(self, active):
        t0 = clock()
        with self._span("engine.step"):
            out = self._step0(active)
        t1 = clock()
        if t1 >= self.win[0]:
            rows = int(np.count_nonzero(active))
            # resident tokens are read after the step: each row's context
            # as the kernel saw it, plus the token it just wrote
            self.obs.steps.append(
                (t1, rows, int(self.engine.host_positions[active].sum())))
        if self.win[0] <= t1 < self.win[1]:
            self.step_log.append((t1, int(out[1][active].sum())))
            if self._sampling(t1):
                self.obs.sample("step_ms", (t1 - t0) * 1e3)
                self.obs.count("decode_steps")
                self.obs.count("decode_rows", rows)
                pool = self.engine.pool
                if hasattr(pool, "num_blocks"):     # paged pools only
                    self.obs.count("kv_blocks_used_sum", pool.blocks_used)
                    self.obs.count("kv_blocks_total_sum",
                                   pool.num_blocks - 1)   # block 0: scratch
        if self.slot_rec:
            for slot, rec in list(self.slot_rec.items()):
                if not active[slot]:
                    continue
                if len(rec.check["logits"]) <= CHECK_STEPS:
                    rec.check["logits"].append(
                        np.asarray(self.engine.last_logits[slot]))
                else:
                    del self.slot_rec[slot]
        return out

    def _on_token(self, rid, tok):
        t = clock()
        rec = self.recs[rid]
        in_win = self.win[0] <= t < self.win[1]
        if rec.check is not None:
            rec.check["tokens"].append(int(tok))
        if rec.first_token is None:
            rec.first_token = t
            if in_win:
                self.obs.sample("ttft_ms", (t - rec.due) * 1e3)
        elif in_win:
            self.obs.sample("itl_ms", (t - rec.last_token) * 1e3)
        rec.last_token = t
        if in_win:
            rec.tokens_in_window += 1
            self.obs.count("tokens_out")

    def _on_finish(self, res):
        rec = self.recs.pop(res.request_id)
        t = clock()
        if self.win[0] <= t < self.win[1]:
            self.obs.count("finished")
            if res.finish_reason not in ("length", "eos"):
                self.obs.count("failed")
                self.obs.count("tokens_out", -rec.tokens_in_window)
                self.obs.count("tokens_failed", rec.tokens_in_window)
                self.facts.setdefault("errors", []).append(
                    f"{res.finish_reason}: {res.error}"[:200])
        elif res.finish_reason not in ("length", "eos"):
            self.facts.setdefault("errors_outside_window", []).append(
                f"{res.finish_reason}: {res.error}"[:200])

    # ----------------------------------------------------------- submit
    def _submit(self, req, due: float, check: bool = False):
        from nezha_tpu.serve import Request
        from nezha_tpu.serve.scheduler import QueueFull

        rid = f"w{self.epoch}r{req.index}"
        rec = _Rec(rid, due)
        if check:
            rec.check = {"logits": [], "tokens": [], "req": req}
        self.recs[rid] = rec
        self.by_prompt[id(req.prompt)] = rec
        in_win = self.win[0] <= due < self.win[1]
        if in_win:
            self.obs.count("attempted")
        try:
            with self._span("submit"):
                self.sched.submit(Request(
                    prompt=req.prompt, max_new_tokens=req.max_new_tokens,
                    temperature=req.temperature, top_k=req.top_k,
                    eos_id=None, seed=req.seed, request_id=rid))
        except QueueFull:
            del self.recs[rid], self.by_prompt[id(req.prompt)]
            if in_win:
                self.obs.count("failed")
                self.obs.count("rejected")
        return rec

    # ------------------------------------------------------------ loops
    def _generate(self, stream, t0: float, stop_t: float):
        """Generator thread (open loop): hand each request over at its
        due time; note how late the hand-over ran."""
        for req in stream:
            due = t0 + req.due_s
            if due >= stop_t:
                return
            delay = due - clock()
            if delay > 0:
                with self._span("generator.sleep"):
                    time.sleep(delay)
            self.lateness.append((due, clock() - due))
            self.arrivals.append((due, req))

    def _drive(self, stream, until: float, backlog_depth: int = 0):
        """Step the scheduler until ``until``. Open loop: ``stream`` is
        None and arrivals come from the generator thread. Backlog: keep
        ``backlog_depth`` requests queued, due the moment they are made."""
        sched = self.sched
        while True:
            now = clock()
            if now >= until:
                return
            if stream is not None:
                while sched.queue_depth < backlog_depth:
                    self._submit(next(stream), clock())
            else:
                while self.arrivals:
                    due, req = self.arrivals.popleft()
                    self._submit(req, due)
            if sched.has_work():
                with self._span("scheduler.step"):
                    sched.step()
            else:
                time.sleep(0.0002)

    # ----------------------------------------------------------- set-up
    def warm_programs(self):
        """One request per prefill width, so every program of this cell
        (the prefill widths and the step) is built before anything is
        timed or compared."""
        cfg = self.engine.cfg
        rng = np.random.default_rng([self.seed, 0xBEEF])
        for i, width in enumerate(cfg.all_prefill_buckets):
            n = min(width, cfg.max_len - 3)
            self._submit(traffic_lib.Req(
                index=-1000 - i, due_s=None,
                prompt=rng.integers(0, self.vocab, n).tolist(),
                max_new_tokens=2, temperature=0.0, top_k=None, seed=0),
                clock())
        self.sched.run_until_idle()

    def check_against_reference(self) -> dict:
        """A few requests of the cell's own mix through the same
        scheduler; their logits after prefill and after the first decode
        steps against the plain reference run on prompt + emitted tokens
        (teacher-forced with the program's own tokens, so one sampled
        difference cannot derail the rest)."""
        import jax
        import jax.numpy as jnp

        from chipbench.reference import gpt2 as ref

        stream = traffic_lib.request_stream(self.traffic, self.seed + 7_919,
                                            self.vocab)
        recs = []
        for i in range(CHECK_REQUESTS):
            req = next(stream)
            req.index = -2000 - i
            req.max_new_tokens = min(req.max_new_tokens, CHECK_STEPS + 1)
            recs.append(self._submit(req, clock(), check=True))
        self.sched.run_until_idle()
        self.slot_rec.clear()

        heads = self.engine.model.cfg.num_heads
        pad_to = self.engine.cfg.max_len
        fn = jax.jit(lambda p, t, pos: ref.logits_at(p, t, pos, heads))
        params = self.engine.variables["params"]
        got_rows, ref_rows, greedy = [], [], []
        for rec in recs:
            chk = rec.check
            req, toks = chk["req"], chk["tokens"]
            k = len(chk["logits"])          # 1 prefill + decode steps
            if k < 2 or len(toks) < k - 1:
                return {"ok": False, "why": f"request {rec.rid} captured "
                        f"{k} logit rows, {len(toks)} tokens"}
            n = len(req.prompt)
            seq = np.zeros((1, pad_to), np.int32)
            seq[0, :n + k - 1] = req.prompt + toks[:k - 1]
            # one shape for every request: short ones repeat a position
            pos = np.minimum(np.arange(n - 1, n + CHECK_STEPS),
                             n - 2 + k)[None, :]
            want = np.asarray(fn(params, jnp.asarray(seq),
                                 jnp.asarray(pos)))[0, :k]
            got_rows.append(np.stack(chk["logits"]))
            ref_rows.append(want)
            if req.temperature == 0.0:
                greedy.append((rec.rid, toks[:k], want))
        got, want = np.concatenate(got_rows), np.concatenate(ref_rows)
        facts = checks.compare_logits(got, want)
        facts["requests"] = len(recs)
        facts["prefix_cached_tokens"] = [r.check["cached"] for r in recs]
        bad = [(rid, j) for rid, toks, rows in greedy
               for j, tok in enumerate(toks)
               if not checks.greedy_token_ok(tok, rows[j], facts["logit_tol"])]
        facts["greedy_tokens_checked"] = sum(len(t) for _, t, _ in greedy)
        if bad:
            facts["ok"] = False
            facts["why"] = f"greedy tokens off the reference: {bad[:4]}"
        return facts

    # -------------------------------------------------------------- run
    def setup(self, t_process0: float) -> None:
        from chipbench import device as device_lib

        self.t_process0 = t_process0
        self.build()
        self.compiles = device_lib.CompileCounter()
        t_built = clock()
        self.warm_programs()
        t_warm = clock()
        self.check = self.check_against_reference()
        t_checked = clock()
        self.facts.update(build_s=t_built - t_process0,
                          warm_programs_s=t_warm - t_built,
                          reference_check_s=t_checked - t_warm,
                          reference_check=self.check,
                          set_up_compile_events=self.compiles.snapshot())

    def measure(self, seconds: float, rate_per_s: Optional[float] = None
                ) -> dict:
        """Warm-up traffic, then one measured window of ``seconds``.
        -> the head of the result line; ``self.obs`` holds the rest. May
        be called again (the sweep does, once a rate) after a drain."""
        tr = self.traffic
        model = self.obs.model
        self.obs = Obs()
        self.obs.model = model
        self.lateness.clear()
        self.arrivals.clear()       # a sweep's earlier window may have left some
        self.epoch += 1             # request ids are unique across windows
        self.step_log = []          # (end time, tokens emitted) per step
        warm_s = float(tr["warmup"]["seconds"])
        open_loop = tr["generator"] == "open_loop"
        trace_s = float(tr.get("trace_seconds", 3.0)) if self.trace else 0.0
        trace_s = min(trace_s, seconds / 2.0)
        stream = thread = None
        if open_loop:
            # The cache is filled as the traffic will find it: one
            # request per shared prefix before the timed arrivals start
            # (once: a sweep's later windows find it filled).
            prefixes = [] if self.prefixes_warm else \
                traffic_lib.shared_prefixes(tr, self.seed, self.vocab)
            self.prefixes_warm = True
            rng = np.random.default_rng([self.seed, 0xCAFE])
            for i, prefix in enumerate(prefixes):
                self._submit(traffic_lib.Req(
                    index=-3000 - i, due_s=None,
                    prompt=prefix + rng.integers(0, self.vocab, 16).tolist(),
                    max_new_tokens=1, temperature=0.0, top_k=None, seed=0),
                    clock())
            self.sched.run_until_idle()
            # ... and as many requests caught mid-life as the rate keeps
            # in flight at steady state (a number in the mix's file, like
            # the rate): where a request lives longer than the window, no
            # warm-up traffic of affordable length gets there.
            if not self.sched.has_work():
                for req in traffic_lib.stationary_fill(
                        tr, self.seed, self.vocab,
                        int(tr["warmup"].get("live_at_start", 0))):
                    self._submit(req, clock())
                self.sched.step()
            rate = rate_per_s or tr["arrivals"]["rate_per_s"]
            t0 = clock()
            stop_t = t0 + warm_s + seconds
            gen = traffic_lib.request_stream(tr, self.seed, self.vocab, rate,
                                             warm_s + seconds)
            thread = threading.Thread(target=self._generate,
                                      args=(gen, t0, stop_t), daemon=True)
        else:
            for req in traffic_lib.stationary_fill(tr, self.seed, self.vocab,
                                                   self.slots):
                self._submit(req, clock())
            # One scheduler pass admits (prefills) the whole fill: that is
            # set-up the traffic needs, and it ends before warm-up starts.
            self.sched.step()
            stream = traffic_lib.request_stream(tr, self.seed, self.vocab)
            t0 = clock()
            stop_t = t0 + warm_s + seconds
        depth = int(tr.get("backlog_depth", 0))
        win0 = t0 + warm_s
        self.win = (win0, stop_t)
        self.sample_until = stop_t - trace_s
        if thread is not None:
            thread.start()
        self._drive(stream, win0, depth)
        # ---- the measured window ----
        stats0 = dict(self.engine.compile_stats())
        t_win0 = clock()
        self._drive(stream, self.sample_until, depth)
        self.trace_dir = None
        if self.trace:
            import jax
            self.trace_dir = os.path.join(self.work_dir, "trace")
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            t_trace0 = clock()
            self._drive(stream, stop_t, depth)
            self.trace_span = (t_trace0, clock())
            jax.profiler.stop_trace()
        t_win1 = clock()
        stats1 = dict(self.engine.compile_stats())
        if thread is not None:
            thread.join()

        obs = self.obs
        obs.set("setup_s", win0 - self.t_process0)
        obs.set("window_s", seconds)
        obs.set("sample_window_s", self.sample_until - win0)
        obs.set("slots", self.slots)
        obs.set("chips", 1)
        if len(self.step_log) >= 2:
            # Throughput over whole decode steps: the tokens of the steps
            # that BEGAN and ended inside the window, over the time from
            # the first step's end to the last one's. With thousands of
            # steps this is tokens over the window; with tens it takes
            # out the +-1 step that the window's edges would cut.
            obs.set("token_span_s", self.step_log[-1][0] - self.step_log[0][0])
            obs.set("tokens_in_span", sum(n for _, n in self.step_log[1:])
                    - obs.counters.get("tokens_failed", 0))
        unstarted = sum(1 for r in self.recs.values()
                        if win0 <= r.due < stop_t - 1.0
                        and r.first_token is None)
        compiled = (self.compiles.compiles_between(t_win0, t_win1)
                    + stats1["misses"] - stats0["misses"])
        self.facts.update(
            warmup_s=warm_s, window_overrun_s=t_win1 - stop_t,
            compilations_in_window=compiled, engine_programs=stats1,
            unstarted_at_end=unstarted,
            queue_depth_at_end=self.sched.queue_depth,
            live_at_end=len(self.recs),
            pool_prefix_hits=self.engine.pool.prefix_hits,
            kv_blocks_used=self.engine.pool.blocks_used,
            counters=dict(obs.counters),
            samples={name: {"n": len(v), "mean": sum(v) / len(v),
                            **{f"p{q}": percentile(v, q)
                               for q in (50, 90, 95, 99)}}
                     for name, v in obs.samples.items() if v})
        ok = bool(self.check.get("ok")) and compiled == 0 \
            and not obs.counters.get("failed")
        if open_loop:
            late = [l for due, l in self.lateness if win0 <= due < stop_t]
            gap = np.log(2.0) / rate
            p50, p99 = percentile(late, 50), percentile(late, 99)
            self.facts["generator_lateness_ms"] = {
                "p50": p50 and p50 * 1e3, "p99": p99 and p99 * 1e3,
                "median_gap_ms": gap * 1e3,
                "limit_share_of_gap": tr["lateness_limit_share"]}
            if late and p50 > tr["lateness_limit_share"] * gap:
                ok = False
                self.facts["why_not_correct"] = "generator ran late"
            attempted = obs.counters.get("attempted", 0)
            if unstarted > tr["unstarted_limit_share"] * max(attempted, 1):
                ok = False
                self.facts["why_not_correct"] = (
                    f"{unstarted} of {attempted} requests had no first "
                    f"token at the end: the system fell behind")
        return {"correct": ok,
                "attempted": int(obs.counters.get("attempted", 0)),
                "failed": int(obs.counters.get("failed", 0))}

    def drain(self, limit_s: float = 60.0) -> None:
        """Let what is in flight finish (between two windows of a sweep)."""
        self.win = (float("inf"), float("inf"))
        end = clock() + limit_s
        while self.sched.has_work() and clock() < end:
            self.sched.step()


def run(cell, args, work_dir, t_process0):
    """-> (ServeRun, head of the result line). The caller reads the
    metrics from ``job.obs``."""
    job = ServeRun(cell, args.seed, float(args.seconds), bool(args.trace),
                   work_dir)
    job.setup(t_process0)
    return job, job.measure(float(args.seconds))
