"""Training cells: the program's own entry point (``cli/train.py::run``),
timed from the benchmark's side.

``run`` takes a number of steps, not seconds, and owns its ``Trainer``, so
the job runs twice in this process. A set-up call of two steps with
``--log-every 1`` gives the first step's loss for the comparison with the
plain reference. The measured call gets the mix's ``steps`` and
``log_every``: a fixed number, because the schedule's horizon
(``max(steps, 200)``) is compiled into the step, so a number sized from a
timed step would make a different program, and a cold compile, by chance.
It is chosen to outlast ``--seconds`` with room to spare; where a faster
program runs out of steps first, the window ends with the last one. Each
log record closes a lap of ``log_every`` steps with a host fetch of the
loss; a thread that stays off jax tails ``--metrics-file`` and stamps each
record on the benchmark's clock. The first lap holds the trace and the
cache read and is set-up; the measured window is the whole number of laps
after it that fits ``--seconds``.
"""

from __future__ import annotations

import json
import math
import os
import shutil
import threading
import time

import numpy as np

from chipbench import checks, traffic as traffic_lib
from chipbench.obs import Obs
from chipbench.trace import kernel_costs

clock = time.perf_counter
# No annotation of the benchmark's own in a train trace: the program opens
# its profile window inside ``fit``, and an annotation that began before
# the window is not recorded. Idle gaps there have no owner until the
# program annotates its steps (PERF.md, open questions).
ANNOTATIONS = ()


class Tail(threading.Thread):
    """Stamps each line appended to ``path`` with the benchmark's clock."""

    def __init__(self, path: str):
        super().__init__(daemon=True)
        self.path, self.records, self._done = path, [], threading.Event()

    def run(self):
        while not os.path.exists(self.path):
            if self._done.is_set():
                return
            time.sleep(0.0005)
        with open(self.path) as f:
            buf = ""
            while True:
                chunk = f.readline()
                if chunk.endswith("\n"):
                    self.records.append((clock(), json.loads(buf + chunk)))
                    buf = ""
                elif chunk:
                    buf += chunk
                elif self._done.is_set():
                    return
                else:
                    time.sleep(0.0002)

    def finish(self):
        self._done.set()
        self.join()


def _call(cli, argv, metrics_path):
    if os.path.exists(metrics_path):
        os.remove(metrics_path)
    tail = Tail(metrics_path)
    tail.start()
    try:
        final = cli.run(cli.build_parser().parse_args(
            argv + ["--metrics-file", metrics_path]))
    finally:
        tail.finish()
    return tail.records, final


def reference_loss(cli, args, job, chips: int = 1) -> dict:
    """The plain reference's forward loss on the job's first batch, at the
    weights the seed gives, in blocks of a few sequences. Across chips the
    program's data-parallel steps average each chip's own mean loss, which
    for MLM (a different number of masked positions on each chip) is not
    the mean over the whole batch: the reference is given the same shares,
    contiguous rows of the batch, and averages their means."""
    import jax
    import jax.numpy as jnp

    cfg = cli._configs()[args.config]
    if args.model_preset == "tiny":
        for field, value in cfg.tiny.items():
            setattr(cfg, field, value)
    model = cfg.build_model()
    params = model.init(jax.random.PRNGKey(args.seed))["params"]
    batch = traffic_lib.train_batch(job, job["batch_size"])
    heads = model.cfg.num_heads
    if job["reference"] == "gpt2":
        from chipbench.reference import gpt2 as ref
        fn = jax.jit(lambda p, b: ref.lm_loss_sum(p, b["tokens"], heads))
    else:
        from chipbench.reference import bert as ref
        fn = jax.jit(lambda p, b: ref.mlm_loss_sum(
            p, b["tokens"], b["segment_ids"], b["labels"], heads))
    block = job["reference_block"]
    share = job["batch_size"] // chips
    if share % block:
        raise ValueError(f"reference_block {block} must divide a chip's "
                         f"{share} rows")
    means, count = [], 0
    for lo in range(0, job["batch_size"], share):
        total = n_share = 0
        for i in range(lo, lo + share, block):
            part = {k: jnp.asarray(v[i:i + block]) for k, v in batch.items()}
            s, n = fn(params, part)
            total += float(s)
            n_share += int(n)
        means.append(total / n_share)
        count += n_share
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))
    return {"loss": sum(means) / len(means), "targets": count,
            "n_params": n_params,
            "num_layers": model.cfg.num_layers,
            "hidden_size": model.cfg.hidden_size, "num_heads": heads}


class TrainRun:
    def __init__(self, cell, seed, seconds, trace, work_dir):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.trace, self.work_dir = trace, work_dir
        self.obs = Obs()
        self.facts = {}
        self.trace_dir = None
        self.trace_span = None      # serve cells only

    def run(self, t_process0: float) -> dict:
        from chipbench import device as device_lib
        from nezha_tpu.cli import train as cli

        job = self.cell["traffic"]["job"]
        chips = self.cell["chips"]
        base = list(job["argv"]) + ["--seed", str(self.seed)]
        args0 = cli.build_parser().parse_args(base + ["--steps", "2"])
        compiles = device_lib.CompileCounter()
        ref = reference_loss(cli, args0, job, chips)
        t_ref = clock()

        # ---- set-up call: two steps, every step logged ----
        records, _ = _call(cli, base + ["--steps", "2", "--log-every", "1"],
                           os.path.join(self.work_dir, "setup.jsonl"))
        t_setup_call = clock()
        if len(records) != 2:
            raise RuntimeError(f"set-up call logged {len(records)} records")
        first_loss = records[0][1]["loss"]
        warm_step_s = records[1][0] - records[0][0]
        rtol = job["loss_rtol"]
        loss_ok = checks.loss_close(first_loss, ref["loss"], rtol)

        # ---- measured call ----
        every, steps = int(job["log_every"]), int(job["steps"])
        laps = steps // every - 1           # the first lap is set-up
        argv = base + ["--steps", str(steps), "--log-every", str(every)]
        # a traced run profiles a few steps past the middle of the window
        trace_start = every * (1 + int(0.6 * laps))
        if self.trace:
            self.trace_dir = os.path.join(self.work_dir, "trace")
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            argv += ["--profile-dir", self.trace_dir, "--profile-steps",
                     f"{trace_start}:{int(job['trace_steps'])}"]
        records, final = _call(cli, argv,
                               os.path.join(self.work_dir, "run.jsonl"))
        if len(records) < 3:
            raise RuntimeError(f"measured call logged {len(records)} "
                               f"records; needs 3")
        t1 = records[0][0]
        if self.trace:
            # host samples only from the windows before the profiler
            # started: starting and stopping it stalls the loop
            usable = [r for r in records if r[1]["step"] <= trace_start]
        else:
            usable = records
        m = 0
        for i in range(1, len(usable)):
            if usable[i][0] - t1 <= self.seconds:
                m = i
        if m < 1:
            raise RuntimeError("no whole log window fits the run")
        t_end = usable[m][0]
        window_steps = m * every
        tokens_per_step = job["batch_size"] * job["seq_len"]
        losses = [r[1]["loss"] for r in records]
        finite = all(map(math.isfinite, losses))
        bad_steps = every * sum(1 for r in usable[1:m + 1]
                                if not math.isfinite(r[1]["loss"]))
        falls = usable[m][1]["loss"] < first_loss
        compiled = compiles.compiles_between(t1, t_end)
        # The program's own clock over the same laps (each record's
        # steps_per_sec is its lap): the two must agree, or the benchmark's
        # stamps are not what it timed. A stall inside the program shows on
        # both clocks, so it is told by the slowest lap instead.
        lap_s = [usable[i][0] - usable[i - 1][0] for i in range(1, m + 1)]
        program_s = sum(every / r[1]["steps_per_sec"]
                        for r in usable[1:m + 1])
        clock_diff = abs(program_s - (t_end - t1)) / (t_end - t1)
        clocks_agree = clock_diff <= job["clock_rtol"]

        obs = self.obs
        for lap in lap_s:
            obs.sample("train_step_ms", lap / every * 1e3)
        obs.set("setup_s", t1 - t_process0)
        obs.set("window_s", t_end - t1)
        obs.set("chips", chips)
        obs.set("train_tokens", window_steps * tokens_per_step)
        obs.set("train_chip_seconds", (t_end - t1) * chips)
        obs.set("flops_per_token",
                kernel_costs.transformer_train_flops_per_token(
                    ref["n_params"], ref["num_layers"], ref["hidden_size"],
                    job["seq_len"]))
        obs.model = {**ref, "batch_size": job["batch_size"],
                     "seq_len": job["seq_len"], "causal": job["causal"]}
        placement = {k: final.get(k) for k in (
            "batch_devices", "state_devices", "state_split_devices")}
        placed = chips == 1 or placement["batch_devices"] == chips
        self.facts.update(
            reference_s=t_ref - t_process0,
            setup_call_s=t_setup_call - t_ref,
            first_window_s=t1 - t_setup_call,
            reference_loss=ref["loss"], first_step_loss=first_loss,
            loss_rel_diff=abs(first_loss - ref["loss"]) / abs(ref["loss"]),
            loss_rtol=rtol, last_loss=usable[m][1]["loss"],
            warm_step_s=warm_step_s, steps_asked=steps,
            window_steps=window_steps, window_s=t_end - t1,
            program_clock_window_s=program_s, clock_rel_diff=clock_diff,
            clock_rtol=job["clock_rtol"],
            slowest_lap_over_median=max(lap_s) / float(np.median(lap_s)),
            placement=placement, compilations_in_window=compiled,
            compile_events=compiles.snapshot())
        why = [w for w, bad in (
            ("first-step loss off the reference", not loss_ok),
            ("a loss is not finite", not finite),
            ("loss did not fall", not falls),
            ("a program was built inside the window", compiled > 0),
            ("the benchmark's clock and the program's laps disagree",
             not clocks_agree),
            (f"batch not on {chips} devices", not placed)) if bad]
        if why:
            self.facts["why_not_correct"] = why
        return {"correct": not why, "attempted": window_steps,
                "failed": bad_steps}


def run(cell, args, work_dir, t_process0):
    job = TrainRun(cell, args.seed, float(args.seconds), bool(args.trace),
                   work_dir)
    return job, job.run(t_process0)
