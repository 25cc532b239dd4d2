"""Training step construction and the host-side training loop.

The reference's hot loop (SURVEY.md §3: forward over op graph -> loss ->
backward -> collective -> optimizer update) becomes ONE jit'd function here:
XLA sees forward+backward+update as a single program, fuses it, and overlaps
the DP gradient collective with backward compute. Buffer donation makes the
parameter/optimizer-state update in-place in HBM (the TPU analogue of the
reference's in-place CUDA optimizer kernels).
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, Iterator, Optional

import jax
import numpy as np
import jax.numpy as jnp

from nezha_tpu import obs
from nezha_tpu.nn.module import Module, Variables
from nezha_tpu.obs.metrics import StepTimer
from nezha_tpu.optim.optimizers import Optimizer, apply_updates

TrainState = Dict[str, Any]  # {"variables": Variables, "opt_state": Any, "rng": key}


def merge_state(old: Any, new: Any) -> Any:
    """Overlay partial state updates (e.g. BatchNorm stats) onto old state."""
    if not isinstance(new, dict) or not isinstance(old, dict):
        return new if new is not None else old
    out = dict(old)
    for k, v in new.items():
        out[k] = merge_state(old.get(k), v) if k in old else v
    return out


def init_train_state(model: Module, optimizer: Optimizer, rng: jax.Array) -> TrainState:
    variables = model.init(rng)
    return {
        "variables": variables,
        "opt_state": optimizer.init(variables["params"]),
        "rng": rng,
    }


def make_train_step(model: Module, optimizer: Optimizer,
                    loss_fn: Callable[[Any, dict, Variables], Any],
                    jit: bool = True, donate: bool = True):
    """Build the fused train step.

    ``loss_fn(model_out, batch)`` -> scalar loss. The model is called as
    ``model.apply(variables, batch, training=True, rng=...)`` — models take the
    whole batch dict or its main tensor; see each model's ``apply``.

    Returns ``step(state, batch) -> (state, metrics)``.
    """

    def step(state: TrainState, batch: dict):
        variables, opt_state = state["variables"], state["opt_state"]
        rng, step_rng = jax.random.split(state["rng"])

        def compute_loss(params):
            out, new_state = model.apply(
                {"params": params, "state": variables["state"]},
                batch, training=True, rng=step_rng)
            loss = loss_fn(out, batch)
            return jnp.asarray(loss, jnp.float32), (new_state, out)

        (loss, (new_state, _)), grads = jax.value_and_grad(
            compute_loss, has_aux=True)(variables["params"])
        updates, opt_state = optimizer.update(grads, opt_state, variables["params"])
        params = apply_updates(variables["params"], updates)
        new_variables = {"params": params,
                         "state": merge_state(variables["state"], new_state)}
        metrics = {"loss": loss}
        return ({"variables": new_variables, "opt_state": opt_state, "rng": rng},
                metrics)

    if jit:
        step = jax.jit(step, donate_argnums=(0,) if donate else ())
    return step


class Trainer:
    """Host-side loop: pulls batches, dispatches jit'd steps (async — JAX
    queues steps ahead while the host prepares the next batch), logs metrics,
    periodically checkpoints."""

    def __init__(self, model: Module, optimizer: Optimizer, loss_fn,
                 rng: Optional[jax.Array] = None,
                 checkpoint_dir: Optional[str] = None,
                 checkpoint_every: int = 0,
                 log_every: int = 10,
                 metric_logger: Optional[Callable[[int, dict], None]] = None,
                 tracer=None,
                 process_group=None,
                 failure_check_every: int = 0,
                 on_failure: Optional[Callable[[list], None]] = None,
                 failure_mode: str = "stop",
                 rejoin_timeout_s: float = 300.0,
                 recover_fn: Optional[Callable[[], None]] = None,
                 step_fn=None,
                 shard_fn: Optional[Callable[[dict], dict]] = None,
                 save_fn: Optional[Callable[[str, Any, int], Any]] = None,
                 save_wait: Optional[Callable[[], None]] = None,
                 checkpoint_keep: Optional[int] = None,
                 examples_per_step: int = 0,
                 tokens_per_step: int = 0):
        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.rng = rng if rng is not None else jax.random.PRNGKey(0)
        self.checkpoint_dir = checkpoint_dir
        self.checkpoint_every = checkpoint_every
        self.log_every = log_every
        self.metric_logger = metric_logger
        # Optional aux subsystems: a utils.Tracer to capture an XLA profile
        # over a step window, and a dist.ProcessGroup polled for dead peers
        # (reference coordinator heartbeat role, SURVEY.md §1) so a healthy
        # rank can checkpoint-and-stop instead of hanging in a collective.
        self.tracer = tracer
        self.process_group = process_group
        self.failure_check_every = failure_check_every
        self.on_failure = on_failure
        # Elastic recovery (SURVEY.md §5): "stop" checkpoints then raises
        # (supervisor restarts the world); "rejoin" additionally waits for
        # the dead rank's replacement to re-rendezvous (the coordinator
        # frees crashed rank slots, csrc/coordinator.cpp rejoin), reloads
        # the rescue checkpoint, and CONTINUES in-process. recover_fn, when
        # set, replaces the default reload (initialize) for states that
        # need mode-specific re-layout after restore.
        if failure_mode not in ("stop", "rejoin"):
            raise ValueError(f"failure_mode must be stop|rejoin, got "
                             f"{failure_mode!r}")
        if failure_mode == "rejoin":
            # Reject the combos whose semantics would otherwise silently
            # degrade: recovery NEEDS a checkpoint to reload, and an
            # on_failure callback would never fire on the heal path.
            if not checkpoint_dir:
                raise ValueError("failure_mode='rejoin' needs a "
                                 "checkpoint_dir: recovery reloads the "
                                 "rescue checkpoint")
            if on_failure is not None:
                raise ValueError("failure_mode='rejoin' and on_failure are "
                                 "mutually exclusive (rejoin continues "
                                 "in-process; the callback would never "
                                 "fire)")
        self.failure_mode = failure_mode
        self.rejoin_timeout_s = rejoin_timeout_s
        self.recover_fn = recover_fn
        # Injection points so one loop serves every parallelism mode: a
        # prebuilt sharded step (DP/ZeRO-1/GSPMD), a host-side batch-placement
        # fn, and a checkpoint writer (e.g. sharded_checkpoint.save_sharded).
        self.step_fn = step_fn if step_fn is not None else make_train_step(
            model, optimizer, loss_fn)
        self.shard_fn = shard_fn
        self._save_fn = save_fn
        # For async save_fns (AsyncCheckpointer.save): blocks until the
        # in-flight write commits. Called before raising on peer failure —
        # a rescue checkpoint whose files are still being written when the
        # process dies is a torn save.
        self._save_wait = save_wait
        # Retention: keep only the N newest checkpoints (None = keep all).
        # Custom save_fns handle their own pruning (the CLI wraps them).
        self.checkpoint_keep = checkpoint_keep
        self.examples_per_step = examples_per_step
        # Tokens consumed per optimizer step (LM configs: batch x seq) —
        # feeds the tokens/sec-per-chip metric of record (PAPER.md §0).
        self.tokens_per_step = tokens_per_step
        # Rate windows close on the loop's own log boundaries (a resume
        # can land mid-window), so the timer runs in explicit-lap mode.
        self._timer = StepTimer(window=max(log_every, 1))
        self._first_step = True  # next dispatch pays trace+compile
        self._n_chips = 1  # set from the first batch's placement
        self.state: Optional[TrainState] = None
        self.global_step = 0

    def _save(self, step: int) -> None:
        with obs.span("checkpoint.save", step=step):
            self._save_checkpoint(step)

    def _save_checkpoint(self, step: int) -> None:
        if self._save_fn is not None:
            if self.checkpoint_keep:
                # Every built-in save_fn (save_checkpoint, save_sharded,
                # AsyncCheckpointer.save) takes keep_last; only pass it when
                # retention is on so bare custom save_fns keep working.
                self._save_fn(self.checkpoint_dir, self.state, step,
                              keep_last=self.checkpoint_keep)
            else:
                self._save_fn(self.checkpoint_dir, self.state, step)
        else:
            from nezha_tpu.train import checkpoint as ckpt
            ckpt.save_checkpoint(self.checkpoint_dir, self.state, step,
                                 keep_last=self.checkpoint_keep)

    def _rejoin_and_reload(self, failed: list) -> None:
        """The healthy-rank half of elastic recovery: the rescue checkpoint
        is already committed (fit saves before calling this); poll until the
        coordinator reports no failed ranks (the replacement's HELLO clears
        the mark), then reload the rescue checkpoint so survivor and
        replacement resume from the same step with identical state. Raises
        if no replacement rejoins within ``rejoin_timeout_s``."""
        import sys

        print(f"peer rank(s) {failed} failed at step {self.global_step}; "
              f"checkpoint committed; waiting for rejoin "
              f"(timeout {self.rejoin_timeout_s:.0f}s)", file=sys.stderr)
        deadline = time.monotonic() + self.rejoin_timeout_s
        while True:
            still = self.process_group.failed_ranks()
            if not still:
                break
            if time.monotonic() > deadline:
                raise RuntimeError(
                    f"peer rank(s) {still} failed at step "
                    f"{self.global_step}; no replacement rejoined within "
                    f"{self.rejoin_timeout_s:.0f}s")
            time.sleep(0.2)
        if self.recover_fn is not None:
            self.recover_fn()
        else:
            self.initialize(resume=True)
        print(f"world healed; resumed from step {self.global_step}",
              file=sys.stderr)

    def initialize(self, resume: bool = True):
        from nezha_tpu.train import checkpoint as ckpt
        state = init_train_state(self.model, self.optimizer, self.rng)
        if resume and self.checkpoint_dir:
            if self._save_fn is not None:
                # A custom save_fn means a custom on-disk format; the only
                # shipped one is the per-shard layout, so pair its restore.
                from nezha_tpu.train import sharded_checkpoint as sck
                restored, step = sck.try_restore_sharded(
                    self.checkpoint_dir, state)
            else:
                restored, step = ckpt.try_restore(self.checkpoint_dir, state)
            if restored is not None:
                # One device-side copy so XLA is the SOLE owner of the
                # bytes: the dense restore returns numpy leaves, and on
                # CPU the implicit (or explicit) device transfer may
                # zero-copy ALIAS the host buffer — the next DONATING
                # train step then has XLA free memory numpy still owns
                # (NaN state, then a glibc heap abort; reproduced on
                # jax 0.4.37 by the elastic-rejoin reload in
                # tests/test_cli.py). jnp.asarray may alias; .copy()
                # allocates an XLA-owned buffer the alias is read from.
                # numpy leaves only: the sharded restore already hands
                # back XLA-owned copies (sharded_checkpoint.py), and a
                # second whole-state copy would transiently double
                # restore memory.
                restored = jax.tree_util.tree_map(
                    lambda a: jnp.asarray(a).copy()
                    if isinstance(a, np.ndarray) else a,
                    restored)
                state, self.global_step = restored, step
        self.state = state
        return state

    def fit(self, batches: Iterator[dict], steps: int) -> Dict[str, float]:
        if self.state is None:
            self.initialize()
        last_metrics: Dict[str, float] = {}
        self._timer.start()
        window_steps = 0  # actual steps this logging window (a resume can
        # land mid-window, so log_every would overstate the first rate)
        for _ in range(steps):
            # A profile window opens and closes here, between two steps
            # and before the step it names, on a drained device: an
            # annotation that began before the window opened is not
            # recorded, and a device op caught in flight has no host
            # span over it, so the window holds whole steps only, every
            # one on record.
            if self.tracer is not None:
                self.tracer.maybe_trace(self.global_step + 1,
                                        sync=self._drain)
            # One iteration = one train.step on the profiler's timeline,
            # its host phases nested inside: who owns an idle gap of the
            # device is read off these (PERF.md §3).
            with obs.annotate_step("train.step", self.global_step + 1):
                with obs.annotate("train.data"):
                    batch = next(batches)
                    if self.shard_fn is not None:
                        batch = self.shard_fn(batch)
                metrics = self._dispatch(batch)
                self.global_step += 1
                window_steps += 1
                if self._peers_rejoined():
                    # Rate windows must not count the heal wait.
                    self._timer.start()
                    window_steps = 0
                    continue
                if self.log_every and self.global_step % self.log_every == 0:
                    last_metrics = self._log_window(metrics, window_steps)
                    window_steps = 0
                if (self.checkpoint_every and self.checkpoint_dir
                        and self.global_step % self.checkpoint_every == 0):
                    self._save(self.global_step)
        if not last_metrics and steps:
            last_metrics = {k: float(v) for k, v in metrics.items()}
            last_metrics["step"] = self.global_step
        return last_metrics

    def _drain(self) -> None:
        """Block until the device has run every dispatched step (the
        barrier at either end of a profile window). On the timeline it
        is a ``train.fetch``: the host blocked on the device."""
        with obs.annotate("train.fetch"):
            jax.block_until_ready(self.state)

    def _dispatch(self, batch) -> Dict[str, Any]:
        """Enqueue one optimizer step; returns its (device) metrics."""
        first = obs.NULL_SPAN
        if self._first_step:
            self._first_step = False
            if (not self.tokens_per_step and isinstance(batch, dict)
                    and hasattr(batch.get("tokens"), "size")):
                # LM batches: global tokens consumed per step, for the
                # tokens/sec-per-chip metric (shape is static, so one
                # read here covers the run).
                self.tokens_per_step = int(batch["tokens"].size)
            # The chips the *_per_chip rates divide by: the devices the
            # batch is placed on (1 in single-device mode, whatever else
            # the host holds).
            self._n_chips = max(device_span(batch), 1)
            # The first dispatch carries trace+compile; as a span it is
            # the run's compile-time record (jit compiles synchronously,
            # so the call returns after the build).
            first = obs.span("train.first_step", step=self.global_step + 1)
        with obs.annotate("train.dispatch"), first:
            self.state, metrics = self.step_fn(self.state, batch)
        return metrics

    def _peers_rejoined(self) -> bool:
        """The periodic peer-failure check. False when it is not due or
        every peer is alive; True when the world healed and the state
        was reloaded (the step just taken is void); raises (or calls
        ``on_failure``) otherwise."""
        if not (self.failure_check_every and self.process_group is not None
                and self.global_step % self.failure_check_every == 0):
            return False
        failed = self.process_group.failed_ranks()
        if not failed:
            return False
        if self.checkpoint_dir:  # preserve progress first
            self._save(self.global_step)
            if self._save_wait is not None:
                self._save_wait()  # commit before raising
        if self.failure_mode == "rejoin":  # ckpt_dir guaranteed
            with obs.span("train.rejoin", failed=failed):
                self._rejoin_and_reload(failed)
            return True
        if self.on_failure is None:
            raise RuntimeError(f"peer rank(s) {failed} failed at step "
                               f"{self.global_step}")
        self.on_failure(failed)
        return False

    def _log_window(self, metrics, window_steps: int) -> Dict[str, float]:
        """Close one logging window: fetch the step's metrics, lap the
        timer, derive the rates, record and log."""
        with obs.annotate("train.fetch"):
            # The float() fetches are the window's device barrier (the
            # StepTimer contract): every dispatched step has finished
            # before the lap closes.
            out = {k: float(v) for k, v in metrics.items()}
        rate = self._timer.lap(out.get("loss", 0.0), window_steps)
        out["steps_per_sec"] = rate if rate is not None else 0.0
        if self.examples_per_step:
            eps = out["steps_per_sec"] * self.examples_per_step
            out["examples_per_sec"] = eps
            out["examples_per_sec_per_chip"] = eps / self._n_chips
        if self.tokens_per_step:
            tps = out["steps_per_sec"] * self.tokens_per_step
            out["tokens_per_sec"] = tps
            out["tokens_per_sec_per_chip"] = tps / self._n_chips
        out["step"] = self.global_step
        obs.counter("train.steps").inc(window_steps)
        obs.record_metrics(self.global_step, out)
        if self.metric_logger:
            self.metric_logger(self.global_step, out)
        return out


def device_span(tree, split_only: bool = False) -> int:
    """Most devices any jax.Array leaf of ``tree`` occupies; with
    ``split_only``, counting only leaves whose sharding partitions them
    (replicas excluded). 0 when no leaf qualifies."""
    span = 0
    for x in jax.tree_util.tree_leaves(tree):
        sharding = getattr(x, "sharding", None)
        if sharding is None or (split_only
                                and sharding.is_fully_replicated):
            continue
        span = max(span, len(sharding.device_set))
    return span
