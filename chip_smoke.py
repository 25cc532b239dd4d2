#!/usr/bin/env python3
"""The quickest proof that nezha-tpu still starts on the chip.

    python3 chip_smoke.py             # one chip (what the driver runs)
    python3 chip_smoke.py --chips 4   # the four-chip host, run by hand

One chip. GPT-2 124M at its published widths (12 layers, hidden 768, 12
heads of 64, vocab 50257, 1,024 positions, bf16 policy, random weights from
a seed) goes through the entry points a user calls, in this one process:

- ``nezha_tpu.cli.train.run``: ``--config gpt2_124m`` at its default batch
  8 x seq 1024 on synthetic tokens; every loss finite, the last below the
  first; ``attn_impl="auto"`` resolved to the flash kernel.
- ``nezha_tpu.cli.serve.run_worker``: the stdio front end answers a few
  requests (prompts longer than one prefill bucket, one sharing a cached
  prefix) on the paged bf16 pool and on the int8 pool, once on the kernel
  path a TPU resolves and once on the composed XLA path
  (``--decode-impl xla --prefill-impl xla``), which is the reference.
  Every request must finish ``length``/``eos`` with zero
  ``serve.errors_total`` and zero ``serve.step_retries_total``. An engine
  built by the CLI's own ``_build_stack`` is then stepped by hand on each
  path: logits after prefill and after the first decode step must agree
  within ``LOGIT_TOL_ULPS`` bf16 ulps of the largest reference logit, and
  every greedy token stream must match the reference's up to the first
  position where the reference's top-2 margin is inside that tolerance
  (random weights give nearly flat, bf16-quantized logits, so exact
  argmax ties are routine and bare token equality would flake).

A whole block of the model with four residual streams (``models/xing4.py``,
its first dense layer at the published widths: hidden 3,584, four float32
streams, 20 Sinkhorn rounds) then runs INSIDE one compiled program on the
kernel path (``nezha_mhc_pre`` / ``nezha_mhc_post`` round each sublayer) and
on the composed path, at a decode step's 32 rows and at a chunk's 256
tokens: the new streams must agree within ``MHC_BLOCK_TOL`` of their own
root mean square. The two kernels were right alone and wrong inside a
program once (PERF.md section 6, PR 36); this is the check that shows it.

Four chips (``--chips 4``) runs only what exists across chips and what it
is compared with: ``--parallel dp --mesh dp=4`` and ``--parallel zero1``
against the same steps on one device of the host (per-step loss within
``LOSS_RTOL``; batch and optimizer state asserted to live on four devices),
then ``nezha-serve --mesh 4`` against ``--mesh 1`` on the dp checkpoint
under the same logit/margin rule, pools asserted head-sharded over four
devices.

Earlier stdout lines are one JSON object per phase. The LAST stdout line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``
and is printed only when every phase passed on a TPU; any failure raises,
so the process exits non-zero without it. No phase is wrapped in a catch.
"""

from __future__ import annotations

import argparse
import dataclasses
import io
import json
import math
import os
import sys
import tempfile
import time

import numpy as np

# What the smoke runs. tests/test_chip_smoke.py rehearses every phase on
# the CPU by patching these to the tiny preset (and KERNEL_IMPL to
# "kernel", which runs the kernels through the Pallas interpreter
# off-TPU); the program itself has no option for that.
MODEL_PRESET = "full"           # GPT-2 124M at published widths
KERNEL_IMPL = "auto"            # what a deployment on a TPU resolves
EXPECT_IMPLS = {"train": "flash", "decode": "kernel", "prefill": "kernel"}
TRAIN_STEPS = 10
NEW_TOKENS = 48
SERVE_SHAPE = ("--max-len", "1024", "--max-batch-size", "8",
               "--max-prefill-len", "256", "--prefill-buckets", "64,256",
               "--kv-block-size", "16", "--kv-num-blocks", "512")
PROMPT_LENS = (300, 417, 556, 150)   # three of four span several chunks
SHARED_PREFIX = (256, 40)       # one more request: r0[:256] + 40 new tokens

LOGIT_TOL_ULPS = 8              # bf16 ulps (2**-8) of the largest |logit|
MHC_PRESET = "full"             # the four-stream model's published widths
MHC_TOKENS = (32, 256)          # a step's rows; two token tiles of a chunk
MHC_BLOCK_TOL = 0.05            # of the new streams' root mean square
LOSS_RTOL = 5e-3                # dp=4 / zero1 vs one device, per step


def say(phase: str, **facts) -> None:
    print(json.dumps({"phase": phase, **facts}), flush=True)


def require_tpu(chips: int):
    """The devices, or exit non-zero: the smoke never runs off the chip."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise SystemExit(
            f"chip_smoke: needs {chips} TPU device(s); jax reports "
            f"{len(devices)} x {devices[0].platform!r}")
    return devices


def cache_entries(cache_dir: str) -> int:
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def count_cache_events() -> dict:
    """Persistent-compile-cache hits and misses from here on, as jax
    itself reports them."""
    import jax.monitoring

    counts = {"hits": 0, "misses": 0}

    def listen(event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            counts["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            counts["misses"] += 1

    jax.monitoring.register_event_listener(listen)
    return counts


def resolved_impls() -> dict:
    """The resolvers' own answers for the three attention sites."""
    from nezha_tpu.cli.common import gpt2_for_preset
    from nezha_tpu.models import gpt2

    cfg = dataclasses.replace(gpt2_for_preset(MODEL_PRESET).cfg,
                              decode_impl=KERNEL_IMPL,
                              prefill_impl=KERNEL_IMPL)
    return {"train": gpt2._resolve_auto_impl(cfg),
            "decode": "kernel" if gpt2._decode_flash_ok(cfg) else "xla",
            "prefill": "kernel" if gpt2._prefill_flash_ok(cfg) else "xla"}


# ------------------------------------------------------------------ train
def train(work: str, tag: str, *extra: str) -> dict:
    """``nezha-train --config gpt2_124m`` for TRAIN_STEPS steps -> per-step
    losses, the first step's seconds (trace + compile + run) and the run's
    final metrics (the placement facts among them)."""
    from nezha_tpu.cli import train as cli

    metrics = os.path.join(work, tag, "metrics.jsonl")
    final = cli.run(cli.build_parser().parse_args([
        "--config", "gpt2_124m", "--model-preset", MODEL_PRESET,
        "--steps", str(TRAIN_STEPS), "--log-every", "1", "--seed", "0",
        "--metrics-file", metrics, *extra]))
    with open(metrics) as f:
        rows = [json.loads(line) for line in f]
    losses = [row["loss"] for row in rows]
    if len(losses) != TRAIN_STEPS or not all(map(math.isfinite, losses)):
        raise RuntimeError(f"{tag}: losses {losses}")
    return {"losses": losses,
            "first_step_s": round(1.0 / rows[0]["steps_per_sec"], 2),
            "placement": {k: final[k] for k in (
                "batch_devices", "state_devices", "state_split_devices")}}


def check_loss_falls(tag: str, losses) -> None:
    if not losses[-1] < losses[0]:
        raise RuntimeError(f"{tag}: loss did not fall: {losses}")


def check_losses_match(tag: str, losses, ref) -> float:
    worst = max(abs(a - b) / abs(b) for a, b in zip(losses, ref))
    if worst > LOSS_RTOL:
        raise RuntimeError(
            f"{tag}: per-step loss off by {worst:.2e} > {LOSS_RTOL}: "
            f"{losses} vs {ref}")
    return worst


# ------------------------------------------------------------------ serve
def make_prompts() -> list:
    from nezha_tpu.cli.common import gpt2_for_preset

    vocab = gpt2_for_preset(MODEL_PRESET).cfg.vocab_size
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, vocab, n).tolist() for n in PROMPT_LENS]
    shared, fresh = SHARED_PREFIX
    prompts.append(prompts[0][:shared] + rng.randint(0, vocab,
                                                     fresh).tolist())
    return prompts


def serve_argv(kv_dtype: str, impl: str, *weights: str) -> list:
    return ["--model-preset", MODEL_PRESET, "--kv-dtype", kv_dtype,
            "--decode-impl", impl, "--prefill-impl", impl,
            "--max-new-tokens", str(NEW_TOKENS), "--seed", "0",
            *SERVE_SHAPE, *weights]


def serve_wire(work: str, tag: str, argv: list, prompts: list) -> dict:
    """The requests through ``run_worker``'s stdio front end. Any error
    event, any finish other than length/eos, any step retry or counted
    error fails."""
    from nezha_tpu.cli import serve as cli

    run_dir = os.path.join(work, tag)
    lines = "".join(json.dumps({
        "id": f"r{i}", "prompt_tokens": prompt,
        "max_new_tokens": NEW_TOKENS, "temperature": 0}) + "\n"
        for i, prompt in enumerate(prompts))
    out = io.StringIO()
    rc = cli.run_worker(
        cli.build_parser().parse_args(argv + ["--run-dir", run_dir]),
        stdin=io.StringIO(lines), stdout=out)
    events = [json.loads(line) for line in out.getvalue().splitlines()]
    done = {e["id"]: e for e in events if e["event"] == "done"}
    bad = [e for e in events if e["event"] == "error"
           or (e["event"] == "done"
               and e["finish_reason"] not in ("length", "eos"))]
    with open(os.path.join(run_dir, "summary.json")) as f:
        counters = json.load(f)["counters"]
    retries = counters.get("serve.step_retries_total", 0)
    errors = counters.get("serve.errors_total", 0)
    if rc or bad or retries or errors or len(done) != len(prompts):
        raise RuntimeError(
            f"{tag}: rc={rc} done={len(done)}/{len(prompts)} "
            f"step_retries={retries} errors={errors} bad={bad[:3]}")
    with open(os.path.join(run_dir, "spans.jsonl")) as f:
        spans = [json.loads(line) for line in f]
    return {"tokens": [done[f"r{i}"]["tokens"]
                       for i in range(len(prompts))],
            "compile_s": [round(s["dur_s"], 2) for s in spans
                          if s["name"] == "executor.compile"]}


def serve_direct(argv: list, prompts: list) -> dict:
    """The same prompts on an engine the CLI's own ``_build_stack``
    builds, stepped by hand: logits after prefill and after the first
    decode step, the greedy tokens, and the top-2 margin of the logits
    each token was chosen from."""
    from nezha_tpu.cli import serve as cli

    scheduler, _, _ = cli._build_stack(cli.build_parser().parse_args(argv))
    engine = scheduler.engine
    # stepped by hand below: each call returns the block it launched
    engine.overlap_blocks(False)
    slots = []
    for prompt in prompts:
        slots.append(engine.pool.alloc())
        engine.prefill(slots[-1], prompt, max_new_tokens=NEW_TOKENS)
    active = np.zeros(engine.cfg.max_batch_size, bool)
    active[slots] = True
    prefill_logits = np.asarray(engine.last_logits)[slots]
    step_logits = None
    tokens, margins = [], []
    for _ in range(NEW_TOKENS):
        top2 = np.partition(np.asarray(engine.last_logits)[slots], -2,
                            axis=-1)[:, -2:]
        margins.append(top2[:, 1] - top2[:, 0])
        block, emitted = engine.step(active)
        if not (emitted[slots] == 1).all() or not engine.step_ok[slots].all():
            raise RuntimeError(f"decode step emitted {emitted[slots]}, "
                               f"ok {engine.step_ok[slots]}")
        tokens.append(block[slots, 0])
        if step_logits is None:
            step_logits = np.asarray(engine.last_logits)[slots]
    return {"engine": engine, "prefill_logits": prefill_logits,
            "step_logits": step_logits,
            "tokens": np.stack(tokens, 1).tolist(),
            "margins": np.stack(margins, 1)}


def compare(tag: str, ref: dict, got: dict, streams: dict) -> dict:
    """``got`` (direct engine on the path under test) and every token
    stream in ``streams`` against the reference direct engine ``ref``,
    by the rule in the module docstring. The first decode step consumes
    the first sampled token, so its logits are compared only for the
    requests whose first token matches the reference's (a mismatch there
    is judged by the margin rule like any other). Raises on a breach;
    returns the facts worth printing."""
    scale = max(1.0, float(np.abs(ref["prefill_logits"]).max()),
                float(np.abs(ref["step_logits"]).max()))
    tol = LOGIT_TOL_ULPS * 2.0 ** -8 * scale
    same_first = np.array([row[0] == want[0] for row, want
                           in zip(got["tokens"], ref["tokens"])])
    per_request = {k: np.abs(got[k] - ref[k]).max(axis=-1)
                   for k in ("prefill_logits", "step_logits")}
    per_request["step_logits"] = per_request["step_logits"][same_first]
    diffs = {k: float(v.max(initial=0.0)) for k, v in per_request.items()}
    if not same_first.any() \
            or not all(np.isfinite(got[k]).all() for k in diffs) \
            or max(diffs.values()) > tol:
        raise RuntimeError(
            f"{tag}: logits off by more than tol {tol:.4f} (first token "
            f"kept: {same_first.tolist()}); per request: "
            f"{ {k: v.round(4).tolist() for k, v in per_request.items()} }")
    agreement, excused = {}, {}
    for name, rows in streams.items():
        agreed = []
        for i, (row, want) in enumerate(zip(rows, ref["tokens"])):
            if len(row) != NEW_TOKENS:
                raise RuntimeError(f"{tag}/{name}: request {i} returned "
                                   f"{len(row)} tokens, not {NEW_TOKENS}")
            first = next((j for j in range(NEW_TOKENS)
                          if row[j] != want[j]), None)
            if first is not None and ref["margins"][i][first] > tol:
                raise RuntimeError(
                    f"{tag}/{name}: request {i} diverges at token {first} "
                    f"where the reference's top-2 margin is "
                    f"{ref['margins'][i][first]:.4f} > tol {tol:.4f}")
            agreed.append(NEW_TOKENS if first is None else first)
            if first is not None:
                excused[f"{name}/r{i}@{first}"] = round(
                    float(ref["margins"][i][first]), 5)
        agreement[name] = agreed
    return {"logit_tol": round(tol, 4),
            "max_logit_diff": {k: round(v, 5) for k, v in diffs.items()},
            "step_logits_compared": int(same_first.sum()),
            "tokens_agreeing_of_%d" % NEW_TOKENS: agreement,
            "ref_margin_at_divergence": excused}


def mhc_block() -> dict:
    """The first block of the four-stream model, kernel path against
    composed path, each ONE compiled program, on the same streams."""
    import jax
    import jax.numpy as jnp

    from nezha_tpu.models.xing4 import xing4
    from nezha_tpu.nn.module import child_vars

    kw = dict(num_hidden_layers=1, vocab_held=256)
    models = {impl: xing4(MHC_PRESET, decode_impl=impl, **kw)
              for impl in ("xla", KERNEL_IMPL)}
    variables = models["xla"].init(jax.random.PRNGKey(0))
    block = child_vars(variables, "h0")
    worst = {}
    for tokens in MHC_TOKENS:
        toks = jax.random.randint(jax.random.PRNGKey(tokens), (1, tokens),
                                  0, 256)
        e = models["xla"].embed.apply(child_vars(variables, "embed"),
                                      toks)[0].astype(jnp.float32)
        x = jnp.concatenate([e] * models["xla"].cfg.hc_mult, axis=-1)
        ref, got = (jax.jit(lambda v, x, blk=m.h[0]: blk.apply(v, x)[0])(
            block, x) for m in models.values())
        worst[tokens] = float(jnp.abs(got - ref).max()
                              / jnp.sqrt((ref * ref).mean()))
        if not worst[tokens] <= MHC_BLOCK_TOL:
            raise RuntimeError(
                f"mhc_block: at {tokens} tokens the kernel path's streams "
                f"are {worst[tokens]:.3g} of their rms from the composed "
                f"path's (limit {MHC_BLOCK_TOL})")
    return {"streams_diff_over_rms": worst, "tol": MHC_BLOCK_TOL}


# ----------------------------------------------------------------- phases
def one_chip(work: str) -> None:
    impls = resolved_impls()
    say("attention", **impls)
    if impls != EXPECT_IMPLS:
        raise RuntimeError(f"attention resolved to {impls}, "
                           f"expected {EXPECT_IMPLS}")
    run = train(work, "train")
    check_loss_falls("train", run["losses"])
    say("train", **run)

    prompts = make_prompts()
    for kv in ("bf16", "int8"):
        kernel = serve_argv(kv, KERNEL_IMPL, "--random-init")
        composed = serve_argv(kv, "xla", "--random-init")
        wire = serve_wire(work, f"serve_{kv}_kernel", kernel, prompts)
        wire_ref = serve_wire(work, f"serve_{kv}_composed", composed,
                              prompts)
        ref = serve_direct(composed, prompts)
        got = serve_direct(kernel, prompts)
        if not got["engine"].prefill_kernel_active \
                or ref["engine"].prefill_kernel_active:
            raise RuntimeError(f"serve_{kv}: prefill kernel flags wrong")
        say(f"serve_{kv}", requests=len(prompts),
            prompt_lens=[len(p) for p in prompts],
            compile_s={"kernel": wire["compile_s"],
                       "composed": wire_ref["compile_s"]},
            **compare(f"serve_{kv}", ref, got, {
                "wire_kernel": wire["tokens"],
                "wire_composed": wire_ref["tokens"],
                "direct_kernel": got["tokens"]}))
    say("mhc_block", **mhc_block())


def four_chips(work: str) -> None:
    from nezha_tpu.models import gpt2
    from nezha_tpu.parallel.gspmd import auto_partitioner_scope
    from nezha_tpu.train.loop import device_span

    ckpt = os.path.join(work, "ckpt_dp4")
    one = train(work, "train_1dev", "--parallel", "single")
    check_loss_falls("train_1dev", one["losses"])
    dp = train(work, "train_dp4", "--parallel", "dp", "--mesh", "dp=4",
               "--batch-size", "8", "--ckpt-dir", ckpt)
    zero1 = train(work, "train_zero1", "--parallel", "zero1",
                  "--mesh", "dp=4", "--batch-size", "8")
    if dp["placement"]["batch_devices"] != 4 \
            or dp["placement"]["state_devices"] != 4 \
            or zero1["placement"]["batch_devices"] != 4 \
            or zero1["placement"]["state_split_devices"] != 4:
        raise RuntimeError(f"not on four devices: dp {dp['placement']} "
                           f"zero1 {zero1['placement']}")
    # dp and zero1 run the step under shard_map, outside the
    # auto-partitioner, so "auto" resolves as it does on one device.
    say("train_4", attn_impl=resolved_impls()["train"], one_device=one,
        dp4=dp, zero1=zero1,
        dp4_max_rel_diff=check_losses_match("train_dp4", dp["losses"],
                                            one["losses"]),
        zero1_max_rel_diff=check_losses_match(
            "train_zero1", zero1["losses"], one["losses"]))

    prompts = make_prompts()
    for kv in ("bf16", "int8"):
        mesh1 = serve_argv(kv, KERNEL_IMPL, "--ckpt-dir", ckpt,
                           "--mesh", "1")
        mesh4 = serve_argv(kv, KERNEL_IMPL, "--ckpt-dir", ckpt,
                           "--mesh", "4")
        wire4 = serve_wire(work, f"serve_{kv}_mesh4", mesh4, prompts)
        wire1 = serve_wire(work, f"serve_{kv}_mesh1", mesh1, prompts)
        ref = serve_direct(mesh1, prompts)
        got = serve_direct(mesh4, prompts)
        engine = got["engine"]
        pools_on = device_span(engine.pool.caches, split_only=True)
        if pools_on != 4:
            raise RuntimeError(f"serve_{kv}_mesh4: pools split over "
                               f"{pools_on} devices")
        with auto_partitioner_scope(engine.mesh):
            nested = {
                "decode": gpt2._decode_flash_shmap_mesh(
                    engine.model.cfg) is not None,
                "prefill": gpt2._prefill_flash_shmap_mesh(
                    engine.model.cfg) is not None}
        say(f"serve_{kv}_mesh4", pools_split_over=pools_on,
            nested_shard_map_kernel=nested,
            compile_s={"mesh4": wire4["compile_s"],
                       "mesh1": wire1["compile_s"]},
            **compare(f"serve_{kv}_mesh4", ref, got, {
                "wire_mesh4": wire4["tokens"],
                "wire_mesh1": wire1["tokens"],
                "direct_mesh4": got["tokens"]}))
        if not all(nested.values()):
            raise RuntimeError(f"serve_{kv}_mesh4 ran composed: {nested}")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    chips = parser.parse_args(argv).chips

    import jax

    devices = require_tpu(chips)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    say("device", **device, jax=jax.__version__)

    from nezha_tpu.utils import enable_persistent_compile_cache
    cache_dir = enable_persistent_compile_cache()
    cache_events = count_cache_events()
    say("compile_cache", dir=cache_dir, entries=cache_entries(cache_dir),
        from_env="JAX_COMPILATION_CACHE_DIR" in os.environ)

    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="nezha_smoke_") as work:
        (one_chip if chips == 1 else four_chips)(work)
    stats = {str(d.id): {k: v for k, v in (d.memory_stats() or {}).items()
                         if k in ("bytes_in_use", "peak_bytes_in_use",
                                  "bytes_limit")}
             for d in devices[:chips]}
    if not all(s.get("peak_bytes_in_use", 0) > 0 for s in stats.values()):
        raise RuntimeError(f"a device never held data: {stats}")
    say("memory", per_device=stats)
    say("compile_cache", dir=cache_dir, entries=cache_entries(cache_dir),
        **cache_events, wall_s=round(time.perf_counter() - t0, 1))
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
