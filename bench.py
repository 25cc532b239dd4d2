"""Headline benchmarks: GPT-2 124M tokens/sec/chip + ResNet-50 images/sec/chip.

Runs the FULL training steps (forward + backward + optimizer) on the TPU
chip jax selects. Prints exactly
ONE JSON line; the headline metric stays GPT-2 tokens/s/chip (tracked by
``vs_baseline``), with ResNet-50 images/s and MFU estimates carried as extra
keys of the same object (BASELINE.md rows 1 and 3):

    {"metric": "gpt2_124m_tokens_per_sec_per_chip", "value": N,
     "unit": "tokens/s/chip", "vs_baseline": R, "platform": "tpu",
     "mfu": F,
     "extras": {"resnet50_images_per_sec_per_chip": M, "resnet50_mfu": F2}}

``vs_baseline`` compares against BASELINE.json's published number when one
exists; the reference published none (BASELINE.md: "no published numbers
were recoverable"), so the fallback baseline is this repo's own recorded
first measurement (bench_baseline.json), making the ratio a regression
tracker. With no record at all it reports 1.0 and writes the record.

A run that finds no TPU FAILS (backend errors propagate, a non-TPU
platform exits non-zero): a CPU number must never stand in for a chip
number. ``NEZHA_BENCH_CPU=1`` is the one explicit CPU pin — the tests'
harness check at toy sizes; its record is labeled ``"platform": "cpu"``
and baselines are kept PER PLATFORM, so it can neither regress nor
overwrite the TPU anchor.

MFU = measured model FLOP/s divided by peak chip FLOP/s. Model FLOPs come
from XLA's own cost analysis of the compiled step, or the standard
6*N_params + attention analytic count where a Pallas kernel hides FLOPs
from XLA. Peaks live in ``PEAK_BF16_FLOPS``, keyed by ``device_kind``; a
kind that is not in the table is an error, not a default.
"""

from __future__ import annotations

import json
import os
import sys
import time


# Peak dense bf16 FLOP/s per chip, keyed by jax's ``device_kind``.
# v5e: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16).
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}


def _aot_compile(step, *args):
    """AOT-compile the step; return (callable, flops-per-XLA-cost-analysis).

    The compiled executable is reused for timing (the jit dispatch cache is
    separate from lower().compile(), so handing back `step` would compile
    the identical program twice). A compile failure propagates.
    """
    compiled = step.lower(*args).compile()
    flops = float(compiled.cost_analysis().get("flops", 0.0))
    return compiled, flops if flops > 0 else None


def _peak_flops(device):
    """Peak chip FLOP/s for MFU; None on the explicit CPU pin (MFU is
    meaningless there). An unknown TPU kind raises."""
    if device.platform != "tpu":
        return None
    try:
        return PEAK_BF16_FLOPS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no peak FLOP/s on record for device kind "
            f"{device.device_kind!r}; add it to PEAK_BF16_FLOPS with its "
            f"source") from None


def _init_backend():
    """Initialize the jax backend and return its first device. Backend
    failures propagate, and a platform that is not a TPU is refused
    unless ``NEZHA_BENCH_CPU`` pinned the CPU explicitly."""
    import jax

    pinned = bool(os.environ.get("NEZHA_BENCH_CPU"))
    if pinned:
        jax.config.update("jax_platforms", "cpu")
    device = jax.devices()[0]
    if device.platform != "tpu" and not pinned:
        raise RuntimeError(
            f"bench: no TPU (jax platform is {device.platform!r}); set "
            f"NEZHA_BENCH_CPU=1 only for a harness check on the CPU")
    return device


# ----------------------------------------------- per-platform baselines
def _load_baseline(path: str):
    """-> (record dict, corrupt flag). A file we failed to parse is
    surfaced as corrupt so a crashed writer can never reset the
    regression anchor to the current run."""
    try:
        with open(path) as f:
            recorded = json.load(f)
    except FileNotFoundError:
        return {}, False
    except (ValueError, OSError):
        return {}, True
    if not isinstance(recorded, dict):
        return {}, True
    return recorded, False


def _family_baseline(recorded: dict, family: str) -> dict:
    """The anchor numbers for one platform. Legacy flat records
    (pre-namespacing) belong to the platform they name (default tpu);
    `by_platform` entries overlay them — so a CPU-pinned run is only
    ever compared against (and only ever records) CPU anchors, and the
    TPU baseline cannot be regressed or overwritten from a machine with
    no TPU."""
    out = {}
    if str(recorded.get("platform", "tpu")) == family:
        out.update({k: v for k, v in recorded.items()
                    if isinstance(v, (int, float))
                    and not isinstance(v, bool)})
    by = recorded.get("by_platform")
    if isinstance(by, dict) and isinstance(by.get(family), dict):
        out.update(by[family])
    return out


def _record_anchors(recorded: dict, family: str, updates: dict) -> None:
    recorded.setdefault("by_platform", {}).setdefault(
        family, {}).update(updates)


def _time_steps(step, state, batch, steps_target: int, budget_s: float,
                windows: int = 5):
    """Warm up, then time ``windows`` independent windows of
    ``steps_target`` steps each (host-fetch barrier per window) and return
    (median steps/sec, relative spread).

    Median-of-N so the regression tracker can see single-digit-percent
    moves through host jitter (VERDICT r2 weak #1: one window hid a 7%
    RN50 regression inside an assumed ±8% noise band; windows are
    ~seconds, compile dominates, so five are as cheap as three). Each
    window ends in a host fetch of the loss — the barrier.
    """
    for _ in range(2):
        state, m = step(state, batch)
    float(m["loss"])

    rates = []
    for _ in range(windows):
        t0 = time.perf_counter()
        done = 0
        while done < steps_target and (time.perf_counter() - t0) < budget_s:
            state, m = step(state, batch)
            done += 1
        float(m["loss"])
        rates.append(done / (time.perf_counter() - t0))
    rates.sort()
    median = rates[len(rates) // 2]
    spread = (rates[-1] - rates[0]) / median if median else 0.0
    return median, spread


def bench_gpt2(on_tpu: bool, peak, **cfg_overrides):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from nezha_tpu import optim
    from nezha_tpu.models.gpt2 import GPT2, GPT2Config, lm_loss
    from nezha_tpu.tensor import bf16_policy
    from nezha_tpu.train.loop import init_train_state, make_train_step

    batch, seq = (8, 1024) if on_tpu else (2, 256)
    steps_target = 20 if on_tpu else 3
    # fused_loss_chunk=-1: bf16 logits with the fp32 upcast fused into the
    # CE's logsumexp — never materializes fp32 [B,S,V] (+3% measured).
    cfg = (GPT2Config(fused_loss_chunk=-1, **cfg_overrides) if on_tpu
           else GPT2Config(num_layers=4, fused_loss_chunk=-1,
                           **cfg_overrides))

    model = GPT2(cfg, policy=bf16_policy())
    opt = optim.adamw(6e-4, weight_decay=0.1)
    state = init_train_state(model, opt, jax.random.PRNGKey(0))
    step = make_train_step(model, opt, lm_loss)

    tokens = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (batch, seq + 1)).astype(np.int32)
    b = {"tokens": jnp.asarray(tokens)}

    step, _xla_flops = _aot_compile(step, state, b)
    # GPT-2 MFU uses the analytic count, not XLA's: the attention runs in a
    # Pallas kernel whose FLOPs are opaque to compiled.cost_analysis(), so
    # the XLA number undercounts. 6*N per token fwd+bwd + 6*L*d*S causal
    # attention (score+value dots, halved for causality).
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(
        state["variables"]["params"]))
    step_flops = (6 * n_params +
                  6 * cfg.num_layers * cfg.hidden_size * seq) * batch * seq

    steps_per_sec, spread = _time_steps(step, state, b, steps_target, 60.0)
    tokens_per_sec = batch * seq * steps_per_sec
    mfu = (step_flops * steps_per_sec / peak) if (peak and step_flops) else None
    return tokens_per_sec, mfu, spread


def bench_resnet50(on_tpu: bool, peak):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from nezha_tpu import ops, optim
    from nezha_tpu.models.resnet import resnet50
    from nezha_tpu.tensor import bf16_policy
    from nezha_tpu.train.loop import init_train_state, make_train_step

    batch, size = (128, 224) if on_tpu else (4, 64)
    steps_target = 10 if on_tpu else 2

    # s2d stem: same arithmetic as the 7x7/s2 conv, relaid out for the MXU
    # (test_s2d_stem_matches_conv7 proves equivalence).
    model = resnet50(stem="s2d" if on_tpu else "conv7",
                     policy=bf16_policy())
    opt = optim.momentum(0.1, beta=0.9, weight_decay=1e-4)
    state = init_train_state(model, opt, jax.random.PRNGKey(0))
    ce = lambda logits, b_: ops.softmax_cross_entropy_with_integer_labels(
        logits, b_["label"]).mean()
    step = make_train_step(model, opt, ce)

    rng = np.random.RandomState(0)
    b = {"image": jnp.asarray(
             rng.rand(batch, size, size, 3).astype(np.float32)),
         "label": jnp.asarray(rng.randint(0, 1000, batch), jnp.int32)}

    step, step_flops = _aot_compile(step, state, b)
    if step_flops is None and peak:
        # RN50 fwd ~= 8.2 GFLOP per 224px image (4.1 GMACs); train ~= 3x.
        step_flops = 3 * 8.2e9 * (size / 224.0) ** 2 * batch
    steps_per_sec, spread = _time_steps(step, state, b, steps_target, 90.0)
    images_per_sec = batch * steps_per_sec
    mfu = (step_flops * steps_per_sec / peak) if (peak and step_flops) else None
    return images_per_sec, mfu, spread


def bench_bert(on_tpu: bool, peak):
    """Config 4's model on one chip (dense adamw step; the ZeRO-1 sharding
    itself is exercised by tests/dryrun — per-chip throughput is the perf
    number of record)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from nezha_tpu import optim
    from nezha_tpu.models.bert import Bert, BertConfig, mlm_loss
    from nezha_tpu.tensor import bf16_policy
    from nezha_tpu.train.loop import init_train_state, make_train_step

    batch, seq = (16, 512) if on_tpu else (2, 64)
    steps_target = 10 if on_tpu else 2
    # fused_loss_chunk=-1: never materializes the fp32 [16,512,30522]
    # logits (~1 GB/step) — same fused-logsumexp head as GPT-2.
    cfg = (BertConfig(fused_loss_chunk=-1) if on_tpu
           else BertConfig(num_layers=2))

    model = Bert(cfg, policy=bf16_policy())
    opt = optim.adamw(1e-4, weight_decay=0.01)
    state = init_train_state(model, opt, jax.random.PRNGKey(0))
    step = make_train_step(model, opt, mlm_loss)

    r = np.random.RandomState(0)
    tokens = r.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    labels = np.full_like(tokens, -100)
    mask = r.rand(batch, seq) < 0.15
    labels[mask] = tokens[mask]
    # No padding_mask: full-length batches; its all-True mask would force
    # composed-XLA attention off the flash path (BertConfig.attn_impl).
    b = {"tokens": jnp.asarray(tokens), "labels": jnp.asarray(labels),
         "segment_ids": jnp.zeros_like(jnp.asarray(tokens))}

    step, _ = _aot_compile(step, state, b)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(
        state["variables"]["params"]))
    step_flops = (6 * n_params +
                  6 * cfg.num_layers * cfg.hidden_size * seq) * batch * seq
    steps_per_sec, spread = _time_steps(step, state, b, steps_target, 60.0)
    tokens_per_sec = batch * seq * steps_per_sec
    mfu = (step_flops * steps_per_sec / peak) if (peak and step_flops) else None
    return tokens_per_sec, mfu, spread


def bench_wrn101(on_tpu: bool, peak):
    """Config 5: Wide-ResNet-101-2, large-batch mixed bf16/fp32."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from nezha_tpu import ops, optim
    from nezha_tpu.models.resnet import ResNet, wide_resnet101
    from nezha_tpu.tensor import bf16_policy
    from nezha_tpu.train.loop import init_train_state, make_train_step

    batch, size = (64, 224) if on_tpu else (2, 64)
    steps_target = 5 if on_tpu else 2

    model = (wide_resnet101(stem="s2d", policy=bf16_policy()) if on_tpu
             else ResNet((1, 1, 1, 1), width_factor=2, policy=bf16_policy()))
    opt = optim.momentum(0.1, beta=0.9, weight_decay=1e-4)
    state = init_train_state(model, opt, jax.random.PRNGKey(0))
    ce = lambda logits, b_: ops.softmax_cross_entropy_with_integer_labels(
        logits, b_["label"]).mean()
    step = make_train_step(model, opt, ce)

    rng = np.random.RandomState(0)
    b = {"image": jnp.asarray(
             rng.rand(batch, size, size, 3).astype(np.float32)),
         "label": jnp.asarray(rng.randint(0, 1000, batch), jnp.int32)}

    step, step_flops = _aot_compile(step, state, b)
    if step_flops is None and peak:
        # WRN-101-2 fwd ~= 45.6 GFLOP per 224px image; train ~= 3x.
        step_flops = 3 * 45.6e9 * (size / 224.0) ** 2 * batch
    steps_per_sec, spread = _time_steps(step, state, b, steps_target, 90.0)
    images_per_sec = batch * steps_per_sec
    mfu = (step_flops * steps_per_sec / peak) if (peak and step_flops) else None
    return images_per_sec, mfu, spread


def bench_mlp(on_tpu: bool):
    """Config 1 through the REAL CLI entry (the reference's CPU-path
    benchmark config): examples/sec from the trainer's own metrics.

    Two logging windows; the returned metrics are the LAST one, whose t0
    resets after the first window — so the reported rate excludes the
    first-step compile (the Trainer's window timer starts before step 1)."""
    from nezha_tpu.cli.train import build_parser, run

    steps = 300 if on_tpu else 20
    metrics = run(build_parser().parse_args(
        ["--config", "mlp_mnist", "--steps", str(steps),
         "--batch-size", "256", "--log-every", str(steps // 2)]))
    return metrics.get("examples_per_sec", 0.0)


def main() -> int:
    import jax

    device = _init_backend()
    platform = device.platform
    on_tpu = platform == "tpu"
    peak = _peak_flops(device)

    # Persistent compile cache (same-machine): repeat bench sessions reuse
    # executables instead of paying the 20-40 s first-compile per config.
    from nezha_tpu.utils import enable_persistent_compile_cache
    enable_persistent_compile_cache()

    # Dispatch round-trip: one trivial op + host fetch per call, recorded
    # beside the configs so a dispatch-bound number (the CLI MLP) can be
    # attributed mechanically.
    import jax.numpy as jnp
    _x = jnp.zeros((), jnp.float32)
    _add = jax.jit(lambda v: v + 1.0)
    _add(_x).block_until_ready()
    _t0 = time.perf_counter()
    for _ in range(20):
        _x = _add(_x)
        _x.block_until_ready()
    ping_ms = (time.perf_counter() - _t0) / 20 * 1e3

    tokens_per_sec, gpt2_mfu, gpt2_spread = bench_gpt2(on_tpu, peak)
    images_per_sec, rn50_mfu, rn50_spread = bench_resnet50(on_tpu, peak)
    bert_tps, bert_mfu, _ = bench_bert(on_tpu, peak)
    wrn_ips, wrn_mfu, _ = bench_wrn101(on_tpu, peak)
    mlp_eps = bench_mlp(on_tpu)

    # r5 trunk-lever A/B points (ROADMAP Speed 6). They run LAST, after
    # every headline config; a variant that fails fails the run.
    gpt2_scan_tps = gpt2_ln_tps = None
    if on_tpu:
        gpt2_scan_tps = bench_gpt2(on_tpu, peak, scan_layers=True)[0]
        gpt2_ln_tps = bench_gpt2(on_tpu, peak, ln_impl="pallas")[0]

    baseline_path = os.environ.get("NEZHA_BENCH_BASELINE") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "bench_baseline.json")
    recorded, corrupt = _load_baseline(baseline_path)
    anchors = _family_baseline(recorded, platform)
    vs_baseline = 1.0
    base = anchors.get("gpt2_124m_tokens_per_sec_per_chip")
    if isinstance(base, (int, float)) and base > 0:
        vs_baseline = tokens_per_sec / base
    else:
        base = None
    if not corrupt:
        # Record this platform's first measurements (regression
        # anchors); never overwrite an existing anchor, never touch
        # another platform's — a CPU-pinned run can only ever seed or
        # compare against the CPU slot.
        updates = {}
        if not base:
            updates["gpt2_124m_tokens_per_sec_per_chip"] = tokens_per_sec
        if not anchors.get("resnet50_images_per_sec_per_chip"):
            updates["resnet50_images_per_sec_per_chip"] = images_per_sec
        if updates:
            _record_anchors(recorded, platform, updates)
            try:
                with open(baseline_path, "w") as f:
                    json.dump(recorded, f)
            except OSError:
                pass

    rn50_base = anchors.get("resnet50_images_per_sec_per_chip")
    extras = {
        "resnet50_images_per_sec_per_chip": round(images_per_sec, 2),
        "gpt2_spread": round(gpt2_spread, 4),
        "resnet50_spread": round(rn50_spread, 4),
        "bert_base_tokens_per_sec_per_chip": round(bert_tps, 2),
        "wrn101_images_per_sec_per_chip": round(wrn_ips, 2),
        "mlp_examples_per_sec": round(mlp_eps, 2),
        "ping_ms": round(ping_ms, 3),
    }
    if isinstance(rn50_base, (int, float)) and rn50_base > 0:
        extras["resnet50_vs_baseline"] = round(images_per_sec / rn50_base, 4)
    if rn50_mfu is not None:
        extras["resnet50_mfu"] = round(rn50_mfu, 4)
    if bert_mfu is not None:
        extras["bert_base_mfu"] = round(bert_mfu, 4)
    if wrn_mfu is not None:
        extras["wrn101_mfu"] = round(wrn_mfu, 4)
    if gpt2_scan_tps is not None:
        extras["gpt2_scan_tokens_per_sec"] = round(gpt2_scan_tps, 2)
    if gpt2_ln_tps is not None:
        extras["gpt2_ln_pallas_tokens_per_sec"] = round(gpt2_ln_tps, 2)

    out = {
        "metric": "gpt2_124m_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 2),
        "unit": "tokens/s/chip",
        "vs_baseline": round(vs_baseline, 4),
        # The label makes a CPU-pinned record legible as one: its
        # vs_baseline tracks the CPU anchor, never the TPU number.
        "platform": platform,
        "device_kind": device.device_kind,
        "extras": extras,
    }
    if gpt2_mfu is not None:
        out["mfu"] = round(gpt2_mfu, 4)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
