"""Decompose the CLI MLP number (VERDICT r4 item 7: 2,330 ex/s at B=256
is ~9 steps/s — orders of magnitude below what a 3-layer MLP should do).

Prints one JSON line per measurement so the attribution is mechanical:

  - ping_ms:        round-trip of a trivial dispatch+fetch.
  - bare_steps_ps:  jitted train step, batch staged on device ONCE,
                    async dispatch with a single trailing block — the
                    framework-free ceiling.
  - feed_steps_ps:  same step but a fresh host batch transferred every
                    step (the Trainer's pattern: next(batches) ->
                    jnp.asarray -> step).
  - loader_batches_ps: next(batches) alone (synthetic generator or MNIST
                    loader — whatever the CLI would use), no device work.
  - cli_examples_ps: the full CLI run (bench.py's bench_mlp), for
                    reference against the decomposition.

If bare >> feed ≈ cli, the cost is per-step host->device transfer;
if ping_ms * steps accounts for the gap, it is pure
dispatch RTT; if loader is slow, it is the data path. The conclusion
belongs in BENCH_NOTES.md.

Usage: python experiments/mlp_probe.py [--steps 60] [--batch 256]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--cpu", action="store_true",
                    help="CPU-backend smoke of the harness itself")
    args = ap.parse_args()
    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")
    from nezha_tpu.utils import enable_persistent_compile_cache
    enable_persistent_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from nezha_tpu import data, ops, optim
    from nezha_tpu.models.mlp import MLP
    from nezha_tpu.train.loop import init_train_state, make_train_step

    out = lambda **kw: print(json.dumps(kw), flush=True)

    # 1. Dispatch round-trip: trivial op, host fetch each call.
    x = jnp.zeros((), jnp.float32)
    add = jax.jit(lambda v: v + 1.0)
    add(x).block_until_ready()  # compile
    t0 = time.perf_counter()
    n_ping = 30
    for _ in range(n_ping):
        x = add(x)
        x.block_until_ready()
    out(metric="ping_ms", value=round((time.perf_counter() - t0) / n_ping
                                      * 1e3, 3))

    # Mirror the CLI's mlp_mnist config exactly (model/opt/loss/data).
    model = MLP()
    opt = optim.momentum(0.1)
    ce = lambda logits, b: ops.softmax_cross_entropy_with_integer_labels(
        logits, b["label"]).mean()
    state = init_train_state(model, opt, jax.random.PRNGKey(0))
    step = make_train_step(model, opt, ce)

    batches = data.mnist_batches(args.batch)
    host = next(batches)
    dev = {k: jnp.asarray(v) for k, v in host.items()}

    # 2. Bare step: device-resident batch, async dispatch, one final sync.
    # The step donates its state, so `s` threads through every loop below
    # (old handles are dead after each call).
    s, m = step(state, dev)
    jax.block_until_ready(m)  # compile + settle
    t0 = time.perf_counter()
    for _ in range(args.steps):
        s, m = step(s, dev)
    jax.block_until_ready(m)
    bare = args.steps / (time.perf_counter() - t0)
    out(metric="bare_steps_ps", value=round(bare, 2),
        examples_ps=round(bare * args.batch, 1))

    # 3. Fed step: fresh host batch transferred every step (Trainer
    #    pattern), async dispatch, one final sync.
    t0 = time.perf_counter()
    for _ in range(args.steps):
        fresh = {k: jnp.asarray(v) for k, v in host.items()}
        s, m = step(s, fresh)
    jax.block_until_ready(m)
    fed = args.steps / (time.perf_counter() - t0)
    out(metric="feed_steps_ps", value=round(fed, 2),
        examples_ps=round(fed * args.batch, 1))

    # 4. Loader alone (the same batches the CLI config would feed).
    t0 = time.perf_counter()
    for _ in range(args.steps):
        next(batches)
    out(metric="loader_batches_ps",
        value=round(args.steps / (time.perf_counter() - t0), 2))

    # 5. Full CLI for reference (bench.py's own config-1 path).
    from bench import bench_mlp
    on_tpu = jax.default_backend() == "tpu"
    out(metric="cli_examples_ps", value=round(bench_mlp(on_tpu), 1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
