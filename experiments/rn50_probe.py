"""RN50 perf probe + tuning matrix (run on the real chip).

Round-3 landed two structural fixes proven equivalent by test but never
measured on hardware: the space-to-depth stem and
compute-dtype BatchNorm. Round 4 adds the next levers from the r3 roofline
(BENCH_NOTES.md: 51 GB/step HLO bytes-accessed — bandwidth-heavy): buffer
donation on the train state and batch 256. This script measures them all.

Default mode prints one JSON line per variant (median-of-3 windows):

  baseline   conv7 stem, B=128, donated state (the r2 bench geometry)
  s2d        space-to-depth stem (r3 fix #1; expected ~3.5 ms of the 5 ms
             stem per the r3 utilization probe)
  no_donate  donation off (costs a full param+opt-state copy per step if
             XLA can't reuse; quantifies what donation buys)
  b256       s2d + batch 256 (amortizes fixed costs; bigger MXU tiles)
  remat      per-bottleneck jax.checkpoint (trade saved-activation HBM
             reads for recompute FLOPs — wins iff bandwidth-bound)

``--probe`` runs the r3 breakdown instead (fwd / fwd+bwd / stem-alone /
XLA cost analysis) for roofline arithmetic.

Usage: python experiments/rn50_probe.py [--steps 10] [--variants s2d ...]
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

PEAK_FLOPS = 197e12  # v5e bf16


IMAGE_SIZE = 224  # overridable via --image-size for CPU smoke runs


def _build(stem: str, batch: int, donate: bool,
           remat: bool = False):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from nezha_tpu import ops, optim
    from nezha_tpu.models.resnet import resnet50
    from nezha_tpu.tensor import bf16_policy
    from nezha_tpu.train.loop import init_train_state, make_train_step

    model = resnet50(stem=stem, remat=remat, policy=bf16_policy())
    opt = optim.momentum(0.1, beta=0.9, weight_decay=1e-4)
    state = init_train_state(model, opt, jax.random.PRNGKey(0))
    ce = lambda logits, b_: ops.softmax_cross_entropy_with_integer_labels(
        logits, b_["label"]).mean()
    step = make_train_step(model, opt, ce, donate=donate)
    rng = np.random.RandomState(0)
    sz = IMAGE_SIZE
    b = {"image": jnp.asarray(rng.rand(batch, sz, sz, 3).astype(np.float32)),
         "label": jnp.asarray(rng.randint(0, 1000, batch), jnp.int32)}
    return step, state, b


def measure(variant: dict, steps: int) -> dict:
    batch = variant.get("batch", 128)
    step, state, b = _build(variant.get("stem", "conv7"), batch,
                            variant.get("donate", True),
                            variant.get("remat", False))
    # ONE AOT compile serves both the timing loop and the cost analysis
    # (a second compile per geometry would double chip time and hold a
    # duplicate state in HBM alongside the donated one — b256 could OOM).
    compiled = step.lower(state, b).compile()
    flops = None
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        flops = cost.get("flops") or None
    except Exception:
        pass
    # bench.py's timing discipline (median-of-5 windows, host-fetch
    # barriers; state threads through, so donation stays legal).
    from bench import _time_steps
    sps, spread = _time_steps(compiled, state, b, steps, 90.0)
    return {"variant": variant["name"], "batch": batch,
            "images_per_sec": round(batch * sps, 1),
            "mfu": round(flops * sps / PEAK_FLOPS, 4) if flops else None,
            "spread": round(spread, 4)}


VARIANTS = [
    {"name": "baseline", "stem": "conv7"},
    {"name": "s2d", "stem": "s2d"},
    {"name": "no_donate", "stem": "s2d", "donate": False},
    {"name": "b256", "stem": "s2d", "batch": 256},
    # r5 bandwidth hypothesis: recompute each bottleneck in backward
    # instead of reading saved intermediates — if the step is truly bound
    # on saved-activation traffic (51 GB/step HLO vs 19.8 GB analytic
    # floor), remat should WIN despite +~30% conv FLOPs.
    {"name": "remat", "stem": "s2d", "remat": True},
    {"name": "remat_b256", "stem": "s2d", "remat": True, "batch": 256},
]


def probe() -> None:
    """The r3 breakdown: where does the step go? (roofline inputs)"""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from nezha_tpu import nn, ops
    from nezha_tpu.models.resnet import resnet50
    from nezha_tpu.tensor import bf16_policy

    B = 128
    step, state, b = _build("conv7", B, donate=False)
    model = resnet50(policy=bf16_policy())
    ce = lambda logits, b_: ops.softmax_cross_entropy_with_integer_labels(
        logits, b_["label"]).mean()

    def timeit(fn, *args, n=10, fetch=None):
        out = fn(*args)
        if fetch:
            fetch(out)
        t0 = time.perf_counter()
        for _ in range(n):
            out = fn(*args)
        if fetch:
            fetch(out)
        return (time.perf_counter() - t0) / n, out

    compiled = jax.jit(step).lower(state, b).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    print("XLA flops/step:", cost.get("flops"),
          " bytes:", cost.get("bytes accessed"))
    dt, _ = timeit(lambda: compiled(state, b), n=10,
                   fetch=lambda o: float(o[1]["loss"]))
    print(f"full step: {dt*1e3:.2f} ms -> {B/dt:.0f} img/s "
          f"MFU(XLA)={cost.get('flops', 0)/dt/PEAK_FLOPS:.3f}")

    fwd = jax.jit(lambda v, bb: model.apply(v, bb, training=True)[0].sum()
                  ).lower(state["variables"], b).compile()
    dt_f, _ = timeit(lambda: fwd(state["variables"], b), n=10,
                     fetch=float)
    print(f"fwd only: {dt_f*1e3:.2f} ms")

    def loss_fn(params, variables, bb):
        v = dict(variables)
        v["params"] = params
        logits, _ = model.apply(v, bb, training=True)
        return ce(logits, bb)

    g = jax.jit(jax.grad(loss_fn)).lower(
        state["variables"]["params"], state["variables"], b).compile()
    dt_g, _ = timeit(
        lambda: g(state["variables"]["params"], state["variables"], b),
        n=10, fetch=lambda o: float(jax.tree_util.tree_leaves(o)[0].sum()))
    print(f"fwd+bwd: {dt_g*1e3:.2f} ms (optimizer+rest: "
          f"{(dt - dt_g)*1e3:.2f} ms)")

    stem = nn.Conv2d(3, 64, 7, stride=2, use_bias=False,
                     policy=bf16_policy())
    sv = stem.init(jax.random.PRNGKey(1))

    def stem_loss(p, x):
        v = dict(sv)
        v["params"] = p
        y, _ = stem.apply(v, x)
        return jnp.sum(jnp.asarray(y, jnp.float32))

    gs = jax.jit(jax.grad(stem_loss)).lower(sv["params"], b["image"]
                                            ).compile()
    dt_s, _ = timeit(
        lambda: gs(sv["params"], b["image"]), n=20,
        fetch=lambda o: float(jax.tree_util.tree_leaves(o)[0].sum()))
    print(f"stem conv fwd+bwd: {dt_s*1e3:.2f} ms")


def stages(batch: int = 128) -> None:
    """Per-stage fwd+bwd time AND HLO bytes-accessed (default B=128, s2d).

    The r3/r4 whole-step numbers say "bandwidth-bound somewhere"; this
    ranks the four bottleneck stages + stem + head so the traffic work
    aims at the hungriest stage instead of the whole network.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from nezha_tpu import nn, ops
    from nezha_tpu.models.resnet import resnet50
    from nezha_tpu.nn.module import run_child
    from nezha_tpu.tensor import bf16_policy

    B, size = batch, IMAGE_SIZE
    model = resnet50(stem="s2d", policy=bf16_policy())
    variables = model.init(jax.random.PRNGKey(0))

    sizes, idx, groups = (3, 4, 6, 3), 0, []
    for n in sizes:
        groups.append(list(range(idx, idx + n)))
        idx += n
    s4 = size // 4
    in_shapes = [(B, s4, s4, 64), (B, s4, s4, 256),
                 (B, s4 // 2, s4 // 2, 512), (B, s4 // 4, s4 // 4, 1024)]

    def timed_grad(f, *args, n=10):
        """compile f's grad (wrt all args), time it, report ms + HLO GB."""
        g = jax.jit(jax.grad(f, argnums=tuple(range(len(args)))))
        compiled = g.lower(*args).compile()
        gb = None
        try:
            cost = compiled.cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0]
            gb = cost.get("bytes accessed", 0) / 1e9
        except Exception:
            pass
        out = compiled(*args)
        float(jax.tree_util.tree_leaves(out)[0].sum())
        t0 = time.perf_counter()
        for _ in range(n):
            out = compiled(*args)
        float(jax.tree_util.tree_leaves(out)[0].sum())
        return (time.perf_counter() - t0) / n * 1e3, gb

    rng = np.random.RandomState(0)
    img = jnp.asarray(rng.rand(B, size, size, 3).astype(np.float32))

    def stem_f(params, x):
        v = {"params": params, "state": variables["state"]}
        states: dict = {}
        from nezha_tpu.models.resnet import _space_to_depth_stem
        pol = model.stem_conv.policy
        y = _space_to_depth_stem(pol.cast_to_compute(x),
                                 pol.cast_to_compute(params["stem_conv"]["w"]))
        y = run_child(model.stem_bn, "stem_bn", v, states, y, training=True)
        y = jnp.maximum(y, 0)
        return jnp.sum(jnp.asarray(nn.max_pool(y, 3, 2, "SAME"), jnp.float32))

    ms, gb = timed_grad(stem_f, variables["params"], img)
    print(f"stem(s2d)+bn+pool : {ms:7.2f} ms  {gb and f'{gb:6.1f} GB'}")

    for s, g in enumerate(groups):
        x = jnp.asarray(rng.rand(*in_shapes[s]).astype(np.float32),
                        jnp.bfloat16)

        def stage_f(params, xin, _g=tuple(g)):
            v = {"params": params, "state": variables["state"]}
            states: dict = {}
            out = xin
            for i in _g:
                out = run_child(model.blocks[i], f"blocks{i}", v, states,
                                out, training=True)
            return jnp.sum(jnp.asarray(out, jnp.float32))

        ms, gb = timed_grad(stage_f, variables["params"], x)
        print(f"stage{s + 1} ({len(g)} blocks) : {ms:7.2f} ms  "
              f"{gb and f'{gb:6.1f} GB'}")

    xh = jnp.asarray(
        rng.rand(B, s4 // 8, s4 // 8, 2048).astype(np.float32),
        jnp.bfloat16)
    lbl = jnp.asarray(rng.randint(0, 1000, B), jnp.int32)

    def head_f(params, xin):
        v = {"params": params, "state": variables["state"]}
        states: dict = {}
        pooled = nn.global_avg_pool(xin)
        logits = run_child(model.head, "head", v, states, pooled,
                           training=True)
        return ops.softmax_cross_entropy_with_integer_labels(
            jnp.asarray(logits, jnp.float32), lbl).mean()

    ms, gb = timed_grad(head_f, variables["params"], xh)
    print(f"pool+head+CE      : {ms:7.2f} ms  {gb and f'{gb:6.1f} GB'}")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--probe", action="store_true",
                    help="run the step-breakdown probe instead of the "
                         "variant matrix")
    ap.add_argument("--stages", action="store_true",
                    help="per-stage fwd+bwd time + HLO bytes (traffic "
                         "ranking)")
    ap.add_argument("--variants", nargs="+", default=None,
                    choices=[v["name"] for v in VARIANTS])
    ap.add_argument("--image-size", type=int, default=224,
                    help="input size (shrink for CPU smoke runs)")
    ap.add_argument("--base-batch", type=int, default=None,
                    help="override every variant's batch (CPU smoke)")
    ap.add_argument("--cpu", action="store_true",
                    help="pin the CPU backend (harness smoke; numbers "
                         "are meaningless)")
    args = ap.parse_args()
    if args.cpu:
        import jax
        jax.config.update("jax_platforms", "cpu")
    from nezha_tpu.utils import enable_persistent_compile_cache
    enable_persistent_compile_cache()
    global IMAGE_SIZE
    IMAGE_SIZE = args.image_size
    if args.base_batch:
        for v in VARIANTS:
            v["batch"] = args.base_batch
    if args.probe:
        probe()
        return 0
    if args.stages:
        stages(batch=args.base_batch or 128)
        return 0
    for v in VARIANTS:
        if args.variants and v["name"] not in args.variants:
            continue
        print(json.dumps(measure(v, args.steps)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
