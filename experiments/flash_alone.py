"""The three flash kernels ALONE at the train cell's shape (run on the chip).

What PERF.md's "kernel alone" readings of ``nezha_flash_fwd``,
``nezha_flash_bwd_dq`` and ``nezha_flash_bwd_dkv`` are made with: each
kernel jitted on its own (the forward with its lse residual, as training
runs it; the backward's two calls each with the other's outputs unused, so
the compiler drops it) at ``[B, H, S, D]`` bf16, ``--reps`` calls
dispatched back to back and timed to the last one's completion, the median
of ``--rounds`` such rounds (``*_ms``: the call, with the XLA ops around the
kernel), and the kernel's own device time a call from a profiler trace of
one more round (``*_kernel_ms``: the ``nezha_flash`` ops alone). Before
timing, one call of each is checked against composed float32 attention at
a batch of one (the compiled kernel, not the interpreter). ``--root DIR``
imports ``nezha_tpu`` from another checkout (the parent commit unpacked in
a git-ignored directory), one process a checkout, to read both on one
machine. Prints one JSON line.

Usage, on a machine with the chip (the parent unpacked in export_check/):
    python3 experiments/flash_alone.py --root export_check/parent
    python3 experiments/flash_alone.py
"""

import argparse
import importlib
import json
import os
import statistics
import sys
import tempfile
import time


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--shape", default="16,12,1024,64")
    ap.add_argument("--causal", type=int, default=1)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), help="checkout to import nezha_tpu from")
    ap.add_argument("--cpu", action="store_true",
                    help="tiny shapes through the interpreter (a smoke of "
                         "this script, no timing worth reading)")
    args = ap.parse_args()
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, args.root)
    sys.path.append(here)                   # chipbench, for the trace

    import jax
    import jax.numpy as jnp
    import numpy as np

    # The package exports the function under the module's name.
    fa = importlib.import_module("nezha_tpu.ops.pallas.flash_attention")

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    elif jax.default_backend() != "tpu":
        sys.exit("no TPU: a kernel's time comes from a chip run (--cpu "
                 "smokes the script)")
    b, h, s, d = (int(x) for x in args.shape.split(","))
    if args.cpu:
        b, h, s, args.reps, args.rounds = 1, 2, 256, 1, 1
    causal = bool(args.causal)
    interpret = True if args.cpu else None

    fwd = jax.jit(lambda q, k, v: fa.flash_block_fwd(
        q, k, v, causal, interpret=interpret))

    def bwd(q, k, v, o, lse, do):
        return fa.flash_block_bwd(q, k, v, o, lse, do, causal,
                                  interpret=interpret)

    dq = jax.jit(lambda *a: bwd(*a)[0])
    dkv = jax.jit(lambda *a: bwd(*a)[1:])

    def inputs(batch, seed):
        keys = jax.random.split(jax.random.PRNGKey(seed), 4)
        q, k, v, do = (jax.random.normal(kk, (batch, h, s, d), jnp.bfloat16)
                       for kk in keys)
        return q, k, v, do

    def reference(q, k, v, do):
        q32, k32, v32, do32 = (x.astype(jnp.float32) for x in (q, k, v, do))

        def attn(q, k, v):
            sc = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
            if causal:
                sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
            return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(sc, -1), v)

        out, vjp = jax.vjp(attn, q32, k32, v32)
        return (out, *vjp(do32))

    def check():
        q, k, v, do = inputs(1, args.seed + 1)
        o, lse = fwd(q, k, v)
        got = (o, dq(q, k, v, o, lse, do), *dkv(q, k, v, o, lse, do))
        want = reference(q, k, v, do)
        return {n: float(jnp.max(jnp.abs(g.astype(jnp.float32) - w))
                         / (float(jnp.max(jnp.abs(w))) or 1.0))
                for n, g, w in zip(("out", "dq", "dk", "dv"), got, want)}

    def timed(fn, *a):
        jax.block_until_ready(fn(*a))             # compile + warm
        ms = []
        for _ in range(args.rounds):
            t0 = time.perf_counter()
            for _ in range(args.reps):
                r = fn(*a)
            jax.block_until_ready(r)
            ms.append((time.perf_counter() - t0) / args.reps * 1e3)
        return statistics.median(ms)

    def kernel_ms(fn, *a):
        from chipbench.trace import reduce

        trace_dir = tempfile.mkdtemp()
        with jax.profiler.trace(trace_dir):
            for _ in range(args.reps):
                r = fn(*a)
            jax.block_until_ready(r)
        secs, _ = reduce.matching_seconds(
            reduce.load(reduce.find_xplane(trace_dir)), [r"nezha_flash"])
        return secs / args.reps * 1e3

    err = check()
    q, k, v, do = inputs(b, args.seed)
    o, lse = fwd(q, k, v)
    line = {"root": args.root, "shape": [b, h, s, d], "causal": causal,
            "fwd_ms": timed(fwd, q, k, v),
            "dq_ms": timed(dq, q, k, v, o, lse, do),
            "dkv_ms": timed(dkv, q, k, v, o, lse, do),
            "fwd_kernel_ms": kernel_ms(fwd, q, k, v),
            "dq_kernel_ms": kernel_ms(dq, q, k, v, o, lse, do),
            "dkv_kernel_ms": kernel_ms(dkv, q, k, v, o, lse, do),
            "rel_err": err,
            "device": jax.devices()[0].device_kind}
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
