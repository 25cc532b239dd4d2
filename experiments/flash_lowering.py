"""What the causal flash kernels cost to LOWER, compile-only, for a described
v5e (no chip needed).

A kernel body is traced and lowered once a layer on every process start,
before the compile cache is consulted, so a body that grows with its block's
sub-tile count costs set-up on every run. This lowers the forward (with its
lse residual), the backward (dq and dK/dV) and the whole gradient at
``[B, H, S, D]`` in bf16, each ``--reps`` times with JAX's caches cleared
between (as a new process would), and prints one JSON line a case: the best
``lower()`` seconds, the lowered module's size in characters and the size of
each ``tpu_custom_call``'s payload (the kernel's serialized body).
``--compile`` adds one compile of each. ``--root DIR`` imports ``nezha_tpu``
from another checkout (the parent commit unpacked in a git-ignored
directory), to read both under one installation.

Usage: JAX_PLATFORMS=cpu python experiments/flash_lowering.py --seq 512,1024
"""

import argparse
import json
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")


def payload_sizes(text):
    """Sizes of the ``backend_config`` payloads of a module's
    ``tpu_custom_call``s, in the order they appear."""
    return [len(m.group(1)) for m in re.finditer(
        r'@tpu_custom_call\([^)]*\) \{backend_config = "((?:[^"\\]|\\.)*)"',
        text)]


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seq", default="1024")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--heads", type=int, default=12)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--causal", type=int, default=1)
    ap.add_argument("--compile", action="store_true")
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), help="checkout to import nezha_tpu from")
    args = ap.parse_args()
    sys.path.insert(0, args.root)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import importlib

    # The package exports the function under the module's name.
    fa = importlib.import_module("nezha_tpu.ops.pallas.flash_attention")

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    causal = bool(args.causal)

    for s in (int(x) for x in args.seq.split(",")):
        shape = (args.batch, args.heads, s, args.dim)
        x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one)
        lse = jax.ShapeDtypeStruct(shape[:3], jnp.float32, sharding=one)
        cases = {
            "fwd": (lambda q, k, v: fa.flash_block_fwd(
                q, k, v, causal, interpret=False), (x, x, x)),
            "bwd": (lambda q, k, v, o, l, g: fa.flash_block_bwd(
                q, k, v, o, l, g, causal, interpret=False),
                (x, x, x, x, lse, x)),
            "fwd+bwd": (jax.grad(lambda q, k, v: fa.flash_attention(
                q, k, v, causal, interpret=False).astype(
                    jnp.float32).sum(), argnums=(0, 1, 2)), (x, x, x)),
        }
        for name, (fn, shapes) in cases.items():
            best = None
            for _ in range(args.reps):
                jax.clear_caches()
                t0 = time.perf_counter()
                lowered = jax.jit(fn).lower(*shapes)
                dt = time.perf_counter() - t0
                best = dt if best is None else min(best, dt)
            text = lowered.as_text()
            line = {"root": args.root, "seq": s, "case": name,
                    "causal": causal, "lower_s": round(best, 4),
                    "module_chars": len(text),
                    "payload_chars": payload_sizes(text)}
            if args.compile:
                t0 = time.perf_counter()
                lowered.compile()
                line["compile_s"] = round(time.perf_counter() - t0, 3)
            print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
