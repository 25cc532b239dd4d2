"""The routed experts' kernel ALONE at the serving cells' shapes (run on the chip).

What PERF.md's "a layer call, kernel alone" readings are made with: one
``moe_experts`` call (``nezha_tpu/ops/pallas/moe_experts.py``) at the pair
rows, group sizes and stacked weights a cell's step (``--rows decode``) or
1,024-token prefill program (``--rows chunk``) gives it, beside the three
``jax.lax.ragged_dot`` calls it replaced, each timed as ``--reps`` chained
calls inside one jitted ``lax.scan`` and compared on the rows before
``sum(sizes)``.

  mistral  32 of 128 experts held, top-4, d 4096, d_ff 2048; 128 / 1,024 tokens
  kx       16 of 128, top-8, d 6144, d_ff 2048; 128 / 1,024 tokens
  kimi     64 of 256, top-8, d 2304, d_ff 1024; 256 / 1,024 tokens

Every token draws ``top_k`` distinct experts of all ``num_experts`` with
weights ``rank ** -skew`` over a shuffled ranking (``--skew 0``: uniform);
the held ones' counts are ``sizes``. ``--tiles tm,tf,window`` (repeatable)
times the kernel at other tile sizes than ``tile_sizes`` gives. Prints one
JSON line a reading, with the bytes the touched experts' weights hold and
the share of the chip's HBM bandwidth (``chipbench/trace/peaks.json``) the
call reaches on them.

Usage: chiprun --chips 1 -- python3 experiments/moe_experts_alone.py \
           --shape mistral --rows decode --rows chunk
"""

import argparse
import importlib
import json
import os
import sys
import time

SHAPES = {
    # held, num_experts, top_k, d, d_ff, decode tokens, chunk tokens
    "mistral": (32, 128, 4, 4096, 2048, 128, 1024),
    "kx": (16, 128, 8, 6144, 2048, 128, 1024),
    "kimi": (64, 256, 8, 2304, 1024, 256, 1024),
}


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--shape", action="append", choices=sorted(SHAPES))
    ap.add_argument("--rows", action="append", choices=["decode", "chunk"])
    ap.add_argument("--tiles", action="append", default=[],
                    help="tm,tf,window in place of tile_sizes()")
    ap.add_argument("--skew", type=float, default=0.0)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), help="checkout to import nezha_tpu from")
    ap.add_argument("--cpu", action="store_true",
                    help="tiny shapes through the interpreter (a smoke of "
                         "this script, no timing worth reading)")
    args = ap.parse_args()
    sys.path.insert(0, args.root)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    # the package exports a function of the module's own name
    me = importlib.import_module("nezha_tpu.ops.pallas.moe_experts")

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    elif jax.default_backend() != "tpu":
        sys.exit("no TPU: a kernel's time comes from a chip run (--cpu "
                 "smokes the script)")
    bf16 = jnp.bfloat16
    kind = jax.devices()[0].device_kind
    with open(os.path.join(args.root, "chipbench", "trace",
                           "peaks.json")) as f:       # the benchmark's table
        kinds = json.load(f)["device_kinds"]
    # a chip the table lacks is an error; the CPU smoke borrows the v5e's
    hbm_bytes_per_s = kinds["TPU v5 lite" if args.cpu else kind][
        "hbm_bytes_per_s"]

    def ms_a_call(fn, xs, sizes, weights):
        live = (jnp.arange(xs.shape[0]) < sizes.sum())[:, None]

        @jax.jit
        def chain(xs_, sizes_, *w):
            def one(carry, _):
                out = jnp.where(live, fn(carry, sizes_, *w), 0.0)
                return carry + (0 * out).astype(carry.dtype), None
            return lax.scan(one, xs_, None, length=args.reps)[0]

        chain(xs, sizes, *weights).block_until_ready()
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            chain(xs, sizes, *weights).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        return best / args.reps * 1e3

    for shape in args.shape or sorted(SHAPES):
        held, experts, top_k, d, d_ff, decode, chunk = SHAPES[shape]
        if args.cpu:
            d, d_ff, args.reps = 128, 256, 2
        rng = np.random.default_rng(args.seed)
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 4)
        weights = [jax.random.normal(k, s, bf16) * 0.02 for k, s in zip(
            keys[:3], [(held, d, d_ff), (held, d, d_ff), (held, d_ff, d)])]
        p = np.arange(1, experts + 1, dtype=np.float64) ** -args.skew
        p = rng.permutation(p / p.sum())
        for rows_kind in args.rows or ["decode", "chunk"]:
            tokens = decode if rows_kind == "decode" else chunk
            if args.cpu:
                tokens //= 8
            ids = np.stack([rng.choice(experts, top_k, replace=False, p=p)
                            for _ in range(tokens)])
            sizes = jnp.asarray(np.bincount(
                ids[ids < held], minlength=held), jnp.int32)
            rows, n = tokens * top_k, int(sizes.sum())
            xs = jax.random.normal(keys[3], (rows, d), bf16)
            want = np.asarray(jax.jit(me.moe_experts_reference)(
                xs, sizes, *weights)[:n])
            touched = int((np.asarray(sizes) > 0).sum())
            nbytes = touched * 3 * d * d_ff * 2 + n * d * (2 + 4)
            floor_ms = nbytes / hbm_bytes_per_s * 1e3
            line = {"shape": shape, "rows": rows_kind, "root": args.root,
                    "device": kind,
                    "pair_rows": rows, "held_rows": n, "touched": touched,
                    "largest_group": int(sizes.max()),
                    "bytes": nbytes, "floor_ms": floor_ms}
            ref_ms = ms_a_call(me.moe_experts_reference, xs, sizes, weights)
            print(json.dumps({**line, "impl": "ragged_dot x3",
                              "ms_a_call": ref_ms,
                              "roofline": floor_ms / ref_ms}), flush=True)
            for tiles in [None] + [tuple(int(x) for x in t.split(","))
                                   for t in args.tiles]:
                tm, tf, window = tiles or me.tile_sizes(
                    -(-rows // 16) * 16, d, d_ff, 2)
                try:
                    out, stats = me.moe_experts(xs, sizes, *weights,
                                                tiles=tiles)
                    ms = ms_a_call(
                        lambda *a, _t=tiles: me.moe_experts(*a, tiles=_t)[0],
                        xs, sizes, weights)
                except Exception as e:      # tiles the compiler refuses
                    print(json.dumps({**line, "tm": tm, "tf": tf,
                                      "window": window,
                                      "refused": str(e)[:300]}), flush=True)
                    continue
                print(json.dumps({
                    **line, "impl": "nezha_moe_experts", "tm": tm, "tf": tf,
                    "window": window, "visits": int(stats[0]),
                    "max_abs_err": float(np.abs(
                        np.asarray(out[:n]) - want).max()) if n else 0.0,
                    "max_abs_ref": float(np.abs(want).max()) if n else 0.0,
                    "ms_a_call": ms, "roofline": floor_ms / ms}), flush=True)


if __name__ == "__main__":
    main()
