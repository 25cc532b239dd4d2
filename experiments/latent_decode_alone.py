"""The paged decode kernel's LATENT form ALONE at the two cells that run it
(run on the chip).

What PERF.md's "a call, kernel alone" readings of ``latent_decode_attention``
are made with: one call at the shapes a cell's step program gives it, over a
permuted table, the rows' lengths those of the cell's own stationary fill
(``chipbench.traffic.stationary_fill`` over the mix's file: requests caught
mid-life), timed as ``--reps`` chained calls inside one jitted ``lax.scan``
and checked on 32 rows against ``latent_attention_composed`` in float32.

  mistral  128 rows, 32 heads, rows of 384 lanes (values 256, latent 320),
           pool bf16[8193,64,384], table 64, mix batch-gen-4k
  kimi     256 rows, 32 heads, rows of 640 lanes (values 512, latent 576),
           pool bf16[65537,64,640], table 256, mix reason-gen-16k

``--entries 4,8,16`` times the call at each value of the module's
``_ENTRIES_PER_STEP``; ``--composed`` adds the composed view of the whole
table (where the gathered view fits beside the pool); ``--root DIR`` imports
``nezha_tpu`` and ``chipbench`` from another checkout. Prints one JSON line
a reading: ms a call and its share of the chip's HBM bandwidth
(``chipbench/trace/peaks.json``) on the LIVE bytes (the rows' bound entries
as stored, ``W`` lanes a token, with the q and o rows: what a kernel that
copies whole rows must move) and on the WORK's bytes (``latent`` values a
token, what the benchmark's rooflines count), beside the bytes the loop
copies (a row's last iteration repeats its last entry).

Usage: chiprun --chips 1 -- python3 experiments/latent_decode_alone.py \
           --shape mistral --shape kimi --entries 4,8,16 --composed
"""

import argparse
import json
import os
import sys
import time

SHAPES = {
    # rows, heads, row lanes W, value lanes, latent values, block, table,
    # the cell's mix, its vocabulary held
    "mistral": (128, 32, 384, 256, 320, 64, 64, "batch-gen-4k", 32768),
    "kimi": (256, 32, 640, 512, 576, 64, 256, "reason-gen-16k", 40960),
}


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--shape", action="append", choices=sorted(SHAPES))
    ap.add_argument("--entries", default="",
                    help="comma-separated values of _ENTRIES_PER_STEP "
                         "(default: the module's own)")
    ap.add_argument("--composed", action="store_true",
                    help="also time latent_attention_composed")
    ap.add_argument("--reps", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), help="checkout to import from")
    ap.add_argument("--cpu", action="store_true",
                    help="tiny shapes through the interpreter (a smoke of "
                         "this script, no timing worth reading)")
    args = ap.parse_args()
    sys.path.insert(0, args.root)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from chipbench.traffic import stationary_fill
    from nezha_tpu.ops.pallas import decode_attention as da

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    elif jax.default_backend() != "tpu":
        sys.exit("no TPU: a kernel's time comes from a chip run (--cpu "
                 "smokes the script)")
    kind = jax.devices()[0].device_kind
    with open(os.path.join(args.root, "chipbench", "trace",
                           "peaks.json")) as f:       # the benchmark's table
        kinds = json.load(f)["device_kinds"]
    # a chip the table lacks is an error; the CPU smoke borrows the v5e's
    peak = kinds["TPU v5 lite" if args.cpu else kind]
    hbm_bytes_per_s = peak["hbm_bytes_per_s"]

    for shape in args.shape or sorted(SHAPES):
        b, h, w, r, latent, bs, m, mix, vocab = SHAPES[shape]
        with open(os.path.join(args.root, "chipbench", "traffic",
                               mix + ".json")) as f:
            traffic = json.load(f)
        # a row's length going into a step: its resident tokens and the one
        # the step writes
        lens = np.asarray([len(req.prompt) + 1 for req in stationary_fill(
            traffic, args.seed, vocab, b)], np.int32)
        if args.cpu:
            b, bs, m, args.reps = 8, 8, m // 2, 2
            lens = np.minimum(lens[:b] // 16, m * bs)
        n = 1 + b * m
        rng = np.random.default_rng(args.seed)
        kp, kq = jax.random.split(jax.random.PRNGKey(args.seed))
        pool = jax.random.normal(kp, (n, bs, w), jnp.bfloat16)
        q = jax.random.normal(kq, (b, h, w), jnp.bfloat16)
        scale = float(latent) ** -0.5
        # every row's entries are its own blocks, in no order
        tab = jnp.asarray(1 + rng.permutation(n - 1).reshape(b, m), jnp.int32)
        lens = np.minimum(lens, m * bs)
        entries = -(-lens // bs)
        qo_bytes = b * h * (w + r) * 2
        live_bytes = int(entries.sum()) * bs * w * 2 + qo_bytes
        work_bytes = int(lens.sum()) * latent * 2 + qo_bytes
        line = {"shape": shape, "root": args.root, "device": kind,
                "rows": b, "resident_tokens": int(lens.sum()),
                "median_tokens_a_row": float(np.median(lens)),
                "live_entries": int(entries.sum()),
                "live_bytes": live_bytes, "work_bytes": work_bytes}

        def ms_a_call(fn, lens_):
            @jax.jit                    # traced anew for each value of c
            def chain(q_, pool_, lens_, tab_):
                def one(carry, _):
                    out = fn(carry, pool_, lens_, tab_, r, scale)
                    return carry + (0 * out[:, :, 0, :1]).astype(
                        carry.dtype), None
                return lax.scan(one, q_, None, length=args.reps)[0]

            lens_ = jnp.asarray(lens_)
            chain(q, pool, lens_, tab).block_until_ready()
            best = float("inf")
            for _ in range(5):
                t0 = time.perf_counter()
                chain(q, pool, lens_, tab).block_until_ready()
                best = min(best, time.perf_counter() - t0)
            return best / args.reps * 1e3

        def reading(impl, ms, **more):
            print(json.dumps({
                **line, "impl": impl, **more, "ms_a_call": ms,
                "share_of_hbm_on_live_bytes":
                    live_bytes / hbm_bytes_per_s / (ms * 1e-3),
                "share_of_hbm_on_work_bytes":
                    work_bytes / hbm_bytes_per_s / (ms * 1e-3)}), flush=True)

        want = np.asarray(jax.jit(da.latent_attention_composed,
                                  static_argnums=(4, 5))(
            q[:32].astype(jnp.float32), pool, jnp.asarray(lens[:32]),
            tab[:32], r, scale), np.float32)
        view_bytes = b * m * bs * w * 2
        if args.composed and view_bytes + n * bs * w * 2 > 0.7 * peak[
                "hbm_bytes"]:
            print(json.dumps({**line, "impl": "composed view",
                              "skipped": f"the gathered view is "
                              f"{view_bytes / 1e9:.2f} GB beside the pool"}),
                  flush=True)
        elif args.composed:
            reading("composed view",
                    ms_a_call(da.latent_attention_composed, lens))
        for c in [int(x) for x in args.entries.split(",") if x] or [None]:
            if c:
                da._ENTRIES_PER_STEP = c
                jax.clear_caches()      # _latent_call is jitted: c is read
                #                         at trace
            c_now = da._pick_block(m, da._ENTRIES_PER_STEP)
            got = np.asarray(jax.jit(
                da.latent_decode_attention, static_argnums=(4, 5))(
                    q[:32], pool, jnp.asarray(lens[:32]), tab[:32], r, scale),
                np.float32)
            copies = int((-(-lens // (c_now * bs)) * c_now).sum())
            reading("latent kernel",
                    ms_a_call(da.latent_decode_attention, lens),
                    entries_per_step=c_now, copied_entries=copies,
                    copied_bytes=copies * bs * w * 2,
                    max_abs_err_32_rows=float(np.abs(got - want).max()),
                    max_abs_ref_32_rows=float(np.abs(want).max()),
                    ms_a_call_all_rows_empty=ms_a_call(
                        da.latent_decode_attention, np.zeros_like(lens)))


if __name__ == "__main__":
    main()
