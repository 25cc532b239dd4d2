"""The paged decode kernel ALONE at a serving cell's shapes (run on the chip).

What PERF.md's "a call, kernel alone" readings are made with: one
``flash_decode_attention(..., block_tables=...)`` call at the shapes a
cell's step program gives it, over a permuted table, timed as ``--reps``
chained calls inside one jitted ``lax.scan`` (so that no host dispatch sits
between two calls) and checked on 32 rows against
``paged_attention_composed`` in float32.

  gpt2       256 rows, pools bf16[16385,16,768], table 64, lengths uniform
             in [150, 650] (~25 live entries a row, ~6,500 a call)
  kx-global  128 rows, 64 query heads over pools bf16[16385,64,1024],
             table 128, lengths uniform in [1200, 6100]
  kx-ring    the same rows over a ring of 3 entries, window 128

``--entries 4,8,16`` times the call at each value of the module's
``_ENTRIES_PER_STEP``; ``--empty`` adds the same call with every length 0
(what the iteration space costs when it moves nothing); ``--root DIR``
imports ``nezha_tpu`` from another checkout (the parent commit unpacked in a
git-ignored directory), one process a checkout, to read both on one machine.
Prints one JSON line a reading.

Usage: chiprun --chips 1 -- python3 experiments/paged_decode_alone.py \
           --shape gpt2 --entries 4,8,16 --empty
"""

import argparse
import functools
import json
import os
import sys
import time

SHAPES = {
    # rows, query heads, head dim, K/V heads, block, pool blocks, table,
    # window, (shortest, longest) row
    "gpt2": (256, 12, 64, 12, 16, 16385, 64, None, (150, 650)),
    "kx-global": (128, 64, 128, 8, 64, 16385, 128, None, (1200, 6100)),
    "kx-ring": (128, 64, 128, 8, 64, 385, 3, 128, (1200, 6100)),
}


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--shape", choices=sorted(SHAPES), default="gpt2")
    ap.add_argument("--entries", default="",
                    help="comma-separated values of _ENTRIES_PER_STEP "
                         "(default: the module's own)")
    ap.add_argument("--empty", action="store_true",
                    help="also time the call with every row empty")
    ap.add_argument("--reps", type=int, default=50)
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

    from nezha_tpu.ops.pallas import decode_attention as da

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    elif jax.default_backend() != "tpu":
        sys.exit("no TPU: a kernel's time comes from a chip run (--cpu "
                 "smokes the script)")
    b, h, d, kvh, bs, n, m, window, (lo, hi) = SHAPES[args.shape]
    if args.cpu:
        b, n, args.reps = 8, 8 * m + 1, 2
    rng = np.random.default_rng(args.seed)
    kk, kv, kq = jax.random.split(jax.random.PRNGKey(args.seed), 3)
    k = jax.random.normal(kk, (n, bs, kvh * d), jnp.bfloat16)
    v = jax.random.normal(kv, (n, bs, kvh * d), jnp.bfloat16)
    q = jax.random.normal(kq, (b, h, 1, d), jnp.bfloat16)
    # every row's entries are its own blocks, in no order
    tab = jnp.asarray(1 + rng.permutation(n - 1)[:b * m].reshape(b, m)
                      if n - 1 >= b * m else
                      1 + rng.integers(0, n - 1, size=(b, m)), jnp.int32)
    lens = rng.integers(lo, hi + 1, size=b).astype(np.int32)
    if not window:
        lens = np.minimum(lens, m * bs)
    live = int(np.minimum(-(-lens // bs), m).sum())

    def call(q_, k_, v_, lens_, tab_):
        return da.flash_decode_attention(q_, k_, v_, lens_,
                                         block_tables=tab_, window=window)

    def ms_a_call(lens_):
        @jax.jit                    # traced anew for each value of c
        def chain(q_, k_, v_, lens_, tab_):
            def one(carry, _):
                out = call(carry, k_, v_, lens_, tab_)
                return carry + (0 * out).astype(carry.dtype), None
            return lax.scan(one, q_, None, length=args.reps)[0]

        lens_ = jnp.asarray(lens_)
        chain(q, k, v, lens_, tab).block_until_ready()
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            chain(q, k, v, lens_, tab).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        return best / args.reps * 1e3

    for c in [int(x) for x in args.entries.split(",") if x] or [None]:
        if c:
            da._ENTRIES_PER_STEP = c
            jax.clear_caches()      # _paged_call is jitted: c is read at trace
        got = np.asarray(jax.jit(call)(q, k, v, jnp.asarray(lens), tab)[:32],
                         np.float32)
        want = np.asarray(jax.jit(functools.partial(
            da.paged_attention_composed, window=window))(
                q[:32].astype(jnp.float32), k, v, jnp.asarray(lens[:32]),
                tab[:32]), np.float32)
        line = {"shape": args.shape, "root": args.root,
                "device": jax.devices()[0].device_kind,
                "entries_per_step": da._ENTRIES_PER_STEP, "rows": b,
                "live_entries": live,
                "max_abs_err_32_rows": float(np.abs(got - want).max()),
                "ms_a_call": ms_a_call(lens)}
        if args.empty:
            line["ms_a_call_all_rows_empty"] = ms_a_call(np.zeros_like(lens))
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
