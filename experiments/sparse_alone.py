"""The two sparse kernels ALONE at the MiniCPM-SALA cell's widths (run on
the chip), checked against their ``jax.numpy`` twins and timed.

One ``sparse_score`` call and one ``sparse_decode_paged`` call at the
shapes the cell's step program gives them (2 K/V heads of 128, 32 query
heads, blocks of 64, float32 compressed keys ``[N, 8, 128]``, a table of
1,024 entries a row, ``--rows`` rows whose visible lengths are drawn in
``[--shortest, --longest]``; a few rows inactive), over a permuted table of
a pool of ``--blocks`` blocks. The selection between them is the program's
own (``block_scores``, ``select_mask``, ``select_list``). Each call is
timed as ``--reps`` chained calls inside one jitted ``lax.scan``; the
results are checked against the twins on every row (the decode's output to
2e-2 of its largest value, bf16 operands; the scores to 1e-4). Prints one
JSON line.

Usage (on a TPU host): python3 experiments/sparse_alone.py
       (a CPU smoke)   python3 experiments/sparse_alone.py --cpu --rows 4 \
                           --blocks 700 --shortest 500 --longest 12000
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--rows", type=int, default=192)
    p.add_argument("--blocks", type=int, default=65536)
    p.add_argument("--shortest", type=int, default=1024)
    p.add_argument("--longest", type=int, default=32768)
    p.add_argument("--reps", type=int, default=20)
    p.add_argument("--cpu", action="store_true",
                   help="interpret mode on the CPU (a smoke, no timing)")
    args = p.parse_args(argv)
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from nezha_tpu.ops.pallas import sparse_attention as sp

    z = sp.SparseSizes()
    b, h, kvh, d, m = args.rows, 32, 2, 128, 1024
    rng = np.random.default_rng(0)
    lengths = rng.integers(args.shortest, args.longest + 1, b)
    lengths[::7] = 0                                # inactive rows
    need = int(sum(-(-n // 64) for n in lengths))
    if need >= args.blocks:
        raise SystemExit(f"{need} blocks needed, --blocks {args.blocks}")
    perm = 1 + rng.permutation(args.blocks - 1)
    tab = np.zeros((b, m), np.int32)
    at = 0
    for i, n in enumerate(lengths):
        k = -(-int(n) // 64)
        tab[i, :k] = perm[at:at + k]
        at += k
    key = jax.random.PRNGKey(1)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    kp = jax.random.normal(k1, (args.blocks, 64, kvh * d), jnp.bfloat16)
    vp = jax.random.normal(k2, (args.blocks, 64, kvh * d), jnp.bfloat16)
    ck = jax.random.normal(k3, (args.blocks, 8, d), jnp.float32) * 0.2
    q = jax.random.normal(k4, (b, h, d), jnp.bfloat16)
    tab, lens = jnp.asarray(tab), jnp.asarray(lengths, jnp.int32)
    scale = d ** -0.5
    interp = True if args.cpu else None

    s, fresh = sp.sparse_score(q, ck, kp, tab, lens, scale, z,
                               interpret=interp)
    s_ref, fresh_ref = sp.sparse_score_reference(q, ck, kp, tab, lens,
                                                 scale, z)
    n_c = sp.n_complete(lens, z)
    mask = sp.select_mask(sp.block_scores(s_ref.transpose(0, 2, 1),
                                          n_c[:, None], z), lens[:, None], z)
    ids, keys = sp.select_list(mask, lens, z, z.list_len(m))
    o = sp.sparse_decode_paged(q, kp, vp, tab, ids, keys, scale,
                               interpret=interp)
    o_ref = sp.sparse_decode_reference(q, kp, vp, tab, ids, keys, scale)
    out = {
        "device": str(jax.devices()[0].device_kind),
        "rows": b, "blocks_selected": int(mask.sum()),
        "blocks_held": int(sum(-(-n // 64) for n in lengths)) * kvh,
        "score_max_diff": float(jnp.abs(s - s_ref).max()),
        "fresh_max_diff": float(jnp.abs(fresh - fresh_ref).max()),
        "decode_max_rel_diff": float(
            jnp.abs(o.astype(jnp.float32) - o_ref).max()
            / jnp.abs(o_ref).max()),
        "inactive_rows_zero": bool((o[lengths == 0] == 0).all())}
    out["ok"] = (out["score_max_diff"] < 1e-4 and out["fresh_max_diff"] < 1e-3
                 and out["decode_max_rel_diff"] < 2e-2
                 and out["inactive_rows_zero"])
    if not args.cpu:
        # the pools are ARGUMENTS of the timed program: closed over, they
        # would be baked into it as constants (GBs on the host)
        def chained(step):
            def body(carry, _):
                return step(carry), None
            return lambda qq: jax.lax.scan(body, qq, None,
                                           length=args.reps)[0]

        score_fn = jax.jit(lambda qq, ck_, kp_, tab_, lens_: chained(
            lambda c: c + sp.sparse_score(c, ck_, kp_, tab_, lens_, scale,
                                          z)[0][:, :h, :1].astype(c.dtype)
            * 0)(qq))
        decode_fn = jax.jit(lambda qq, kp_, vp_, tab_, ids_, keys_: chained(
            lambda c: sp.sparse_decode_paged(c, kp_, vp_, tab_, ids_, keys_,
                                             scale))(qq))
        for name, fn, rest in (("score", score_fn, (ck, kp, tab, lens)),
                               ("decode", decode_fn,
                                (kp, vp, tab, ids, keys))):
            fn(q, *rest).block_until_ready()
            t0 = time.perf_counter()
            fn(q, *rest).block_until_ready()
            out[f"{name}_ms_a_call"] = (time.perf_counter() - t0) * 1e3 \
                / args.reps
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
