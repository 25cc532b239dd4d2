"""Sampling's top-k head ALONE at the six serving cells' shapes (run on the
chip).

What PERF.md's "selection alone" readings are made with: the ``k``
largest values of ``[rows, vocab]`` float32 rows divided by a temperature
of 0.8, as ``filter_logits_and_flag`` asks for them, timed as ``--reps``
chained calls inside one jitted ``lax.scan`` (the temperature is the
carry, so no call can be hoisted out of the loop), by each of these forms:

  native          ``lax.top_k(x, k)[0]``: the one TopK over the whole row
  two-stage       ``serve.sampling.top_k_values``: the form the step runs
  contig-W        contiguous tiles of W entries viewed as ``[rows, T, W]``,
                  the chosen ones gathered as tiles, the remainder past
                  the last whole tile appended
  strided-W       tile j = entries j, j + T, j + 2T, ... (``[rows, W, T]``)
  window-W        the maxima by ``reduce_window`` (the last tile partial),
                  the chosen tiles' entries gathered one by one from the
                  row, ``-inf`` past its end
  window12-W      only window-W's maxima and their top-k indices
  filter-<form>   the whole ``filter_logits_and_flag`` around that head

Every form's head is compared BITWISE with the native one on five kinds of
rows (normal, bf16-rounded, rounded to halves: thousands of ties, all
``-inf`` but ten entries, all equal); a form that differs anywhere prints
``"equal": false``. One JSON line a reading.

  gpt2     256 x 50,257      kimi     256 x 40,960
  kx       128 x 19,200      falcon   256 x 32,640
  mistral  128 x 32,768      xing4     32 x 131,072

Usage, on one TPU chip: python3 experiments/topk_alone.py \
           [--shape gpt2 ...] [--forms native,window-16,...] \
           [--filter-forms native,two-stage] [--reps 50]
"""

import argparse
import json
import os
import sys
import time

SHAPES = {"gpt2": (256, 50257), "kimi": (256, 40960), "kx": (128, 19200),
          "falcon": (256, 32640), "mistral": (128, 32768),
          "xing4": (32, 131072)}


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--shape", action="append", choices=sorted(SHAPES))
    ap.add_argument("--forms", default="native,two-stage,contig-16,"
                    "contig-32,strided-32,window-16,window-32",
                    help="comma-separated forms (see above; native first)")
    ap.add_argument("--filter-forms", default="native,two-stage",
                    help="forms timed inside the whole filter")
    ap.add_argument("--k", type=int, default=64)
    ap.add_argument("--reps", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), help="checkout to import nezha_tpu from")
    ap.add_argument("--cpu", action="store_true",
                    help="small shapes on the CPU (a smoke of this script, "
                         "no timing worth reading)")
    args = ap.parse_args()
    sys.path.insert(0, args.root)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    elif jax.default_backend() != "tpu":
        sys.exit("no TPU: a time comes from a chip run (--cpu smokes the "
                 "script)")
    from nezha_tpu.serve import sampling

    k = args.k
    two_stage = sampling.top_k_values

    def contig(x, w):
        b, v = x.shape
        t = v // w
        tiles = x[:, :t * w].reshape(b, t, w)
        chosen = lax.top_k(tiles.max(axis=-1), k)[1]
        cand = jnp.take_along_axis(tiles, chosen[:, :, None], axis=1)
        cand = jnp.concatenate([cand.reshape(b, k * w), x[:, t * w:]], 1)
        return lax.top_k(cand, k)[0]

    def strided(x, w):
        b, v = x.shape
        t = -(-v // w)
        x3 = jnp.pad(x, ((0, 0), (0, w * t - v)),
                     constant_values=-jnp.inf).reshape(b, w, t)
        chosen = lax.top_k(x3.max(axis=1), k)[1]
        cand = jnp.take_along_axis(x3, chosen[:, None, :], axis=2)
        return lax.top_k(cand.reshape(b, w * k), k)[0]

    def window_chosen(x, w):
        maxima = lax.reduce_window(x, -jnp.inf, lax.max, (1, w), (1, w),
                                   ((0, 0), (0, -x.shape[1] % w)))
        return lax.top_k(maxima, k)[1]

    def window(x, w):
        b = x.shape[0]
        cols = (window_chosen(x, w) * w)[:, :, None] + jnp.arange(w)
        cand = jnp.take_along_axis(x, cols.reshape(b, k * w), axis=1,
                                   mode="fill", fill_value=-jnp.inf)
        return lax.top_k(cand, k)[0]

    def window12(x, w):
        return window_chosen(x, w).astype(x.dtype)

    kinds_of_form = {"contig": contig, "strided": strided, "window": window,
                     "window12": window12}

    def head_form(name):
        """name -> (fn of the rows and their ``[B, 1]`` temperature, tile
        width or 1)."""
        if name == "native":
            return (lambda x, d: lax.top_k(x / d, k)[0]), 1
        if name == "two-stage":
            return (lambda x, d: two_stage(x, k, d)), 1
        kind, w = name.rsplit("-", 1)
        fn = kinds_of_form[kind]
        return (lambda x, d: fn(x / d, int(w))), int(w)

    def filtered(head_fn):
        def fn(x, d):
            b = x.shape[0]
            saved = sampling.top_k_values
            sampling.top_k_values = lambda s, kk, dd: head_fn(s, dd)
            try:
                out, flag = sampling.filter_logits_and_flag(
                    x, d[:, 0], jnp.full((b,), 40, jnp.int32),
                    jnp.ones((b,), x.dtype), k)
            finally:
                sampling.top_k_values = saved
            return out[:, :k] + 0 * flag
        return fn

    forms = [(name,) + head_form(name) for name in args.forms.split(",")]
    for name in args.filter_forms.split(","):
        fn, w = head_form(name)
        forms.append(("filter-" + name, filtered(fn), w))

    device = jax.devices()[0].device_kind
    for shape in args.shape or list(SHAPES):
        b, v = SHAPES[shape]
        if args.cpu:
            b, v = 8, v // 4
        rng = np.random.default_rng(args.seed)
        normal = rng.standard_normal((b, v)).astype(np.float32) * 3
        sparse = np.full((b, v), -np.inf, np.float32)
        for r in range(b):
            sparse[r, rng.choice(v, 10, replace=False)] = rng.standard_normal(
                10)
        kinds = {
            "normal": normal,
            "bf16": np.asarray(jnp.asarray(normal, jnp.bfloat16).astype(
                jnp.float32)),
            "halves": np.round(normal * 2) / 2,
            "sparse": sparse,
            "equal": np.full((b, v), 0.75, np.float32),
        }
        kinds = {n: jnp.asarray(a) for n, a in kinds.items()}
        temp = jnp.full((b,), 0.8, jnp.float32)     # the mixes' temperature
        w_rule = sampling._tile_width(v, k)
        line = {"shape": shape, "device": device, "rows": b, "vocab": v,
                "k": k, "rule_width": w_rule,
                "candidates": sampling.topk_candidates(v, k)}

        def chained(fn):
            @jax.jit
            def chain(x, t0):
                def one(carry, _):
                    t, _ = carry
                    out = fn(x, t[:, None])
                    return (t + 0 * out[:, 0].astype(t.dtype), out), None
                init = (t0, jnp.zeros((b, k), x.dtype))
                return lax.scan(one, init, None, length=args.reps)[0]
            return chain

        want = {}
        for name, fn, w in forms:
            if v // w < k:              # fewer tiles than values
                continue
            chain = chained(fn)
            t0 = time.perf_counter()
            outs = {n: np.asarray(chain(x, temp)[1]) for n, x in
                    kinds.items()}
            first_s = time.perf_counter() - t0
            if name == "native":
                want = outs
            head = want and not name.startswith(("window12", "filter"))
            equal = {n: bool(np.array_equal(outs[n].view(np.uint32),
                                            want[n].view(np.uint32)))
                     for n in kinds} if head else {}
            best = float("inf")
            x = kinds["normal"]
            for _ in range(5):
                t0 = time.perf_counter()
                jax.block_until_ready(chain(x, temp))
                best = min(best, time.perf_counter() - t0)
            ms = best / args.reps * 1e3
            print(json.dumps({
                **line, "form": name, "ms_a_call": ms,
                "row_read_share_of_hbm": b * v * 4 / 819e9 / (ms * 1e-3),
                "equal": all(equal.values()) if equal else None,
                "unequal_kinds": [n for n, e in equal.items() if not e],
                "first_calls_s": first_s}), flush=True)


if __name__ == "__main__":
    main()
