"""The two ``nezha_mhc`` kernels ALONE on the chip, beside the composed form,
and a short forward pass of Xing4.0 at the published widths against the
float32 reference (by hand; ``--cpu`` smokes the script at tiny widths):

    chiprun --chips 1 -- python3 experiments/mhc_alone.py

Prints one JSON line a reading: the largest difference of ``u``, the maps and
the new streams between kernel and composed form at a decode step's 32 rows
and a chunk's 1,024 tokens, each call's time (median of 20, after a warm-up),
and for a model of ``--layers`` layers (the first ones: dense, dense, sparse
...) the largest logit difference of the kernel path and of the composed path
from ``chipbench/reference/xing4.py`` over ``--tokens`` tokens, in bf16 ulps
of the largest reference logit.

``--by-layer --forms`` tells apart the two changes that cured the first chip
run's fault (PERF.md section 6, PR 36): it runs the block comparison with
the scalars read from an SMEM operand again (``smem``), with each stream
stored before the next is summed again (``stored``), with both
(``smem+stored``: the first form) and with the kernels as they are.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _first_forms(mhc):
    """-> (the kernels as they are, by the module attribute each form
    replaces; the first form's ``_pre_call``; the first form's
    ``_post_kernel``)."""
    import jax
    import jax.numpy as jnp
    import functools

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    sound = {"_pre_call": mhc._pre_call, "_post_kernel": mhc._post_kernel}

    def post_stored(x_ref, y_ref, maps_ref, o_ref, *, n):
        """The first form: stream ``i`` is stored before stream ``i + 1``
        is summed, so an output that IS its input feeds the next sum."""
        c = y_ref.shape[1]
        maps = maps_ref[...]
        for i in range(n):
            acc = maps[:, n + i:n + i + 1] * y_ref[...]
            for j in range(n):
                k = 2 * n + i * n + j
                acc = acc + maps[:, k:k + 1] * x_ref[:, j * c:(j + 1) * c]
            o_ref[:, i * c:(i + 1) * c] = acc

    def pre_kernel_smem(x_ref, phi_ref, ab_smem, u_ref, maps_ref, rows_scr,
                        ab_scr, **kw):
        """The first form's operand: ``a`` and ``b`` as SMEM scalars,
        ``[2, maps]``, laid out here as the kernel's VMEM operand."""
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
        for k in range(ab_scr.shape[0]):
            ab_scr[k:k + 1, :] = jnp.where(
                lane == 0, ab_smem[0, k],
                jnp.where(lane == 1, ab_smem[1, k], 0.0))
        mhc._pre_kernel(x_ref, phi_ref, ab_scr, u_ref, maps_ref, rows_scr,
                        **kw)

    @functools.partial(jax.jit, static_argnames=(
        "n", "iters", "eps", "clamp", "norm_eps", "interpret"))
    def pre_call_smem(x, phi, alpha, b, *, n, iters, eps, clamp, norm_eps,
                      interpret):
        t, nc = x.shape
        c = nc // n
        tm, t_pad = mhc._tiles(t)
        f32 = jnp.float32
        ab = jnp.stack([mhc.map_scales(alpha, n), b.astype(f32)])
        tile = lambda w: pl.BlockSpec((tm, w), lambda i: (i, 0))  # noqa: E731
        u, maps = pl.pallas_call(
            functools.partial(pre_kernel_smem, n=n, iters=iters, eps=eps,
                              clamp=clamp, norm_eps=norm_eps),
            grid=(t_pad // tm,),
            in_specs=[tile(nc), pl.BlockSpec(phi.shape, lambda i: (0, 0)),
                      pl.BlockSpec(memory_space=pltpu.SMEM)],
            out_specs=[tile(c), tile(mhc.MAP_LANES)],
            out_shape=[jax.ShapeDtypeStruct((t_pad, c), f32),
                       jax.ShapeDtypeStruct((t_pad, mhc.MAP_LANES), f32)],
            scratch_shapes=[pltpu.VMEM((mhc.MAP_LANES, tm), f32),
                            pltpu.VMEM((n * (n + 2), 128), f32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",),
                vmem_limit_bytes=mhc._VMEM_LIMIT),
            interpret=interpret, name="nezha_mhc_pre",
        )(mhc._pad_rows(x.astype(f32), t_pad), phi.astype(f32), ab)
        return u[:t], maps[:t]

    return sound, pre_call_smem, post_stored


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--cpu", action="store_true")
    p.add_argument("--layers", type=int, default=3)
    p.add_argument("--tokens", type=int, default=256)
    p.add_argument("--vocab", type=int, default=16384)
    p.add_argument("--by-layer", action="store_true",
                   help="also: each block's streams, kernel against "
                        "composed, on the composed path's own input")
    p.add_argument("--forms", action="store_true",
                   help="with --by-layer: the kernels' first forms too, "
                        "each of the two changes reverted alone")
    p.add_argument("--variants", default="",
                   help="with --by-layer: 'tile:vmem_mib,...' to run it "
                        "again at other token tiles and VMEM limits")
    args = p.parse_args(argv)
    import jax
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from chipbench import manifest
    from chipbench.reference import xing4 as ref
    from nezha_tpu.models.xing4 import xing4
    from nezha_tpu.nn.hyper_connections import (HyperConnection,
                                                mhc_post_composed,
                                                mhc_pre_composed)
    from nezha_tpu.ops.pallas.mhc import mhc_post, mhc_pre

    say = lambda **kw: print(json.dumps(kw), flush=True)   # noqa: E731
    say(device=str(jax.devices()[0]))
    width = 64 if args.cpu else 3584
    hc = HyperConnection(width, 4)
    prm = hc.init(jax.random.PRNGKey(0))["params"]
    kw = hc.static_args()

    def timed(fn, *a):
        out = jax.block_until_ready(fn(*a))
        laps = []
        for _ in range(20):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*a))
            laps.append(time.perf_counter() - t0)
        return out, float(np.median(laps)) * 1e3

    for tokens in () if args.forms else (32, 1024):
        x = jax.random.normal(jax.random.PRNGKey(tokens), (tokens, 4 * width))
        y = jax.random.normal(jax.random.PRNGKey(1), (tokens, width))
        pre_c = jax.jit(lambda x: mhc_pre_composed(
            x, prm["phi"], prm["alpha"], prm["b"], **kw))
        pre_k = jax.jit(lambda x: mhc_pre(
            x, prm["phi"], prm["alpha"], prm["b"], **kw))
        (u0, m0), ms_c = timed(pre_c, x)
        (u1, m1), ms_k = timed(pre_k, x)
        post_c = jax.jit(lambda x, y, m: mhc_post_composed(x, y, m, n=4))
        post_k = jax.jit(lambda x, y, m: mhc_post(x, y, m, n=4))
        o0, ms_pc = timed(post_c, x, y, m0)
        o1, ms_pk = timed(post_k, x, y, m0)
        say(tokens=tokens, u_diff=float(jnp.abs(u0 - u1).max()),
            maps_diff=float(jnp.abs(m0 - m1).max()),
            post_diff=float(jnp.abs(o0 - o1).max()),
            pre_ms={"composed": ms_c, "kernel": ms_k},
            post_ms={"composed": ms_pc, "kernel": ms_pk})

    if args.cpu:
        cfg = None
        model_kw = dict(preset="tiny")
    else:
        cfg = manifest.load_cell("xing4.0-29b.long-prompt-16k")["config"]
        model_kw = dict(preset="full", num_hidden_layers=args.layers,
                        vocab_held=args.vocab)
    toks = jax.random.randint(jax.random.PRNGKey(5), (1, args.tokens), 0,
                              512 if args.cpu else args.vocab)
    variables = xing4(**model_kw).init(jax.random.PRNGKey(3))
    for impl in () if args.forms else ("xla", "kernel"):
        model = xing4(decode_impl=impl, **model_kw)
        got, states = jax.jit(lambda v, t: model.apply(v, t))(variables, toks)
        if cfg is None:
            sys.path.insert(0, os.path.join(manifest.REPO, "tests"))
            from test_serve_xing4 import ref_cfg
            cfg = ref_cfg(model.cfg)
        want = ref.logits_at(variables["params"], toks,
                             jnp.arange(args.tokens)[None], cfg)
        ulp = 2.0 ** -8 * max(1.0, float(jnp.abs(want).max()))
        per_row = np.asarray(jnp.abs(got - want).max(-1))[0] / ulp
        say(impl=impl, max_ref_logit=float(jnp.abs(want).max()),
            row_ulps_median=float(np.median(per_row)),
            row_ulps_max=float(per_row.max()),
            residual=float(model.mhc_residual(states)))
        if impl == "xla":
            first = got
        else:
            say(kernel_against_composed_ulps=float(
                jnp.abs(got - first).max()) / ulp)
    from nezha_tpu.nn.module import child_vars
    from nezha_tpu.ops.pallas import mhc

    composed = {}       # (tokens, layer) -> the composed block's streams

    def by_layer(toks=toks, pre_alone=True):
        """Each block's new streams, kernel path against composed path, on
        the composed path's own input; and each sublayer's ``pre`` alone
        (``pre_alone``: outside any compiled block)."""
        models = {impl: xing4(decode_impl=impl, **model_kw)
                  for impl in ("xla", "kernel")}
        e = models["xla"].embed.apply(
            child_vars(variables, "embed"), toks)[0].astype(jnp.float32)
        x = jnp.concatenate([e] * 4, axis=-1)
        for i in range(len(models["xla"].h)):
            v = child_vars(variables, f"h{i}")
            outs = {}
            for impl, m in models.items():
                blk = m.h[i]
                key = (toks.shape[1], i)
                if impl == "kernel" or key not in composed:
                    outs[impl] = jax.jit(
                        lambda v, x, blk=blk: blk.apply(v, x)[0])(v, x)
                    if impl == "xla":
                        composed[key] = outs[impl]
                outs["xla"] = composed[key]
                for name in ("hc_attn", "hc_mlp") if pre_alone else ():
                    outs[impl, name] = getattr(blk, name).pre(
                        child_vars(v, name), x)
            diff = jnp.abs(outs["xla"] - outs["kernel"])[0]
            say(layer=i,
                streams_rms=float(jnp.sqrt((outs["xla"] ** 2).mean())),
                block_diff=float(diff.max()),
                by_128_tokens=[float(t.max()) for t in diff.reshape(
                    -1, min(128, toks.shape[1]), diff.shape[-1])],
                **{f"{name}_pre_diff": [
                    float(jnp.abs(a - b).max()) for a, b in zip(
                        outs["xla", name], outs["kernel", name])]
                   for name in ("hc_attn", "hc_mlp") if pre_alone})
            x = outs["xla"]

    sound, pre_call_smem, post_stored = _first_forms(mhc)
    forms = {"smem": {"_pre_call": pre_call_smem},
             "stored": {"_post_kernel": post_stored},
             "smem+stored": {"_pre_call": pre_call_smem,
                             "_post_kernel": post_stored},
             "as_it_is": {}}
    for form, patch in forms.items() if args.by_layer and args.forms else ():
        for name, fn in {**sound, **patch}.items():
            setattr(mhc, name, fn)
        jax.clear_caches()
        for n_tok in (32, args.tokens):     # a step's rows, a chunk's tokens
            say(form=form, tokens=n_tok)
            by_layer(toks[:, :n_tok], pre_alone=False)
    for name, fn in sound.items():
        setattr(mhc, name, fn)

    variants = [(mhc.TOKEN_TILE, mhc._VMEM_LIMIT // 2 ** 20)] + [
        tuple(int(n) for n in v.split(":"))
        for v in args.variants.split(",") if v]
    for tile, mib in variants if args.by_layer and not args.forms else ():
        mhc.TOKEN_TILE, mhc._VMEM_LIMIT = tile, mib * 2 ** 20
        jax.clear_caches()      # a jitted call reads them when it is traced
        say(token_tile=tile, vmem_limit_mib=mib)
        by_layer()
    return 0


if __name__ == "__main__":
    sys.exit(main())
