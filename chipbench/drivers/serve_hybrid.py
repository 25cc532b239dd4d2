"""Serving cells of a model whose layers keep two kinds of cache (window
layers in a ring of blocks beside global layers in the growing table) and
route their experts by sigmoid scores: ``drivers/serve_lm.py``'s run with
this model's sizes, limits and per-step counters.

``serve_lm.ServeLMRun`` reads Mistral-Small-4's latent-attention keys
(``sizes_of``) and holds that model's two limits as module constants, so
this driver brings its own ``sizes_of`` (what ``trace/kernel_costs_exaone``
needs) and its own copy of the comparison with its own limits; the stack,
the traffic, the window, the expert-load counters and the ``ANNOTATIONS``
(the program's ``serve.*`` spans among them) are the parent classes'.

One thing is added around ``Engine.step``: beside the resident tokens
(each active row's context), the tokens INSIDE THE WINDOW (the sum of
``min(context, sliding_window)``), which is what a window layer's decode
attention must read.

**The comparison that decides ``correct``** is ``serve_lm``'s rule
(requests of the cell's own mix through the same scheduler in set-up,
logits after prefill and after the first 3 decode steps, teacher-forced,
within ``LOGIT_TOL_ULPS`` bf16 ulps of the largest reference logit on rows
whose routing is clear: the reference's router margin, here on the
selection score ``sigmoid(logit) + bias``, at least
``ROUTER_MARGIN_ULPS``). Beside the mix's own requests it takes prompts cut
or stretched to the lengths of ``edge_prompt_lengths``: the few decode
steps it compares then do what the cell's long decodes do all the time
(bind a block, go round the ring) and the mix's fresh prompts almost never
do in their first three steps; the result counts the compared rows of each
kind (``rows_compared_by_case``).

By hand, the control that places ``LOGIT_TOL_ULPS``: the same comparison
with the reference's activations rounded to fp8, which must read
``ok: false``::

    chiprun -- python3 -m chipbench.drivers.serve_hybrid \
        --workload k-exaone-236b.reason-gen-8k --seed <n> --control fp8
"""

from __future__ import annotations

import importlib

import numpy as np

from chipbench import traffic as traffic_lib
from chipbench.drivers import serve as base
from chipbench.drivers import serve_lm
from chipbench.drivers.serve import CHECK_STEPS, clock

ANNOTATIONS = serve_lm.ANNOTATIONS

# Both limits are set from readings on the chip at the published widths
# (PERF.md section 6, PR 30, has the numbers): what the program gives over
# its seeds, and what the reference gives when its activations are rounded
# to fp8 (the nearest precision below the bf16 the configuration states),
# which must come out as not correct.
# Logits: bf16 ulps (2**-8) of the largest reference logit. Rows on which
# program and reference chose the same experts read at most 2.1-2.85 ulps
# over 29 seeds (five layers; Mistral's six leave 5-6; the edge prompts'
# rows no more than the mix's: 2.44 against 2.80); with fp8 activations
# every row reads 21-48 (the control below, at these limits: 21.5-48.4).
LOGIT_TOL_ULPS = 8
# Router margin, in ulps of 2**-8 on the selection score sigmoid(logit) +
# bias: over 29 seeds (4,048 rows) the program chose other experts than
# the reference on 273 rows, 99% of them at margins under 1.0 and the
# largest at 1.32; the limit leaves that reading 2.3 times of room and
# 21-39 of a run's 128 rows of the mix's own requests to compare (41-51
# of 176 with the edge prompts).
ROUTER_MARGIN_ULPS = 3.0
CHECK_REQUESTS = 32     # of the mix's own
EDGE_EACH = 5           # edge prompts a kind inside the mix's own lengths
MIN_COMPARED_ROWS = 4


def edge_prompt_lengths(block: int, ring: int, lo: int, hi: int,
                        max_len: int) -> dict:
    """-> {kind: [prompt lengths]}. After a prompt of ``m * block - 1``
    tokens the first decode step writes the last row of a block and the
    second one the first row of a block bound during decode:
    ``ring_wrap`` where that block is the ring's entry 0 again, over the
    slot's oldest one (``m % ring == 0``), ``block_boundary`` where it is
    not. ``EDGE_EACH`` of each kind spread over the mix's own prompt
    lengths ``lo..hi``, and ``long_context``: the last length of each kind
    that leaves room for the decode steps under ``max_len`` (the growing
    table's last entries, a prefill at the largest offsets: the stationary
    fill admits such rows mid-life, the mix's fresh prompts stop at
    ``hi``)."""
    def lengths(first, last, wrap):
        return [m * block - 1 for m in range(1, last // block + 2)
                if first <= m * block - 1 <= last
                and (m % ring == 0) == wrap]

    def spread(ns):
        if len(ns) <= EDGE_EACH:
            return ns
        return sorted({ns[round(i * (len(ns) - 1) / (EDGE_EACH - 1))]
                       for i in range(EDGE_EACH)})

    far = max_len - CHECK_STEPS - 1
    return {"ring_wrap": spread(lengths(lo, hi, True)),
            "block_boundary": spread(lengths(lo, hi, False)),
            "long_context": (lengths(hi + 1, far, True)[-1:]
                             + lengths(hi + 1, far, False)[-1:])}


def row_cases(n: int, rows: int, block: int, ring: int, beyond: int) -> list:
    """What each of a request's ``rows`` compared logit rows covers (row 0
    follows the prefill of ``n`` tokens, row ``r`` the decode step whose
    query and newest key sit at position ``n - 1 + r``): a set of
    ``block_boundary`` (that key lies in a block bound after the prefill),
    ``ring_wrap`` (and that block took the ring's entry 0 again) and
    ``long_context`` (a position past ``beyond``, the furthest the mix's
    own check requests reach)."""
    out = []
    for r in range(rows):
        at = (n - 1 + r) // block
        cases = set()
        if at > (n - 1) // block:
            cases.add("block_boundary")
            if at % ring == 0:
                cases.add("ring_wrap")
        if n - 1 + r > beyond:
            cases.add("long_context")
        out.append(cases)
    return out


def sizes_of(config: dict) -> dict:
    """What the cost functions need, from the configuration file's own
    (published) keys and its statement of the chip's share."""
    layers = config["num_hidden_layers"]
    kinds = config["layer_types"][:layers]
    dense = config["first_k_dense_replace"]
    return {
        "hidden": config["hidden_size"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"], "window": config["sliding_window"],
        "dense_width": config["intermediate_size"],
        "expert_width": config["moe_intermediate_size"],
        "experts_routed": config["published"]["num_experts"],
        "experts_held": config["experts_held"][1],
        "top_k": config["num_experts_per_tok"],
        "vocab_held": config["vocab_size"], "layers": layers,
        "dense_layers": dense, "sparse_layers": layers - dense,
        "global_layers": kinds.count("full_attention"),
        "window_layers": kinds.count("sliding_attention")}


class ServeHybridRun(serve_lm.ServeLMRun):
    ref_act_dtype = None    # the control alone rounds the reference

    def build(self):
        base.ServeRun.build(self)
        self.obs.model = sizes_of(self.cell["config"])

    def _step(self, active):
        out = super()._step(active)
        t1 = clock()
        if t1 < self.win[0]:
            return out
        obs = self.obs
        context = self.engine.host_positions[active]
        windowed = int(np.minimum(context, obs.model["window"]).sum())
        if not hasattr(obs, "hybrid_steps"):
            obs.hybrid_steps = []   # (time, rows, resident, inside window)
        obs.hybrid_steps.append((t1, int(np.count_nonzero(active)),
                                 int(context.sum()), windowed))
        if self._sampling(t1):
            obs.count("lm_window_tokens", windowed)
        return out

    def check_against_reference(self) -> dict:
        import jax.numpy as jnp

        config = self.cell["config"]
        ref = importlib.import_module(
            f"chipbench.reference.{config['serve']['reference']}")
        stream = traffic_lib.request_stream(self.traffic, self.seed + 7_919,
                                            self.vocab)
        ecfg, unique = self.engine.cfg, self.traffic["prompt"]["unique"]
        block, ring = ecfg.kv_block_size, self.engine.pool.window_entries
        edges = edge_prompt_lengths(block, ring, unique["min"], unique["max"],
                                    ecfg.max_len)
        rng = np.random.default_rng([self.seed, 0xED6E])
        recs = []
        for i, n in enumerate([None] * CHECK_REQUESTS
                              + [n for ns in edges.values() for n in ns]):
            req = next(stream)
            req.index = -2000 - i
            req.max_new_tokens = min(req.max_new_tokens, CHECK_STEPS + 1)
            if n is not None:       # an edge prompt: cut or stretched to n
                req.prompt = (req.prompt + rng.integers(
                    0, self.vocab, max(0, n - len(req.prompt))).tolist())[:n]
                req.max_new_tokens = CHECK_STEPS + 1
            recs.append(self._submit(req, clock(), check=True))
        self.sched.run_until_idle()
        self.slot_rec.clear()

        # two shapes for every seed: the mix's longest prompt plus the
        # decoded tokens, in whole 512-row blocks, and the slot's whole
        # length for the prompts of ``long_context``
        longest = unique["max"] + CHECK_STEPS
        pad_short = min(-(-longest // 512) * 512, ecfg.max_len)
        params = self.engine.variables["params"]
        cases = []
        got_rows, ref_rows, margins = [], [], []
        for rec in recs:
            chk = rec.check
            req, toks = chk["req"], chk["tokens"]
            k = len(chk["logits"])          # 1 prefill + decode steps
            if k < 2 or len(toks) < k - 1:
                return {"ok": False, "why": f"request {rec.rid} captured "
                        f"{k} logit rows, {len(toks)} tokens"}
            n = len(req.prompt)
            pad_to = pad_short if n + CHECK_STEPS <= pad_short \
                else ecfg.max_len
            cases += row_cases(n, k, block, ring, longest - 1)
            seq = np.zeros((1, pad_to), np.int32)
            seq[0, :n + k - 1] = req.prompt + toks[:k - 1]
            pos = np.minimum(np.arange(n - 1, n + CHECK_STEPS),
                             n - 2 + k)[None, :]
            # not jitted as a whole: the reference compiles one layer at
            # a time, so that it fits beside the loaded model
            want, margin = ref.logits_at(params, jnp.asarray(seq),
                                         jnp.asarray(pos), config,
                                         with_margins=True,
                                         act_dtype=self.ref_act_dtype)
            got_rows.append(np.stack(chk["logits"]))
            ref_rows.append(np.asarray(want)[0, :k])
            margins.append(np.asarray(margin)[0, :k])
        got, want = np.concatenate(got_rows), np.concatenate(ref_rows)
        margin = np.concatenate(margins)
        clear = margin >= ROUTER_MARGIN_ULPS
        per_row = np.abs(got - want).max(axis=1)
        if clear.sum() < MIN_COMPARED_ROWS:
            return {"ok": False, "rows": int(len(clear)),
                    "why": f"only {int(clear.sum())} of {len(clear)} rows "
                           f"have a router margin of {ROUTER_MARGIN_ULPS} "
                           f"ulps or more: too few to compare"}
        tol = LOGIT_TOL_ULPS * 2.0 ** -8 * max(1.0, float(np.abs(want).max()))
        finite = bool(np.isfinite(got).all())
        diff = float(per_row[clear].max()) if finite else float("inf")
        return {
            "ok": finite and diff <= tol, "max_logit_diff": diff,
            "logit_tol": tol, "rows": int(clear.sum()),
            "requests": len(recs), "rows_in_all": int(len(clear)),
            "rows_set_aside": int((~clear).sum()),
            # of the rows compared, and of all, how many cover each case
            "rows_compared_by_case": {
                c: sum(1 for cs, ok in zip(cases, clear) if ok and c in cs)
                for c in edges},
            "rows_by_case": {c: sum(1 for cs in cases if c in cs)
                             for c in edges},
            "router_margin_ulps": ROUTER_MARGIN_ULPS,
            "max_ref_logit": float(np.abs(want).max()),
            # what was set aside, for the record: a row there that the
            # router did split reads an expert's output off, not rounding
            "max_logit_diff_set_aside": (float(per_row[~clear].max())
                                         if (~clear).any() else None),
            "row_diffs": [round(float(d), 4) for d in per_row],
            "row_margins_ulps": [round(float(min(m, 9999.0)), 2)
                                 for m in margin],
            "prompt_lengths": [len(r.check["req"].prompt) for r in recs]}


def run(cell, args, work_dir, t_process0):
    job = ServeHybridRun(cell, args.seed, float(args.seconds),
                         bool(args.trace), work_dir)
    job.setup(t_process0)
    return job, job.measure(float(args.seconds))


def control(argv=None) -> int:
    """By hand: set-up alone, its comparison made against the reference
    with fp8 (e4m3) activations, the nearest precision below the bf16 the
    configuration states. One JSON line; exit 0 iff it reads not correct."""
    import argparse
    import json
    import os
    import time

    import jax.numpy as jnp

    from chipbench import device, manifest

    t0 = time.perf_counter()
    p = argparse.ArgumentParser(description=control.__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--control", choices=("fp8",), required=True)
    args = p.parse_args(argv)
    cell = manifest.load_cell(args.workload)
    device.start(cell["chips"])
    work_dir = os.path.join(manifest.REPO, ".chipbench_work", cell["name"])
    os.makedirs(work_dir, exist_ok=True)
    job = ServeHybridRun(cell, args.seed, 1.0, False, work_dir)
    job.ref_act_dtype = jnp.float8_e4m3fn
    job.setup(t0)
    chk = {k: v for k, v in job.check.items()
           if k not in ("row_margins_ulps", "prompt_lengths")}
    print(json.dumps({"control": args.control, "seed": args.seed,
                      "reference_check": chk}), flush=True)
    return 0 if chk["ok"] is False else 1


if __name__ == "__main__":
    import sys
    sys.exit(control())
