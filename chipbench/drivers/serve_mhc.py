"""Serving cells of a model whose residual path is several streams mixed by
hyper-connections round latent attention and sigmoid-routed experts:
``drivers/serve_lm.py``'s run with this model's sizes, limits, long prompts
and edge prompts.

``serve_lm.ServeLMRun`` reads Mistral-Small-4's keys (``sizes_of``) and holds
that model's limits as module constants, so this driver brings its own
``sizes_of`` (what ``trace/kernel_costs_xing4`` needs) and its own copy of
the comparison; the stack (``cli/serve.py::_build_stack``), the traffic, the
window and the per-step counters are the parent classes'. Added here: a
counter of the contexts the window's uncached prompt tokens attended
(``mhc_prompt_context_sum``: prefill is about half this cell's work, so
``model.mhc_serve_mfu`` counts it), the residual path's two readings in the
facts line (``serve.mhc.sinkhorn_residual_max``: the engine's running worst
``H_res``, read from the scalar its serve programs return;
``serve.mhc.maps_total``: rows and chunk tokens x sublayers over the sampled
window), and ``ANNOTATIONS`` keeps every ``serve.*`` layer span of the
program, the children of ``serve.engine.dispatch`` / ``wait`` / ``prefill``
among them.

**The window replays one recorded draw of the mix's lengths.** The mix's
file gives ``lengths_seed``: the stationary fill's and the backlog's prompt
and output LENGTHS are the general generator's draw from that seed in every
run, while the token ids, the sampling seeds and the weights are the run's
own ``--seed`` (:meth:`ServeMhcRun.measure`, :meth:`ServeMhcRun._submit`).
An admission here is ~0.37 s of a 40 s window and a window holds ~57 of
them; with the lengths drawn anew a run, how many it held moved ``out_tok_s``
by 6-12% over a set of six seeds, which is the mix's own variance and says
nothing of the system (PERF.md section 2). The check requests below are
drawn from the run's seed, lengths and all.

**The comparison that decides ``correct``** is ``serve_lm``'s rule (requests
of the cell's own mix through the same scheduler in set-up, logits after a
prefill of eight or more chunks through the latent table and after the first
``CHECK_STEPS`` decode steps, teacher-forced, in bf16 ulps of the largest
reference logit, on rows whose own routing is clear: the reference's router
margin on the selection score ``sigmoid(logit) + bias`` at least
``ROUTER_MARGIN_ULPS``) with ``serve_state``'s two refinements: the UPPER
QUARTILE of a case's clear rows must be within ``LOGIT_TOL_ULPS`` and every
clear row within ``GROSS_TOL_ULPS`` (NOT every row: a row on which program
and reference chose other experts differs by an expert's whole output
through four streams, which reads as far off as an unrelated row; such rows
are set aside, counted and reported); and beside the mix's own requests it
takes prompts cut or stretched to the lengths of ``edge_prompt_lengths``
(around a whole number of prefill buckets, a latent block bound during
decode, the mix's shortest and longest prompt), each a case of its own. The
reference (``reference/xing4.py``) runs at two padded lengths, one compiled
layer of each kind a length.

By hand, two controls (set-up alone, exit 0 iff the comparison reads ``ok:
false``). ``--control fp8`` places ``LOGIT_TOL_ULPS``: the reference's
activations rounded to fp8, the nearest precision below the bf16 the
configuration states (its streams and maps stay float32, as the
configuration states them). ``--control bf16-streams`` rounds exactly what
the other leaves: the reference's streams as every mix reads and writes
them, ``u`` and the three maps, to bf16, the nearest below the float32 the
configuration states for THEM; what it reads is in PERF.md section 6::

    chiprun -- python3 -m chipbench.drivers.serve_mhc \\
        --workload xing4.0-29b.long-prompt-16k --seed <n> --control fp8
"""

from __future__ import annotations

import dataclasses
import importlib

import numpy as np

from chipbench import traffic as traffic_lib
from chipbench.drivers import serve as base
from chipbench.drivers import serve_lm
from chipbench.drivers.serve import clock

ANNOTATIONS = serve_lm.ANNOTATIONS + (
    "serve.engine.bind", "serve.engine.tables", "serve.engine.launch",
    "serve.engine.fetch", "serve.engine.prefill.bind",
    "serve.engine.prefill.launch")

# The limits are set between two readings on the chip at the published
# widths (PERF.md section 6, PR 36, has the numbers): what the program gives
# over its seeds, and what the reference gives with its activations rounded
# to fp8, which must come out as not correct.
# Logits: bf16 ulps (2**-8) of the largest reference logit; the statistic is
# the upper quartile of a case's rows whose own routing is clear. Over the
# first sound runs a case's quartile reads 1.8-2.7 (median row 1.7-1.8: six
# layers of 3,584-wide contractions with bf16 operands; the float32 streams
# add nothing one can see); with fp8 activations a case's quartile reads
# 36.9-73.4 and no single row under 11.8.
LOGIT_TOL_ULPS = 8
# Router margin, in ulps of 2**-8 on the selection score sigmoid(logit) +
# bias, the least over the four sparse layers. A row on which the bf16
# program and the float32 reference chose other experts reads 25-118 ulps
# (an expert's whole output through four streams: as far off as an unrelated
# row). Such rows thin out with the margin: half of those under 0.5, one in
# twelve at 0.5-1.0, one of 126 at 1.0-1.5 (a spread of ~0.43 ulps between
# the two routers' scores), none of 263 from 1.5 up, where every row read
# 3.5 or less; at 2.0 a split is a 4.6-sigma event, and a fifth of the rows
# are left to compare. A row AFTER a split token of its own request reads
# like any other (8k tokens of context dilute one token's latent row).
ROUTER_MARGIN_ULPS = 2.0
# And no CLEAR row may differ by more than this: the program's largest read
# 2.5-3.4; with fp8 activations 95% of the clear rows read over 15.9 and the
# largest 141.7.
GROSS_TOL_ULPS = 16
# What tells float32 streams from bf16 ones, which no limit on a row's LARGEST
# difference does (the second control's case quartiles read 2.7-3.5 ulps,
# under the limit of 8): each row's sqrt(sum of squared logit differences /
# sum of squared reference logits), the MEDIAN over all compared rows (a row
# whose routing split reads far off; one row in eight is one, and the median
# does not see them). The program reads 9.01e-3 to 9.47e-3 over twelve
# seeds; the reference with its streams, u and maps rounded to bf16 at
# every mix (``--control bf16-streams``) 1.189e-2 and 1.199e-2 on two seeds
# where the program reads 9.14e-3 and 9.37e-3.
ROW_REL_RMS_TOL = 1.07e-2
CHECK_REQUESTS = 8      # of the mix's own (each a prefill of ~8 chunks)
# Decode steps compared a request (the base driver's rule compares 3): a
# reference pass costs the same whatever the number of its rows read.
CHECK_STEPS = 31
MIN_COMPARED_ROWS = 4
# The reference runs at two padded lengths: two thirds of the mix's prompts
# fit the short one.
SHORT_PAD = 9216


def edge_prompt_lengths(block: int, bucket: int, lo: int, hi: int) -> dict:
    """-> {kind: [prompt lengths]}, all inside the mix's own ``lo..hi``.
    ``bucket_edge``: one short of, at and one past a whole number of the
    widest prefill bucket (a last chunk full, and of ONE real token in the
    narrowest bucket). ``block_bind``: a prompt of ``m * block - 1`` tokens,
    whose second decode step writes the first row of a latent block bound
    during decode. ``shortest`` / ``longest``: the mix's own bounds (the
    table's far entries, the most chunk programs in a row)."""
    m = lo // bucket + 1
    mid = (lo + hi) // 2 // block * block - 1
    kinds = {"bucket_edge": [m * bucket - 1, m * bucket, m * bucket + 1],
             "block_bind": [mid], "shortest": [lo], "longest": [hi]}
    return {k: [n for n in ns if lo <= n <= hi] for k, ns in kinds.items()}


def sizes_of(config: dict) -> dict:
    """What the cost functions need, from the configuration file's own
    (published) keys and its statement of the chip's share."""
    layers, dense = config["num_hidden_layers"], config["first_k_dense_replace"]
    n = config["hc_mult"]
    return {
        "hidden": config["hidden_size"],
        "heads": config["num_attention_heads"],
        "q_lora": config["q_lora_rank"], "kv_lora": config["kv_lora_rank"],
        "nope": config["qk_nope_head_dim"],
        "rope": config["qk_rope_head_dim"], "v_head": config["v_head_dim"],
        "dense_width": config["intermediate_size"],
        "expert_width": config["moe_intermediate_size"],
        "experts_routed": config["published"]["n_routed_experts"],
        "experts_held": config["experts_held"][1],
        "top_k": config["num_experts_per_tok"],
        "vocab_held": config["vocab_size"], "layers": layers,
        "dense_layers": dense, "sparse_layers": layers - dense,
        "hc_streams": n, "hc_maps": n * (n + 2), "sublayers": 2 * layers}


def judge(per_row, margin, cases, finite: bool = True, row_rel=None) -> dict:
    """The limits on one run's readings. ``per_row`` [rows]: a compared
    row's largest logit difference in ulps; ``margin`` [rows]: its router
    margin; ``cases`` [rows]: its case (``mix``, or an edge prompt's
    ``kind.length``); ``row_rel`` [rows]: its relative rms difference (see
    ``ROW_REL_RMS_TOL``; left out where a caller has only the first three).
    -> the readings, their limits, ``tripped`` (the limits passed, by name)
    and ``ok``."""
    per_row, margin = np.asarray(per_row, float), np.asarray(margin, float)
    cases = np.asarray(cases)
    clear = margin >= ROUTER_MARGIN_ULPS
    names = list(dict.fromkeys(cases.tolist()))
    compared = {c: int((clear & (cases == c)).sum()) for c in names}
    quartile = {
        c: float(np.percentile(per_row[clear & (cases == c)], 75))
        for c in names if compared[c] >= MIN_COMPARED_ROWS}
    out = {"rows": int(clear.sum()), "rows_in_all": int(len(clear)),
           "rows_set_aside": int((~clear).sum()),
           "rows_compared_by_case": compared,
           "router_margin_ulps": ROUTER_MARGIN_ULPS,
           "logit_tol_ulps": LOGIT_TOL_ULPS, "gross_tol_ulps": GROSS_TOL_ULPS,
           "row_rel_rms_tol": ROW_REL_RMS_TOL}
    if not quartile:
        return {**out, "ok": False,
                "why": f"no case has {MIN_COMPARED_ROWS} rows with a router "
                       f"margin of {ROUTER_MARGIN_ULPS} ulps or more: too "
                       f"few to compare"}
    worst = max(quartile.values()) if finite else float("inf")
    gross = float(per_row[clear].max()) if finite else float("inf")
    rel = None if row_rel is None else (
        float(np.median(row_rel)) if finite else float("inf"))
    tripped = [name for name, reading, limit in (
        ("upper_quartile_ulps", worst, LOGIT_TOL_ULPS),
        ("largest_clear_row_ulps", gross, GROSS_TOL_ULPS),
        ("median_row_rel_rms", rel, ROW_REL_RMS_TOL))
        if reading is not None and not reading <= limit]
    return {**out, "ok": not tripped, "tripped": tripped,
            "upper_quartile_ulps": worst, "largest_clear_row_ulps": gross,
            "median_row_rel_rms": rel,
            "largest_row_ulps": float(per_row.max()),
            "upper_quartile_ulps_by_case": quartile,
            "median_ulps": float(np.median(per_row[clear]))}


class ServeMhcRun(serve_lm.ServeLMRun):
    ref_act_dtype = None        # the controls alone round the reference
    ref_stream_dtype = None

    def build(self):
        base.ServeRun.build(self)
        self.obs.model = sizes_of(self.cell["config"])
        self._watched = {}      # slot -> record, past the base's 3 steps
        self._replay_for = None     # the run's seed, while a trace replays

    def _submit(self, req, due, check=False):
        """While the mix's recorded lengths replay (:meth:`measure`), a
        request keeps its drawn lengths and takes its token ids and its
        sampling seed from the RUN's seed."""
        if self._replay_for is not None and not check:
            rng = np.random.default_rng(
                [self._replay_for, 0x1D5, req.index + 2 ** 20])
            req = dataclasses.replace(
                req, prompt=rng.integers(0, self.vocab,
                                         len(req.prompt)).tolist(),
                seed=int(rng.integers(0, 2 ** 31 - 1)))
        return super()._submit(req, due, check)

    def _prefill(self, slot, tokens, **kw):
        """Beside the base's counters: the contexts the prompt's uncached
        tokens attend (token ``i`` attends ``i + 1`` keys), summed, for the
        tokens the base counted (those of the sampled window)."""
        c = self.obs.counters
        before = c.get("prompt_tokens_uncached", 0)
        super()._prefill(slot, tokens, **kw)
        uncached = int(c.get("prompt_tokens_uncached", 0) - before)
        if uncached:
            n, cached = len(tokens), len(tokens) - uncached
            self.obs.count("mhc_prompt_context_sum",
                           (n * (n + 1) - cached * (cached + 1)) / 2)

    def _step(self, active):
        """The base wrapper keeps a check request's logits for its first
        ``base.CHECK_STEPS`` decode steps and then lets go of its slot; this
        one takes the slot over there and goes on to ``CHECK_STEPS``."""
        for slot, rec in list(self.slot_rec.items()):
            if len(rec.check["logits"]) > base.CHECK_STEPS:
                self._watched[slot] = self.slot_rec.pop(slot)
        out = super()._step(active)
        for slot, rec in list(self._watched.items()):
            # a request's last token retires it: its slot's next logits are
            # another request's
            if active[slot] and len(rec.check["logits"]) < _rows_of(rec):
                rec.check["logits"].append(
                    np.asarray(self.engine.last_logits[slot]))
            else:
                del self._watched[slot]
        return out

    def check_against_reference(self) -> dict:
        import jax.numpy as jnp

        config = self.cell["config"]
        ref = importlib.import_module(
            f"chipbench.reference.{config['serve']['reference']}")
        stream = traffic_lib.request_stream(self.traffic, self.seed + 7_919,
                                            self.vocab)
        ecfg, unique = self.engine.cfg, self.traffic["prompt"]["unique"]
        edges = edge_prompt_lengths(ecfg.kv_block_size, ecfg.max_prefill_len,
                                    unique["min"], unique["max"])
        rng = np.random.default_rng([self.seed, 0xED6E])
        recs, kinds = [], []
        for i, (kind, n) in enumerate(
                [("mix", None)] * CHECK_REQUESTS
                + [(k, n) for k, ns in edges.items() for n in ns]):
            req = next(stream)
            req.index = -2000 - i
            req.max_new_tokens = min(req.max_new_tokens, CHECK_STEPS + 1)
            if n is not None:       # an edge prompt: cut or stretched to n
                req.prompt = (req.prompt + rng.integers(
                    0, self.vocab, max(0, n - len(req.prompt))).tolist())[:n]
                req.max_new_tokens = min(CHECK_STEPS + 1,
                                         ecfg.max_len - n - 1)
                kind = f"{kind}.{n}"
            recs.append(self._submit(req, clock(), check=True))
            kinds.append(kind)
        self.sched.run_until_idle()
        self.slot_rec.clear()
        self._watched.clear()

        longest = unique["max"] + CHECK_STEPS
        pad_long = min(-(-longest // 512) * 512, ecfg.max_len)
        params = self.engine.variables["params"]
        cases, got_rows, ref_rows, margins = [], [], [], []
        for rec, kind in zip(recs, kinds):
            chk = rec.check
            req, toks = chk["req"], chk["tokens"]
            k = len(chk["logits"])          # 1 prefill + decode steps
            if k < 2 or len(toks) < k - 1:
                return {"ok": False, "why": f"request {rec.rid} captured "
                        f"{k} logit rows, {len(toks)} tokens"}
            n = len(req.prompt)
            pad_to = min(SHORT_PAD, pad_long) \
                if n + CHECK_STEPS <= SHORT_PAD else pad_long
            cases += [kind] * k
            seq = np.zeros((1, pad_to), np.int32)
            seq[0, :n + k - 1] = req.prompt + toks[:k - 1]
            pos = np.minimum(np.arange(n - 1, n + CHECK_STEPS),
                             n - 2 + k)[None, :]
            # not jitted as a whole: the reference compiles one layer at a
            # time, so that it fits beside the loaded model
            want, margin = ref.logits_at(
                params, jnp.asarray(seq), jnp.asarray(pos), config,
                with_margins=True, act_dtype=self.ref_act_dtype,
                stream_dtype=self.ref_stream_dtype)
            got_rows.append(np.stack(chk["logits"]))
            ref_rows.append(np.asarray(want)[0, :k])
            margins.append(np.asarray(margin)[0, :k])
        got, want = np.concatenate(got_rows), np.concatenate(ref_rows)
        margin = np.concatenate(margins)
        ulp = 2.0 ** -8 * max(1.0, float(np.abs(want).max()))
        finite = bool(np.isfinite(got).all())
        per_row = np.abs(got - want).max(axis=1) / ulp      # in ulps
        with np.errstate(invalid="ignore", over="ignore"):
            row_rel = np.sqrt(
                np.square(got - want).sum(axis=1, dtype=np.float64)
                / np.square(want).sum(axis=1, dtype=np.float64))
        facts = judge(per_row, margin, cases, finite, row_rel)
        clear = margin >= ROUTER_MARGIN_ULPS
        facts.update(
            # every logit of every clear row at once (reported, not
            # limited: it moves as the median does and is noisier, 80-odd
            # rows against 448)
            clear_rows_rel_rms=float(np.sqrt(
                np.square(got[clear] - want[clear]).sum(dtype=np.float64)
                / np.square(want[clear]).sum(dtype=np.float64)))
            if finite and clear.any() else None,
            ulp=ulp, max_ref_logit=float(np.abs(want).max()),
            requests=len(recs),
            row_diffs_ulps=[round(float(d), 2) for d in per_row],
            row_margins_ulps=[round(float(min(m, 9999.0)), 2)
                              for m in margin],
            prompt_lengths=[len(r.check["req"].prompt) for r in recs],
            prefix_cached_tokens=[r.check["cached"] for r in recs])
        return facts

    def measure(self, seconds, rate_per_s=None) -> dict:
        """The base's window; where the mix's file gives ``lengths_seed``,
        over the lengths drawn from THAT seed (the module's note says why):
        the base draws the fill and the backlog from ``self.seed``, which
        stands in for the window alone, and :meth:`_submit` gives every
        request the run's own ids."""
        run_seed, lengths_seed = self.seed, self.traffic.get("lengths_seed")
        if lengths_seed is not None:
            self._replay_for, self.seed = run_seed, int(lengths_seed)
        try:
            head = super().measure(seconds, rate_per_s)
        finally:
            self.seed, self._replay_for = run_seed, None
        self.facts["lengths_seed"] = lengths_seed
        c = self.obs.counters
        self.facts["serve.mhc.sinkhorn_residual_max"] = getattr(
            self.engine, "mhc_residual_max", None)
        self.facts["serve.mhc.maps_total"] = int(
            (c.get("decode_rows", 0) + c.get("prefill_tokens_padded", 0))
            * self.obs.model["sublayers"])
        return head


def _rows_of(rec) -> int:
    """Logit rows compared of a check request: the prefill's and one a
    decode step, to ``CHECK_STEPS`` or the request's end."""
    return min(CHECK_STEPS + 1, rec.check["req"].max_new_tokens)


def run(cell, args, work_dir, t_process0):
    job = ServeMhcRun(cell, args.seed, float(args.seconds), bool(args.trace),
                      work_dir)
    job.setup(t_process0)
    return job, job.measure(float(args.seconds))


def control(argv=None) -> int:
    """By hand: set-up alone, its comparison made against the reference with
    fp8 (e4m3) activations, the nearest precision below the bf16 the
    configuration states. Exit 0 iff it reads not correct. One JSON line."""
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
    p.add_argument("--control", required=True,
                   choices=("fp8", "bf16-streams"))
    p.add_argument("--root", default=manifest.ROOT,
                   help="where cells/, configs/ and traffic/ are (tests)")
    args = p.parse_args(argv)
    cell = manifest.load_cell(args.workload, args.root)
    device.start(cell["chips"])
    work_dir = os.path.join(manifest.REPO, ".chipbench_work", cell["name"])
    os.makedirs(work_dir, exist_ok=True)
    job = ServeMhcRun(cell, args.seed, 1.0, False, work_dir)
    if args.control == "fp8":
        job.ref_act_dtype = jnp.float8_e4m3fn
    else:
        job.ref_stream_dtype = jnp.bfloat16
    job.setup(t0)
    chk = {k: v for k, v in job.check.items() if k != "prompt_lengths"}
    print(json.dumps({"control": args.control, "seed": args.seed,
                      "reference_check": chk}), flush=True)
    return 0 if chk["ok"] is False else 1


if __name__ == "__main__":
    import sys
    sys.exit(control())
