"""Serving cells of a model whose layers keep two kinds of cache (a
recurrent state a slot on the linear-attention layers beside a paged
latent table on the attention layers) and route their experts by sigmoid
scores: ``drivers/serve_lm.py``'s run with this model's sizes, limits and
edge prompts.

``serve_lm.ServeLMRun`` reads Mistral-Small-4's keys (``sizes_of``) and
holds that model's two limits as module constants, and ``serve_hybrid``
K-EXAONE's, so this driver brings its own ``sizes_of`` (what
``trace/kernel_costs_kimi`` needs) and its own copy of the comparison with
its own limits; the stack, the traffic, the window, the ``ANNOTATIONS``
(the program's ``serve.*`` spans among them) and the per-step counters are
the parent classes': ``moe_rows`` (the active rows of each sampled step,
which are the rows whose state the step updates), ``lm_resident_tokens``
(their contexts: the latent rows the step reads), ``moe_touched`` /
``moe_held_pairs``, and ``Obs.steps`` (time, active rows, resident tokens
of every step) for the readers that need the traced span's own steps.

**The comparison that decides ``correct``** is ``serve_lm``'s rule
(requests of the cell's own mix through the same scheduler in set-up,
logits after prefill and after the first ``CHECK_STEPS`` decode steps,
teacher-forced, in bf16 ulps of the largest reference logit, on rows whose
own routing is clear: the reference's router margin on the selection score
``sigmoid(logit) + bias`` at least ``ROUTER_MARGIN_ULPS``) with two
departures, whose reasons and readings stand beside the limits below.
(1) The UPPER QUARTILE of those rows' differences, for the mix's requests
together and for EACH edge prompt by itself, must be within
``LOGIT_TOL_ULPS``, not their largest; every row of all must be within
``GROSS_TOL_ULPS``. (2) Beside the logits, one reading that no router
stands before: the FIRST layer's recurrent state (a KDA layer fed by the
embedding), read from the slot's entry of the pool after the request's
last compared step, against the reference's state after the same tokens;
its relative difference (Frobenius, all heads) must be within
``STATE_TOL`` on EVERY request. That reading is what tells a float32 state
from a bf16 one, and a state handed over wrongly from one whose logits a
routing split moved. The
reference runs the linear-attention layers as the token recurrence; the
program prefills them in chunks of 64 and decodes through a kernel, so
what is compared is two algebras of one function. Beside the mix's own
requests it takes prompts cut or stretched to the lengths of
``edge_prompt_lengths``: where the state and the convolution's tail are
handed from one program to the next, which the mix's prompts hit only by
chance; the result counts the compared rows of each kind
(``rows_compared_by_case``) and gives each case's readings.

By hand, the three controls (each: set-up alone, exit 0 iff the comparison
reads ``ok: false``; the result names the limits that tripped).
``--control fp8`` places ``LOGIT_TOL_ULPS``: the reference's activations
rounded to fp8. ``--control bf16-state`` places ``STATE_TOL``: the
reference's KDA state rounded to bf16 after every token. ``--control
stale-state`` places ``GROSS_TOL_ULPS``: the PROGRAM at fault, two check
requests decoding from each other's state entry::

    chiprun -- python3 -m chipbench.drivers.serve_state \\
        --workload kimi-linear-48b.reason-gen-16k --seed <n> --control fp8
"""

from __future__ import annotations

import importlib

import numpy as np

from chipbench import traffic as traffic_lib
from chipbench.drivers import serve as base
from chipbench.drivers import serve_lm
from chipbench.drivers.serve import clock

ANNOTATIONS = serve_lm.ANNOTATIONS

# The limits are set from readings on the chip at the published widths
# (PERF.md section 6, PR 32, has the numbers): what the program gives over
# its seeds, and what a control gives (the reference in the nearest
# precision below the one the configuration states, or the program at
# fault), which must come out as not correct.
#
# What is compared is not the other two drivers' "largest difference over
# the rows whose routing is clear", because here no margin makes a row
# clear. A linear-attention layer carries every earlier token forward: the
# convolution reads the last three tokens' hidden states and the state
# sums all of them, so a token on which a bf16 program and the float32
# reference chose different experts (one token in five: 256 experts' scores
# lie close) moves the rows AFTER it, whatever their own margins. Over
# 1,968 rows of three seeds a row's own split reads 9-32 ulps and thins
# out with its margin (60% of rows under 0.1 ulps, 3.4% at 1-1.5, 0.9% at
# 2-2.5), but 1-3% of the rows at ANY margin up to 3.5 read 8-20 ulps, each
# right after tokens that split; every other row reads 2.3-7 (median
# 3.0-3.9). With fp8 activations EVERY row reads 25-59. So the statistic
# is one that a few such rows cannot move and a loss of precision or a
# fault in a hand-over (which moves every row of the request it happens
# in) must: the UPPER QUARTILE of the differences over a case's rows whose
# own margin is at least ROUTER_MARGIN_ULPS. A case is the mix's requests
# together, or ONE edge prompt (a fault confined to one of a kind's
# prompts is a quarter of that kind's rows and all of its own), with at
# least MIN_COMPARED_ROWS such rows.
# Logits: bf16 ulps (2**-8) of the largest reference logit. Over seventeen
# sound runs an edge prompt's upper quartile reads 2.3-6.9 (153 prompts of
# 12-34 clear rows) and the mix's 3.7-4.5; with fp8 activations every
# case's 35.2-41.5, and no single row under 25.
LOGIT_TOL_ULPS = 12
# Router margin, in ulps of 2**-8 on the selection score sigmoid(logit) +
# bias: from 1.0 up one row in thirty is a split of its own (7% at
# 0.75-1.0, 15% at 0.5-0.75); a third of the rows are left to compare.
ROUTER_MARGIN_ULPS = 1.0
# And no row at all, whatever its margin, may differ by more than this: a
# run's largest row, a split, reads 29-40 over twenty-two runs; of two
# requests that decoded from each other's state (--control stale-state)
# every row after the prefill's reads 154-288 (the largest 278 and 288,
# each one's upper quartile 221-222).
GROSS_TOL_ULPS = 64
# The first layer's state: |program - reference| / |reference| (Frobenius
# norm over a request's 32 heads of 128 x 128), the largest over the
# requests. No router stands before it, so nothing splits: what differs is
# the program's bf16 projections and tails against the reference's
# float32 ones, and whatever the state itself loses. The program reads
# 3.45e-3 to 3.56e-3 on every request of eight runs; the reference with
# its state rounded to bf16 after every token (--control bf16-state)
# 9.7e-3 to 1.01e-2, with fp8 activations 5.5e-2 to 5.7e-2, and the two
# requests of --control stale-state 0.58.
STATE_TOL = 6.0e-3
CHECK_REQUESTS = 32     # of the mix's own
# Decode steps compared a request (the base driver's rule compares 3): an
# edge prompt then has ~22 rows to take a quartile of, and a
# reference pass costs the same whatever the number of its rows that are
# read.
CHECK_STEPS = 63
MIN_COMPARED_ROWS = 4
# The reference runs at two padded lengths (one compiled layer each): most
# of the mix's prompts fit the short one.
SHORT_PAD = 2560


def edge_prompt_lengths(chunk: int, block: int, bucket: int, lo: int,
                        hi: int) -> dict:
    """-> {kind: [prompt lengths]}, all inside the mix's own ``lo..hi``.
    ``chunk_edge``: one short of, at and one past a whole number of KDA
    chunks (the prefill scan's last chunk full of pads but for one token,
    full, and empty but for one). ``bucket_edge``: around the widest
    prefill bucket and one past two of them (the state and the
    convolution's tail handed from one chunk program to the next, and a
    last chunk of ONE real token, whose tail is the chunk before it).
    ``block_bind``: a prompt of ``m * block - 1`` tokens, whose second
    decode step writes the first row of a latent block bound during
    decode. ``longest``: the mix's longest prompt (the latent table's far
    entries, the most chunk programs in a row)."""
    m = -(-lo // chunk) + 1
    mid = (lo + hi) // 2 // block * block - 1
    kinds = {"chunk_edge": [m * chunk - 1, m * chunk, m * chunk + 1],
             "bucket_edge": [bucket - 1, bucket, bucket + 1, 2 * bucket + 1],
             "block_bind": [mid], "longest": [hi]}
    return {k: [n for n in ns if lo <= n <= hi] for k, ns in kinds.items()}


def sizes_of(config: dict) -> dict:
    """What the cost functions need, from the configuration file's own
    (published) keys and its statement of the chip's share."""
    layers = config["num_hidden_layers"]
    lin = config["linear_attn_config"]
    kda = sum(1 for l in range(1, layers + 1) if l in lin["kda_layers"])
    dense = config["first_k_dense_replace"]
    return {
        "hidden": config["hidden_size"],
        "heads": config["num_attention_heads"],
        "kv_lora": config["kv_lora_rank"],
        "nope": config["qk_nope_head_dim"],
        "rope": config["qk_rope_head_dim"], "v_head": config["v_head_dim"],
        "kda_heads": lin["num_heads"], "kda_dim": lin["head_dim"],
        "conv_kernel": lin["short_conv_kernel_size"],
        "gate_rank": config["assumed_sizes"]["kda_gate_rank"],
        "dense_width": config["intermediate_size"],
        "expert_width": config["moe_intermediate_size"],
        "experts_routed": config["published"]["num_experts"],
        "experts_held": config["experts_held"][1],
        "top_k": config["num_experts_per_token"],
        "vocab_held": config["vocab_size"], "layers": layers,
        "dense_layers": dense, "sparse_layers": layers - dense,
        "kda_layers": kda, "mla_layers": layers - kda}


def judge(per_row, margin, cases, state_diffs, finite: bool = True) -> dict:
    """The limits on one run's readings. ``per_row`` [rows]: a compared
    row's largest logit difference in ulps; ``margin`` [rows]: its router
    margin; ``cases`` [rows]: its case (``mix``, or an edge prompt's
    ``kind.length``); ``state_diffs`` [requests]: each request's
    first-layer state difference (None where the program gave none). ->
    the readings, their limits, ``tripped`` (the limits passed, by name)
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
           "rows_by_case": {c: int((cases == c).sum()) for c in names},
           "router_margin_ulps": ROUTER_MARGIN_ULPS,
           "logit_tol_ulps": LOGIT_TOL_ULPS, "gross_tol_ulps": GROSS_TOL_ULPS,
           "state_tol": STATE_TOL}
    if not quartile:
        return {**out, "ok": False,
                "why": f"no case has {MIN_COMPARED_ROWS} rows with a router "
                       f"margin of {ROUTER_MARGIN_ULPS} ulps or more: too "
                       f"few to compare"}
    worst = max(quartile.values()) if finite else float("inf")
    gross = float(per_row.max()) if finite else float("inf")
    state = (max(state_diffs) if finite and None not in state_diffs
             else float("inf"))
    tripped = [name for name, reading, limit in (
        ("upper_quartile_ulps", worst, LOGIT_TOL_ULPS),
        ("largest_row_ulps", gross, GROSS_TOL_ULPS),
        ("state_diff", state, STATE_TOL)) if not reading <= limit]
    return {**out, "ok": not tripped, "tripped": tripped,
            "upper_quartile_ulps": worst, "largest_row_ulps": gross,
            "state_diff": state,
            "upper_quartile_ulps_by_case": quartile,
            "median_ulps": float(np.median(per_row[clear]))}


class ServeStateRun(serve_lm.ServeLMRun):
    # the controls alone round the reference, or plant a fault
    ref_act_dtype = None
    ref_state_dtype = None
    plant_stale_state = False

    def build(self):
        base.ServeRun.build(self)
        self.obs.model = sizes_of(self.cell["config"])
        self._watched = {}      # slot -> record, past the base's 3 steps
        self._planted = None

    def _first_state(self, slot: int):
        """The first layer's state as the slot's entry of the pool holds
        it, on the host (None where that layer keeps none)."""
        import jax

        pool = self.engine.pool
        if pool.layer_groups[0] != "state":
            return None
        entry = np.int32(pool.state_tables_host[slot, 0])
        return np.asarray(jax.lax.dynamic_index_in_dim(
            pool.caches[0]["s"], entry, 0, keepdims=False))

    def _swap_two_states(self) -> None:
        """--control stale-state: the first two of the mix's check
        requests, prefilled and not yet decoded, trade state entries, so
        each decodes from the other's state and tails."""
        fresh = sorted(
            (-rec.check["req"].index, slot)
            for slot, rec in self.slot_rec.items()
            if len(rec.check["logits"]) == 1
            and rec.check["req"].index > -2000 - CHECK_REQUESTS)
        if len(fresh) >= 2:
            (_, a), (_, b) = fresh[:2]
            table = self.engine.pool.state_tables_host
            table[[a, b]] = table[[b, a]]
            self._planted = [self.slot_rec[s].rid for s in (a, b)]

    def _step(self, active):
        """The base wrapper keeps a check request's logits for its first
        ``base.CHECK_STEPS`` decode steps and then lets go of its slot;
        this one takes the slot over there and goes on to
        ``CHECK_STEPS``, and reads the first layer's state after the
        request's last compared step."""
        if self.plant_stale_state and self._planted is None:
            self._swap_two_states()
        for slot, rec in list(self.slot_rec.items()):
            if len(rec.check["logits"]) > base.CHECK_STEPS:
                self._watched[slot] = self.slot_rec.pop(slot)
        out = super()._step(active)
        for slot, rec in list(self._watched.items()):
            # a request's last token retires it: its slot's next logits
            # are another request's
            if active[slot] and len(rec.check["logits"]) < _rows_of(rec):
                rec.check["logits"].append(
                    np.asarray(self.engine.last_logits[slot]))
            else:
                del self._watched[slot]
        for slot, rec in (*self.slot_rec.items(), *self._watched.items()):
            chk = rec.check
            if "state" not in chk and len(chk["logits"]) >= _rows_of(rec):
                # the state now holds the prompt and every token fed so far
                chk["state"] = self._first_state(slot)
                chk["state_tokens"] = (len(chk["req"].prompt)
                                       + len(chk["logits"]) - 1)
        return out

    def check_against_reference(self) -> dict:
        import jax.numpy as jnp

        config = self.cell["config"]
        ref = importlib.import_module(
            f"chipbench.reference.{config['serve']['reference']}")
        stream = traffic_lib.request_stream(self.traffic, self.seed + 7_919,
                                            self.vocab)
        ecfg, unique = self.engine.cfg, self.traffic["prompt"]["unique"]
        edges = edge_prompt_lengths(
            self.engine.model.cfg.kda_chunk, ecfg.kv_block_size,
            ecfg.max_prefill_len, unique["min"], unique["max"])
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
        cases = []
        got_rows, ref_rows, margins, state_diffs = [], [], [], []
        for rec, kind in zip(recs, kinds):
            chk = rec.check
            req, toks = chk["req"], chk["tokens"]
            k = len(chk["logits"])          # 1 prefill + decode steps
            if k < 2 or len(toks) < k - 1:
                return {"ok": False, "why": f"request {rec.rid} captured "
                        f"{k} logit rows, {len(toks)} tokens"}
            n = len(req.prompt)
            pad_to = SHORT_PAD if n + CHECK_STEPS <= SHORT_PAD else pad_long
            cases += [kind] * k
            seq = np.zeros((1, pad_to), np.int32)
            seq[0, :n + k - 1] = req.prompt + toks[:k - 1]
            pos = np.minimum(np.arange(n - 1, n + CHECK_STEPS),
                             n - 2 + k)[None, :]
            state = chk.get("state")
            # not jitted as a whole: the reference compiles one layer at
            # a time, so that it fits beside the loaded model
            want, margin, *ref_state = ref.logits_at(
                params, jnp.asarray(seq), jnp.asarray(pos), config,
                with_margins=True, act_dtype=self.ref_act_dtype,
                state_dtype=self.ref_state_dtype,
                state_at=None if state is None else jnp.asarray(
                    [chk["state_tokens"] - 1], jnp.int32))
            got_rows.append(np.stack(chk["logits"]))
            ref_rows.append(np.asarray(want)[0, :k])
            margins.append(np.asarray(margin)[0, :k])
            if state is None:
                state_diffs.append(None)
            else:
                want_state = np.asarray(ref_state[0])[0]
                state_diffs.append(float(
                    np.linalg.norm(state - want_state)
                    / max(np.linalg.norm(want_state), 1e-30)))
        got, want = np.concatenate(got_rows), np.concatenate(ref_rows)
        margin = np.concatenate(margins)
        ulp = 2.0 ** -8 * max(1.0, float(np.abs(want).max()))
        finite = bool(np.isfinite(got).all()) and all(
            d is None or np.isfinite(d) for d in state_diffs)
        per_row = np.abs(got - want).max(axis=1) / ulp      # in ulps
        facts = judge(per_row, margin, cases, state_diffs, finite)
        facts.update(
            ulp=ulp, max_ref_logit=float(np.abs(want).max()),
            requests=len(recs),
            state_diffs=[d if d is None else round(d, 6)
                         for d in state_diffs],
            row_diffs_ulps=[round(float(d), 2) for d in per_row],
            row_margins_ulps=[round(float(min(m, 9999.0)), 2)
                              for m in margin],
            prompt_lengths=[len(r.check["req"].prompt) for r in recs])
        if self._planted:
            facts["stale_state_planted_in"] = self._planted
        return facts


def _rows_of(rec) -> int:
    """Logit rows compared of a check request: the prefill's and one a
    decode step, to ``CHECK_STEPS`` or the request's end."""
    return min(CHECK_STEPS + 1, rec.check["req"].max_new_tokens)


def run(cell, args, work_dir, t_process0):
    job = ServeStateRun(cell, args.seed, float(args.seconds),
                        bool(args.trace), work_dir)
    job.setup(t_process0)
    return job, job.measure(float(args.seconds))


def control(argv=None) -> int:
    """By hand: set-up alone, its comparison made against the reference
    with fp8 (e4m3) activations, the nearest precision below the bf16 the
    configuration states; or with the KDA state rounded to bf16 after
    every token, the nearest below the float32 it states; or, against the
    sound reference, of a program two of whose check requests decode from
    each other's state entry. Exit 0 iff it reads not correct. One JSON
    line."""
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
                   choices=("fp8", "bf16-state", "stale-state"))
    p.add_argument("--root", default=manifest.ROOT,
                   help="where cells/, configs/ and traffic/ are (tests)")
    args = p.parse_args(argv)
    cell = manifest.load_cell(args.workload, args.root)
    device.start(cell["chips"])
    work_dir = os.path.join(manifest.REPO, ".chipbench_work", cell["name"])
    os.makedirs(work_dir, exist_ok=True)
    job = ServeStateRun(cell, args.seed, 1.0, False, work_dir)
    if args.control == "fp8":
        job.ref_act_dtype = jnp.float8_e4m3fn
    elif args.control == "bf16-state":
        job.ref_state_dtype = jnp.bfloat16
    else:
        job.plant_stale_state = True
    job.setup(t0)
    chk = {k: v for k, v in job.check.items() if k != "prompt_lengths"}
    print(json.dumps({"control": args.control, "seed": args.seed,
                      "reference_check": chk}), flush=True)
    return 0 if chk["ok"] is False else 1


if __name__ == "__main__":
    import sys
    sys.exit(control())
