"""Serving cells of a model whose layers are block-sparse attention over
the paged K/V table (with a third leaf of compressed keys) beside
lightning linear-attention layers (a float32 state a slot in the state
group): ``drivers/serve_state.py``'s run with this model's sizes, limits,
readings and edge prompts.

``serve_state.ServeStateRun`` brings what a model with a state group needs
(a check request watched to its last compared step, a reading taken from
the pool right after it); this driver keeps that and brings its own
``sizes_of`` (what ``trace/kernel_costs_minicpm_sala`` needs), limits,
:func:`judge` and reading: the FIRST lightning layer's state and the
sparse layer's compressed keys of the request's slot. Added to the
counters, over the sampled decode steps: the blocks the sparse layer's
queries read and the blocks their rows hold, by the model's own rule
(``MiniCPMSALA.sparse_blocks``, what the program's ``serve.sparse.*``
counters count), ``model.sparse_read_share``'s two counters; each step's
(time, rows, blocks read) for ``kernel.sparse_decode_roofline``; the rows'
contexts (``sala_resident_tokens``) and the window's prompt contexts
(``sala_prompt_context_sum``) for ``model.sala_serve_mfu``.

**The comparison that decides ``correct``**: requests of the cell's own mix
through the same scheduler in set-up, and beside them prompts cut or
stretched to the lengths of :func:`edge_prompt_lengths` (around the first
complete window; around ``dense_len``, so the selection starts inside the
compared steps; around a prefill chunk; and ``LONG_EDGE`` tokens, whose
compared rows select ``topk`` of more than 150 blocks outside the window).
Three readings, each with its limit:

- the logits after prefill and after the first ``CHECK_STEPS`` decode
  steps, teacher-forced, in bf16 ulps (2**-8) of the largest reference
  logit, the largest over EVERY compared row (no router stands before any
  of them);
- the first lightning layer's state read from the slot's entry after the
  request's last compared step, against the reference's after the same
  tokens (relative Frobenius difference, all heads), the largest over the
  requests: the one reading of a state's precision (a bf16 state moves
  the logits by under the logits' limit), and a second reading of the
  sparse layer's output, which it sums over the last few hundred tokens;
- the sparse layer's compressed keys of every complete window of the
  request, read from the ``ck`` leaf through the slot's table, against
  the reference's means of its float32 keys (relative Frobenius).

The reference (``reference/minicpm_sala.py``) makes EVERY query's
selection from its own float32 scores; near-tied block scores may select
differently from the program's bf16 queries, which the limits must allow.

By hand, the controls (one set-up, then one comparison and one JSON line a
control; exit 0 iff every one reads ``ok: false``; a line names the limits
that tripped). ``--control fp8``: the reference's activations rounded to
fp8. ``--control bf16-state``: the reference's lightning state rounded to
bf16 after every token. ``--control window-only``: the reference's sparse
layer selects the init block and the window's blocks alone past
``dense_len`` (a wrong selection, the program left sound)::

    python3 -m chipbench.drivers.serve_sala \\
        --workload minicpm-sala.reason-gen-64k --seed <n> \\
        --control window-only fp8 bf16-state
"""

from __future__ import annotations

import importlib

import numpy as np

from chipbench import traffic as traffic_lib
from chipbench.drivers import serve as base
from chipbench.drivers import serve_ssm, serve_state
from chipbench.drivers.serve import clock

ANNOTATIONS = serve_ssm.ANNOTATIONS

# The limits stand between readings on the chip at the published widths
# (PERF.md section 6 has them): what the program gives over its seeds, and
# what a control gives, which must come out as not correct.
# Logits: bf16 ulps (2**-8) of the largest reference logit, the largest
# over every compared row. The program 1.81-2.11 over three seeds; the
# reference's selection cut to the init block and the window 15.96 (13-16
# on every case past dense_len); fp8 activations 17.37; a bf16 lightning
# state 2.41 (it trips the state's limit instead).
LOGIT_TOL_ULPS = 4
# The first lightning layer's state: |program - reference| / |reference|
# (Frobenius over a request's 32 heads of 128 x 128), the largest over the
# requests. The program 5.28-5.31e-3 over three seeds; a bf16 state
# 1.52e-2 (8.0e-3 on the two 31/32-token prompts, 1.5e-2 on the rest);
# the window-only selection 5.18e-2; fp8 activations 6.56e-2.
STATE_TOL = 1.0e-2
# The compressed keys: |program - reference| / |reference| over a
# request's complete windows, the largest over the requests. The program
# 2.92-3.03e-3 (bf16 keys averaged against float32 ones); fp8 activations
# 2.87e-2; a selection or a state control leaves them as they are.
CK_TOL = 1.0e-2
CHECK_REQUESTS = 8      # of the mix's own
CHECK_STEPS = base.CHECK_STEPS
LONG_EDGE = 16384
# The reference runs at two padded lengths (two compiled layers each).
SHORT_PAD = 4096


def edge_prompt_lengths(sizes, chunk: int) -> dict:
    """-> {kind: [prompt lengths]}. ``first_window``: one short of and at
    the first complete window (``kernel``). ``dense_edge``: prompts whose
    compared steps cross ``dense_len`` (the last dense query, the first
    selecting one). ``chunk_edge``: one past a prefill chunk and one past
    two (a window that straddles two programs). ``long``: ``LONG_EDGE``
    tokens."""
    dense = sizes["dense_len"]
    return {"first_window": [sizes["kernel"] - 1, sizes["kernel"]],
            "dense_edge": [dense - 1, dense + 1],
            "chunk_edge": [chunk + 1, 2 * chunk + 1], "long": [LONG_EDGE]}


def sizes_of(config: dict) -> dict:
    """What the cost functions need, from the configuration file's own
    (published) keys and its statement of the chip's share."""
    layers = config["num_hidden_layers"]
    kinds = config["mixer_types"][:layers]
    z = config["assumed_sizes"]["sparse_config"]
    return {
        "hidden": config["hidden_size"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "lightning_heads": config["lightning_nh"],
        "lightning_head_dim": config["lightning_head_dim"],
        "width": config["intermediate_size"],
        "vocab_held": config["vocab_size"], "layers": layers,
        "sparse_layers": kinds.count("minicpm4"),
        "lightning_layers": kinds.count("lightning-attn"),
        "kernel": z["kernel_size"], "stride": z["kernel_stride"],
        "block": z["block_size"], "topk": z["topk"],
        "init_blocks": z["init_blocks"], "window": z["window_size"],
        "dense_len": z["dense_len"]}


def judge(per_row, state_diffs, ck_diffs, finite: bool = True) -> dict:
    """The limits on one run's readings: ``per_row`` [rows] a compared
    row's largest logit difference in ulps; ``state_diffs`` /
    ``ck_diffs`` [requests] (None where the program gave none). -> the
    readings, their limits, ``tripped`` and ``ok``."""
    per_row = np.asarray(per_row, float)

    def worst(values):
        if not finite or not values or None in values:
            return float("inf")
        return float(max(values))

    largest = float(per_row.max()) if finite and per_row.size \
        else float("inf")
    state, ck = worst(state_diffs), worst(ck_diffs)
    tripped = [name for name, reading, limit in (
        ("largest_row_ulps", largest, LOGIT_TOL_ULPS),
        ("state_diff", state, STATE_TOL),
        ("ck_diff", ck, CK_TOL)) if not reading <= limit]
    return {"ok": not tripped, "tripped": tripped, "rows": int(per_row.size),
            "logit_tol_ulps": LOGIT_TOL_ULPS, "state_tol": STATE_TOL,
            "ck_tol": CK_TOL, "largest_row_ulps": largest,
            "state_diff": state, "ck_diff": ck,
            "median_ulps": float(np.median(per_row)) if per_row.size
            else None}


def _rel(got, want) -> float:
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-30))


class ServeSALARun(serve_state.ServeStateRun):
    window_only = False     # --control window-only alone

    def build(self):
        base.ServeRun.build(self)
        self.obs.model = sizes_of(self.cell["config"])
        self._watched = {}
        self._planted = None

    def _first_state(self, slot: int):
        """What the pool holds of the slot right after its last compared
        step: the first lightning layer's state entry ``[H, D, D]`` and
        the sparse layer's ``ck`` rows of the slot's bound blocks."""
        import jax
        import jax.numpy as jnp

        pool, model = self.engine.pool, self.engine.model
        first = next(i for i in range(model.cfg.num_hidden_layers)
                     if not model.cfg.is_sparse(i))
        entry = np.int32(pool.state_tables_host[slot, 0])
        state = np.asarray(jax.lax.dynamic_index_in_dim(
            pool.caches[first]["s"], entry, 0, keepdims=False))
        bs = self.engine.cfg.kv_block_size
        held = int(-(-(int(self.engine.host_positions[slot]) + 1) // bs))
        blocks = pool.tables_host[slot, :max(1, held)].copy()
        ck = np.asarray(pool.caches[0]["ck"][jnp.asarray(blocks)])
        return {"state": state, "ck": ck}

    def _prefill(self, slot, tokens, **kw):
        """Beside the base's counters: the contexts the prompt's uncached
        tokens attend, summed (``serve_ssm``'s rule)."""
        c = self.obs.counters
        before = c.get("prompt_tokens_uncached", 0)
        super()._prefill(slot, tokens, **kw)
        uncached = int(c.get("prompt_tokens_uncached", 0) - before)
        if uncached:
            n, cached = len(tokens), len(tokens) - uncached
            self.obs.count("sala_prompt_context_sum",
                           (n * (n + 1) - cached * (cached + 1)) / 2)

    def _step(self, active):
        out = super()._step(active)
        t = clock()
        if t >= self.win[0]:
            lengths = self.engine.host_positions[active]
            read, held = self.engine.model.sparse_blocks(lengths)
            if not hasattr(self.obs, "sparse_steps"):
                self.obs.sparse_steps = []
            self.obs.sparse_steps.append(
                (t, int(np.count_nonzero(active)), read))
            if self._sampling(t):
                self.obs.count("sala_resident_tokens", int(lengths.sum()))
                self.obs.count("sparse_blocks_read", read)
                self.obs.count("sparse_blocks_resident", held)
        return out

    def check_against_reference(self) -> dict:
        import jax.numpy as jnp

        config = self.cell["config"]
        ref = importlib.import_module(
            f"chipbench.reference.{config['serve']['reference']}")
        stream = traffic_lib.request_stream(self.traffic, self.seed + 7_919,
                                            self.vocab)
        ecfg, sizes = self.engine.cfg, self.obs.model
        edges = edge_prompt_lengths(sizes, ecfg.max_prefill_len)
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
                kind = f"{kind}.{n}"
            recs.append(self._submit(req, clock(), check=True))
            kinds.append(kind)
        self.sched.run_until_idle()
        self.slot_rec.clear()
        self._watched.clear()

        longest = max(max(len(r.check["req"].prompt) for r in recs),
                      LONG_EDGE) + CHECK_STEPS
        pad_long = min(-(-longest // 512) * 512, ecfg.max_len)
        params = self.engine.variables["params"]
        stride, kernel = sizes["stride"], sizes["kernel"]
        per_block = sizes["block"] // stride
        got_rows, ref_rows, state_diffs, ck_diffs = [], [], [], []
        for rec in recs:
            chk = rec.check
            req, toks = chk["req"], chk["tokens"]
            k = len(chk["logits"])          # 1 prefill + decode steps
            if k < 2 or len(toks) < k - 1:
                return {"ok": False, "why": f"request {rec.rid} captured "
                        f"{k} logit rows, {len(toks)} tokens"}
            n = len(req.prompt)
            pad_to = SHORT_PAD if n + CHECK_STEPS <= SHORT_PAD else pad_long
            seq = np.zeros((1, pad_to), np.int32)
            seq[0, :n + k - 1] = req.prompt + toks[:k - 1]
            pos = np.minimum(np.arange(n - 1, n + CHECK_STEPS),
                             n - 2 + k)[None, :]
            read = chk.get("state")
            fed = chk.get("state_tokens", n + k - 1)
            # not jitted as a whole: the reference compiles one layer of
            # a kind at a time, so that it fits beside the loaded model
            want, kept = ref.logits_at(
                params, jnp.asarray(seq), jnp.asarray(pos), config,
                act_dtype=self.ref_act_dtype,
                state_dtype=self.ref_state_dtype,
                state_at=jnp.asarray([fed - 1], jnp.int32),
                window_only=self.window_only)
            got_rows.append(np.stack(chk["logits"]))
            ref_rows.append(np.asarray(want)[0, :k])
            if read is None:
                state_diffs.append(None)
                ck_diffs.append(None)
                continue
            state_diffs.append(_rel(read["state"], np.asarray(kept[1])[0]))
            n_c = max(0, (fed - kernel) // stride + 1)
            if n_c:
                want_ck = np.asarray(kept[0])[0, :n_c]     # [n_c, KVH, D]
                got_ck = read["ck"].reshape(
                    len(read["ck"]) * per_block, *want_ck.shape[1:])[:n_c]
                ck_diffs.append(_rel(got_ck, want_ck))
        got, want = np.concatenate(got_rows), np.concatenate(ref_rows)
        ulp = 2.0 ** -8 * float(np.abs(want).max())
        finite = bool(np.isfinite(got).all()) and all(
            d is None or np.isfinite(d) for d in state_diffs + ck_diffs)
        per_row = np.abs(got - want).max(axis=1) / ulp      # in ulps
        cases = np.repeat(kinds, [len(r) for r in got_rows])
        facts = judge(per_row, state_diffs, ck_diffs, finite)
        facts.update(
            ulp=ulp, max_ref_logit=float(np.abs(want).max()),
            requests=len(recs),
            largest_row_ulps_by_case={
                c: float(per_row[cases == c].max())
                for c in dict.fromkeys(kinds)},
            state_diffs=[d if d is None else round(d, 6)
                         for d in state_diffs],
            ck_diffs=[round(d, 6) for d in ck_diffs if d is not None],
            row_diffs_ulps=[round(float(d), 2) for d in per_row],
            prompt_lengths=[len(r.check["req"].prompt) for r in recs])
        return facts

    def measure(self, seconds, rate_per_s=None) -> dict:
        head = super().measure(seconds, rate_per_s)
        c, s = self.obs.counters, self.obs.model
        self.facts["serve.ssm.decode_rows_total"] = int(
            c.get("decode_rows", 0) * s["lightning_layers"])
        self.facts["serve.sparse.blocks_read_total"] = int(
            c.get("sparse_blocks_read", 0))
        self.facts["serve.sparse.blocks_resident_total"] = int(
            c.get("sparse_blocks_resident", 0))
        return head


def run(cell, args, work_dir, t_process0):
    job = ServeSALARun(cell, args.seed, float(args.seconds),
                       bool(args.trace), work_dir)
    job.setup(t_process0)
    return job, job.measure(float(args.seconds))


def control(argv=None) -> int:
    """By hand: set-up alone, its comparison made against the reference
    whose sparse layer selects the init block and the window alone past
    ``dense_len`` (``window-only``: a wrong selection); or with fp8 (e4m3)
    activations, the nearest precision below the bf16 the configuration
    states; or with the lightning state rounded to bf16 after every token,
    the nearest below the float32 it states. Several controls share one
    set-up. One JSON line a control; exit 0 iff each reads not correct."""
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
    order = ("window-only", "fp8", "bf16-state")
    p.add_argument("--control", required=True, nargs="+", choices=order)
    p.add_argument("--root", default=manifest.ROOT,
                   help="where cells/, configs/ and traffic/ are (tests)")
    args = p.parse_args(argv)
    cell = manifest.load_cell(args.workload, args.root)
    device.start(cell["chips"])
    work_dir = os.path.join(manifest.REPO, ".chipbench_work", cell["name"])
    os.makedirs(work_dir, exist_ok=True)
    job = ServeSALARun(cell, args.seed, 1.0, False, work_dir)
    not_correct = True
    for i, which in enumerate(sorted(set(args.control), key=order.index)):
        job.window_only = which == "window-only"
        job.ref_act_dtype = jnp.float8_e4m3fn if which == "fp8" else None
        job.ref_state_dtype = (jnp.bfloat16 if which == "bf16-state"
                               else None)
        if i == 0:
            job.setup(t0)
        else:
            job.check = job.check_against_reference()
        chk = {k: v for k, v in job.check.items()
               if k not in ("prompt_lengths", "row_diffs_ulps")}
        print(json.dumps({"control": which, "seed": args.seed,
                          "reference_check": chk}), flush=True)
        not_correct &= chk["ok"] is False
    return 0 if not_correct else 1


if __name__ == "__main__":
    import sys
    sys.exit(control())
