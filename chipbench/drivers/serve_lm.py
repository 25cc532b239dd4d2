"""Serving cells of a model other than GPT-2: ``drivers/serve.py``'s run,
with the reference and the sizes taken from the configuration file.

The base driver names its reference in code (``chipbench.reference.gpt2``)
and reads GPT-2's head sizes; this one reads ``serve.reference`` (a module
of ``chipbench/reference/`` with ``logits_at(params, tokens, positions,
cfg, with_margins=True)``) and the published sizes the cost functions of
``trace/kernel_costs_mistral4.py`` need. Everything else (the stack built
through ``cli/serve.py::_build_stack``, the traffic, the window, the
counters) is the base driver's.

Two things are added around ``Engine.step``: the expert layer's counter
(``Engine.last_expert_load``, the token-expert pairs each held expert
computed, which the step program returns with its tokens) is summed into
counters and kept per step for the roofline readers; and ``ANNOTATIONS``
also keeps the program's own ``serve.*`` spans, so this cell's idle gaps
have the program's owners.

**The comparison that decides ``correct``** is the base driver's rule
(requests of the cell's own mix through the same scheduler, 32 of them
here; logits after
prefill and after the first 3 decode steps, teacher-forced with the
program's tokens, within ``LOGIT_TOL_ULPS`` bf16 ulps of the largest
reference logit: this model's own limit, see below) with one addition
that an expert layer forces.
Which experts a token uses is a discontinuity: where the router's logit
for the last expert chosen and the first one left out lie within rounding
of each other, a bf16 program and a float32 reference may each choose
differently and both be right, and the two results then differ by an
expert's whole output, not by rounding. The reference reports, for every
compared row, the least change of a router logit that would move an
expert held here into or out of the chosen set (the least over the
layers, in bf16 ulps of the logit). A row whose gap is
under ``ROUTER_MARGIN_ULPS`` is set aside, as the base rule sets aside a
greedy token where the reference's own top-2 margin is inside the
tolerance; every other row must agree, and at least ``MIN_COMPARED_ROWS``
rows must be left to compare.
"""

from __future__ import annotations

import importlib

import numpy as np

from chipbench import traffic as traffic_lib
from chipbench.drivers import serve as base
from chipbench.drivers.serve import CHECK_STEPS, clock

ANNOTATIONS = base.ANNOTATIONS + (
    "serve.sched.pass", "serve.sched.admit", "serve.sched.emit",
    "serve.engine.prefill", "serve.engine.dispatch", "serve.engine.wait")

# Both limits are set between two readings on the chip at the published
# widths (PERF.md section 6, PR 26, has the numbers): what the program
# gives over its seeds, and what the reference gives when its activations
# are rounded to fp8, the nearest precision below the bf16 the
# configuration states, which must come out as not correct.
# Logits: bf16 ulps (2**-8) of the largest reference logit. Six layers of
# 4,096-wide contractions with bf16 operands leave 5-6 ulps on rows whose
# routing is clear (GPT-2's twelve narrow layers leave 2-3 of
# checks.LOGIT_TOL_ULPS = 8); fp8 activations leave 45-95.
LOGIT_TOL_ULPS = 16
# Router margin, in bf16 ulps of the last chosen logit: over 13 seeds
# (1,280 rows) the program chose other experts than the reference on 183
# rows, 179 with margins under 5.6 ulps and four at 6.6-7.4; the limit
# leaves the largest reading 1.6 times of room.
ROUTER_MARGIN_ULPS = 12.0
# One row in seven or eight has so clear a margin at six layers, so four
# times the base driver's 8 requests are sent: 128 rows, of which at least
# 4 must be left to compare.
CHECK_REQUESTS = 32
MIN_COMPARED_ROWS = 4


def sizes_of(config: dict) -> dict:
    """What the cost functions need, from the configuration file's own
    (published) keys and its statement of the chip's share."""
    held = config["experts_held"]
    return {
        "hidden": config["hidden_size"],
        "heads": config["num_attention_heads"],
        "q_lora": config["q_lora_rank"], "kv_lora": config["kv_lora_rank"],
        "nope": config["qk_nope_head_dim"],
        "rope": config["qk_rope_head_dim"], "v_head": config["v_head_dim"],
        "expert_width": config["moe_intermediate_size"],
        "experts_routed": config["published"]["n_routed_experts"],
        "experts_held": held[1], "top_k": config["num_experts_per_tok"],
        "vocab_held": config["vocab_size"],
        "layers": config["num_hidden_layers"]}


class ServeLMRun(base.ServeRun):
    def build(self):
        super().build()
        self.obs.model = sizes_of(self.cell["config"])

    def _step(self, active):
        out = super()._step(active)
        load = self.engine.last_expert_load
        t1 = clock()
        if load is None or t1 < self.win[0]:
            return out
        touched, pairs = int(np.count_nonzero(load)), int(load.sum())
        obs = self.obs
        if not hasattr(obs, "lm_steps"):
            obs.lm_steps = []       # (time, touched experts, held pairs)
        obs.lm_steps.append((t1, touched, pairs))
        if self._sampling(t1):
            obs.count("moe_steps")
            obs.count("moe_rows", int(np.count_nonzero(active)))
            obs.count("moe_touched", touched)
            obs.count("moe_held_pairs", pairs)
            obs.count("lm_resident_tokens",
                      int(self.engine.host_positions[active].sum()))
            mean = load.mean(axis=1)
            if (mean > 0).all():
                obs.count("moe_load_max_over_mean_sum",
                          float((load.max(axis=1) / mean).mean()))
        return out

    def check_against_reference(self) -> dict:
        import jax.numpy as jnp

        config = self.cell["config"]
        ref = importlib.import_module(
            f"chipbench.reference.{config['serve']['reference']}")
        stream = traffic_lib.request_stream(self.traffic, self.seed + 7_919,
                                            self.vocab)
        recs = []
        for i in range(CHECK_REQUESTS):
            req = next(stream)
            req.index = -2000 - i
            req.max_new_tokens = min(req.max_new_tokens, CHECK_STEPS + 1)
            recs.append(self._submit(req, clock(), check=True))
        self.sched.run_until_idle()
        self.slot_rec.clear()

        # one shape for every request and every seed: the mix's longest
        # prompt plus the decoded tokens, in whole 512-row blocks
        longest = self.traffic["prompt"]["unique"]["max"] + CHECK_STEPS
        pad_to = min(-(-longest // 512) * 512, self.engine.cfg.max_len)
        # not jitted as a whole: the reference compiles one layer at a
        # time, so that it fits beside the loaded model
        fn = lambda p, t, pos: ref.logits_at(p, t, pos, config,
                                             with_margins=True)
        params = self.engine.variables["params"]
        got_rows, ref_rows, margins = [], [], []
        for rec in recs:
            chk = rec.check
            req, toks = chk["req"], chk["tokens"]
            k = len(chk["logits"])          # 1 prefill + decode steps
            if k < 2 or len(toks) < k - 1:
                return {"ok": False, "why": f"request {rec.rid} captured "
                        f"{k} logit rows, {len(toks)} tokens"}
            n = len(req.prompt)
            seq = np.zeros((1, pad_to), np.int32)
            seq[0, :n + k - 1] = req.prompt + toks[:k - 1]
            pos = np.minimum(np.arange(n - 1, n + CHECK_STEPS),
                             n - 2 + k)[None, :]
            want, margin = fn(params, jnp.asarray(seq), jnp.asarray(pos))
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
        facts = {"ok": finite and diff <= tol, "max_logit_diff": diff,
                 "logit_tol": tol, "rows": int(clear.sum())}
        facts.update(
            requests=len(recs), rows_in_all=int(len(clear)),
            rows_set_aside=int((~clear).sum()),
            router_margin_ulps=ROUTER_MARGIN_ULPS,
            # what was set aside, for the record: a row there that the
            # router did split reads an expert's output off, not rounding
            max_logit_diff_set_aside=(float(per_row[~clear].max())
                                      if (~clear).any() else None),
            row_diffs=[round(float(d), 4) for d in per_row],
            row_margins_ulps=[round(float(min(m, 9999.0)), 2)
                              for m in margin],
            prefix_cached_tokens=[r.check["cached"] for r in recs])
        return facts


def run(cell, args, work_dir, t_process0):
    job = ServeLMRun(cell, args.seed, float(args.seconds), bool(args.trace),
                     work_dir)
    job.setup(t_process0)
    return job, job.measure(float(args.seconds))
