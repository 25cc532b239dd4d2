"""GPT-2 trunk tuning matrix (run on the real chip).

Round-2/3 established: loss path fused (+3%), flash attention tuned (+17%),
and the remaining gap to 50% MFU lives in the trunk (BENCH_NOTES.md r3:
head-free ceiling 128k tok/s). This script A/Bs the remaining trunk knobs
and prints one JSON line per variant:

  - ln:    xla composed layer norm vs the fused Pallas kernel (25 norms/step)
  - attn:  flash (default) sanity point vs xla composed
  - remat: per-block jax.checkpoint (the memory knob's throughput cost)
  - donate: buffer donation on/off (should be ~free, catches regressions)

Usage: python experiments/gpt2_tune.py [--steps 20] [--batch 8] [--seq 1024]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def measure(variant: dict, batch: int, seq: int, steps: int,
            tiny: bool = False) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from nezha_tpu import optim
    from nezha_tpu.models.gpt2 import GPT2, GPT2Config, lm_loss
    from nezha_tpu.tensor import bf16_policy
    from nezha_tpu.train.loop import init_train_state, make_train_step

    small = dict(vocab_size=256, max_positions=max(seq, 64), num_layers=2,
                 num_heads=4, hidden_size=64) if tiny else {}
    cfg = GPT2Config(fused_loss_chunk=-1, **small, **variant.get("cfg", {}))
    model = GPT2(cfg, policy=bf16_policy())
    opt = optim.adamw(6e-4, weight_decay=0.1)
    state = init_train_state(model, opt, jax.random.PRNGKey(0))
    step = make_train_step(model, opt, lm_loss,
                           donate=variant.get("donate", True))

    tokens = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (batch, seq + 1)).astype(np.int32)
    b = {"tokens": jnp.asarray(tokens)}

    # bench.py's timing discipline (median-of-5 windows, host-fetch
    # barriers) — the levers here are few-% items, smaller than one-window
    # excursions.
    from bench import _time_steps
    sps, spread = _time_steps(step, state, b, steps, 60.0)
    tps = batch * seq * sps
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(
        state["variables"]["params"]))
    flops = (6 * n_params + 6 * cfg.num_layers * cfg.hidden_size * seq) \
        * batch * seq
    return {"variant": variant["name"], "tokens_per_sec": round(tps, 1),
            "mfu": round(flops * sps / 197e12, 4),
            "spread": round(spread, 4)}


VARIANTS = [
    {"name": "baseline"},
    {"name": "ln_pallas", "cfg": {"ln_impl": "pallas"}},
    {"name": "scan", "cfg": {"scan_layers": True}},  # one-block trunk scan
    {"name": "attn_xla", "cfg": {"attn_impl": "xla"}},
    {"name": "remat", "cfg": {"remat": True}},  # cost of the memory knob
    {"name": "no_donate", "donate": False},
]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--variants", nargs="+", default=None,
                    choices=[v["name"] for v in VARIANTS])
    ap.add_argument("--tiny", action="store_true",
                    help="seconds-scale model (CPU smoke of the harness; "
                         "numbers are meaningless)")
    args = ap.parse_args()
    if args.tiny:
        # Pin the CPU backend BEFORE any jax call (same pattern as
        # tests/conftest.py).
        import jax
        jax.config.update("jax_platforms", "cpu")
    from nezha_tpu.utils import enable_persistent_compile_cache
    enable_persistent_compile_cache()
    for v in VARIANTS:
        if args.variants and v["name"] not in args.variants:
            continue
        print(json.dumps(measure(v, args.batch, args.seq, args.steps,
                                 tiny=args.tiny)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
