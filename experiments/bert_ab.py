"""BERT A/B on the real chip: (1) fp32 dense logits vs the fused
bf16-logsumexp head (BertConfig.fused_loss_chunk=-1), (2) composed XLA
attention vs the non-causal Pallas flash kernel (BertConfig.attn_impl).

The fp32 [16,512,30522] logit tensor is ~1 GB written+read per step at the
bench geometry (GPT-2's identical fusion measured +3%); the S=512
bidirectional score tensors are ~100 MB/layer/direction (GPT-2's flash
measured +17% e2e at S=1024 causal). One JSON line per variant
(median-of-3 windows), same timing discipline as bench.py.

Usage: python experiments/bert_ab.py [--steps 10] [--tiny]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


VARIANTS = [
    # r2-r4 bench configuration (the 117.5k tok/s morning-of-r4 number)
    {"name": "dense_fp32", "cfg": {"fused_loss_chunk": 0,
                                   "attn_impl": "xla"}},
    # fused bf16-logit CE alone
    {"name": "fused", "cfg": {"fused_loss_chunk": -1, "attn_impl": "xla"}},
    # + non-causal flash attention (the new TPU default)
    {"name": "fused_flash", "cfg": {"fused_loss_chunk": -1,
                                    "attn_impl": "flash"}},
    # + scan-over-layers encoder (r5 trunk lever; parity-tested)
    {"name": "fused_flash_scan", "cfg": {"fused_loss_chunk": -1,
                                         "attn_impl": "flash",
                                         "scan_layers": True}},
    # + fused Pallas layer norms (26 norms/step at BERT-base geometry)
    {"name": "fused_flash_ln", "cfg": {"fused_loss_chunk": -1,
                                       "attn_impl": "flash",
                                       "ln_impl": "pallas"}},
]


def measure(variant: dict, steps: int, tiny: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from nezha_tpu import optim
    from nezha_tpu.models.bert import Bert, BertConfig, mlm_loss
    from nezha_tpu.tensor import bf16_policy
    from nezha_tpu.train.loop import init_train_state, make_train_step

    batch, seq = (2, 64) if tiny else (16, 512)
    kw = dict(num_layers=2) if tiny else {}
    cfg = BertConfig(**variant["cfg"], **kw)
    model = Bert(cfg, policy=bf16_policy())
    opt = optim.adamw(1e-4, weight_decay=0.01)
    state = init_train_state(model, opt, jax.random.PRNGKey(0))
    step = make_train_step(model, opt, mlm_loss)

    r = np.random.RandomState(0)
    tokens = r.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    labels = np.full_like(tokens, -100)
    mask = r.rand(batch, seq) < 0.15
    labels[mask] = tokens[mask]
    # No padding_mask: full-length batches; its all-True mask would force
    # composed-XLA attention off the flash path (BertConfig.attn_impl).
    b = {"tokens": jnp.asarray(tokens), "labels": jnp.asarray(labels),
         "segment_ids": jnp.zeros_like(jnp.asarray(tokens))}

    compiled = step.lower(state, b).compile()
    # Same timing discipline as bench.py (median-of-5 windows, host-fetch
    # barriers): the deltas measured here (+3%-ish) are smaller than the
    # one-window excursions bench.py documents.
    from bench import _time_steps
    # (state buffers are donated inside the timing loop — no further calls
    # on the original state are legal afterwards.)
    sps, spread = _time_steps(compiled, state, b, steps, 60.0)
    return {"variant": variant["name"],
            "tokens_per_sec": round(batch * seq * sps, 1),
            "spread": round(spread, 4)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--tiny", action="store_true",
                    help="CPU harness smoke (numbers meaningless)")
    args = ap.parse_args()
    if args.tiny:
        import jax
        jax.config.update("jax_platforms", "cpu")
    from nezha_tpu.utils import enable_persistent_compile_cache
    enable_persistent_compile_cache()
    for v in VARIANTS:
        print(json.dumps(measure(v, args.steps, args.tiny)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
