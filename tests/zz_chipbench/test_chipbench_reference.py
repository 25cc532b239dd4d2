"""The plain references against the program's models, tiny, on the CPU.

Tolerance: both sides compute in float32 here (the tiny presets use the
float32 policy), so they differ only by the order of float32 sums: 1e-5
absolute on logits of order 1 is two decimal digits above float32's
epsilon times the few hundred terms of a dot product, and a missing term
(a bias, a layer norm, the mask) would show at 1e-2 or more. On the chip
the program computes in bf16 and the rule in chipbench/checks.py applies.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import bert as ref_bert
from chipbench.reference import gpt2 as ref_gpt2

ATOL = 1e-5


@pytest.fixture(scope="module")
def tiny():
    from nezha_tpu.cli.train import _configs

    out = {}
    for name in ("gpt2_124m", "bert_base_zero1"):
        cfg = _configs()[name]
        for field, value in cfg.tiny.items():
            setattr(cfg, field, value)
        model = cfg.build_model()
        out[name] = (cfg, model, model.init(jax.random.PRNGKey(5)))
    return out


def test_gpt2_logits_and_loss_match_the_model(tiny):
    cfg, model, variables = tiny["gpt2_124m"]
    tokens = np.random.RandomState(1).randint(0, 512, (3, 65)).astype(np.int32)
    got, _ = model.apply(variables, tokens[:, :-1])
    want = ref_gpt2.logits(variables["params"], jnp.asarray(tokens[:, :-1]),
                           model.cfg.num_heads)
    assert float(jnp.abs(got - want).max()) < ATOL
    at = ref_gpt2.logits_at(variables["params"], jnp.asarray(tokens[:, :-1]),
                            jnp.asarray([[0, 7], [5, 63], [9, 9]]),
                            model.cfg.num_heads)
    assert float(jnp.abs(at[1, 1] - want[1, 63]).max()) < 1e-6
    total, n = ref_gpt2.lm_loss_sum(variables["params"], jnp.asarray(tokens),
                                    model.cfg.num_heads)
    loss = float(cfg.loss_fn(got, {"tokens": tokens}))
    assert abs(float(total) / n - loss) < ATOL


def test_bert_mlm_logits_and_loss_match_the_model(tiny):
    cfg, model, variables = tiny["bert_base_zero1"]
    batch = next(cfg.batches(4))
    got, _ = model.apply(variables, batch)
    args = [jnp.asarray(batch[k]) for k in ("tokens", "segment_ids")]
    want = ref_bert.mlm_logits(variables["params"], *args,
                               model.cfg.num_heads)
    assert float(jnp.abs(got - want).max()) < ATOL
    total, n = ref_bert.mlm_loss_sum(variables["params"], *args,
                                     jnp.asarray(batch["labels"]),
                                     model.cfg.num_heads)
    assert int(n) == int((batch["labels"] != -100).sum())
    assert abs(float(total) / int(n) - float(cfg.loss_fn(got, batch))) < ATOL
