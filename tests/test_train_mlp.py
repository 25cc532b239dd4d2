"""End-to-end slice: MLP on MNIST, single process (BASELINE.json config 1).
The framework's first full train loop must demonstrably learn."""

import jax
import numpy as np
import pytest

from nezha_tpu import data, ops, optim
from nezha_tpu.models.mlp import MLP
from nezha_tpu.train.loop import Trainer, init_train_state, make_train_step


def _loss_fn(logits, batch):
    return ops.softmax_cross_entropy_with_integer_labels(logits, batch["label"])


def test_mlp_train_step_reduces_loss():
    model = MLP(hidden=(64, 64))
    opt = optim.momentum(0.1)
    state = init_train_state(model, opt, jax.random.PRNGKey(0))
    step = make_train_step(model, opt, _loss_fn)
    batches = data.mnist_batches(64, seed=0)
    losses = []
    for i, batch in zip(range(60), batches):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] * 0.7, losses[::10]


def test_trainer_fit_and_eval():
    model = MLP(hidden=(64,))
    opt = optim.momentum(0.1)
    trainer = Trainer(model, opt, _loss_fn, rng=jax.random.PRNGKey(1),
                      log_every=5)
    trainer.initialize()
    metrics = trainer.fit(data.mnist_batches(64, seed=1), steps=40)
    assert "loss" in metrics and np.isfinite(metrics["loss"])
    # Eval accuracy on synthetic MNIST should beat chance (10%) clearly.
    test_batch = next(data.mnist_batches(256, split="test"))
    logits, _ = model.apply(trainer.state["variables"], test_batch,
                            training=False)
    acc = float(ops.accuracy(logits, test_batch["label"]))
    assert acc > 0.3, acc


@pytest.mark.parametrize("placed_on", [1, 4])
def test_per_chip_rates_divide_by_the_devices_the_batch_is_on(placed_on):
    """On a host with more devices than the job uses (here 8 virtual
    ones) the *_per_chip rates divide by the devices the batch is placed
    on — 1 in single-device mode — not by jax.device_count()."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    assert jax.device_count() > 4
    shard_fn = None
    if placed_on > 1:
        sharding = NamedSharding(
            Mesh(np.array(jax.devices()[:placed_on]), ("dp",)), P("dp"))
        shard_fn = lambda batch: jax.device_put(batch, sharding)  # noqa: E731
    logged = []
    trainer = Trainer(MLP(hidden=(16,)), optim.momentum(0.1), _loss_fn,
                      rng=jax.random.PRNGKey(1), log_every=1,
                      shard_fn=shard_fn, examples_per_step=8,
                      tokens_per_step=8 * 28 * 28,
                      metric_logger=lambda step, m: logged.append(m))
    trainer.fit(data.mnist_batches(8, seed=1), steps=3)
    m = logged[-1]
    assert m["examples_per_sec"] > 0
    assert m["examples_per_sec_per_chip"] == pytest.approx(
        m["examples_per_sec"] / placed_on)
    assert m["tokens_per_sec_per_chip"] == pytest.approx(
        m["tokens_per_sec"] / placed_on)


def test_mnist_batches_shapes():
    b = next(data.mnist_batches(32))
    assert b["image"].shape == (32, 28, 28)
    assert b["label"].shape == (32,)
    assert b["image"].dtype == np.float32
