"""Fused (Pallas) softmax cross-entropy: must match optax exactly in value
and gradient, refuse an untileable vocab, and compose with the sharded train step."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from serverless_learn_tpu.ops.pallas.cross_entropy import (
    fused_cross_entropy_with_integer_labels)


def _ref(logits, labels):
    return optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), labels)


@pytest.mark.parametrize("shape,v", [((4, 16), 512), ((3, 7), 1024), ((21,), 512)])
def test_matches_optax_forward_and_grad(devices, shape, v):
    key = jax.random.PRNGKey(0)
    logits = jax.random.normal(key, (*shape, v), jnp.float32) * 3.0
    labels = jax.random.randint(key, shape, 0, v)
    got = fused_cross_entropy_with_integer_labels(logits, labels)
    np.testing.assert_allclose(np.asarray(got), np.asarray(_ref(logits, labels)),
                               rtol=1e-5, atol=1e-5)
    g_ref = jax.grad(lambda x: _ref(x, labels).mean())(logits)
    g_got = jax.grad(
        lambda x: fused_cross_entropy_with_integer_labels(x, labels).mean()
    )(logits)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-6)


def test_bf16_logits(devices):
    key = jax.random.PRNGKey(1)
    logits = (jax.random.normal(key, (8, 512)) * 2).astype(jnp.bfloat16)
    labels = jax.random.randint(key, (8,), 0, 512)
    got = fused_cross_entropy_with_integer_labels(logits, labels)
    np.testing.assert_allclose(np.asarray(got), np.asarray(_ref(logits, labels)),
                               rtol=1e-2, atol=1e-2)
    # grads keep the input dtype
    g = jax.grad(
        lambda x: fused_cross_entropy_with_integer_labels(x, labels).mean()
    )(logits)
    assert g.dtype == jnp.bfloat16


def test_untiled_vocab_raises(devices):
    """fused_ce=True is an explicit request: optax must not answer it."""
    key = jax.random.PRNGKey(2)
    logits = jax.random.normal(key, (4, 100), jnp.float32)
    labels = jax.random.randint(key, (4,), 0, 100)
    with pytest.raises(ValueError, match="cannot tile vocab 100"):
        fused_cross_entropy_with_integer_labels(logits, labels)


def test_fused_train_step_matches_unfused(devices):
    """llama_tiny, dp=8 mesh: fused loss must reproduce the standard step."""
    from serverless_learn_tpu.config import (
        DataConfig, ExperimentConfig, MeshConfig, OptimizerConfig, TrainConfig)
    from serverless_learn_tpu.data.datasets import SyntheticSource
    from serverless_learn_tpu.training.train_step import build_trainer

    def run(fused):
        cfg = ExperimentConfig(
            model="llama_tiny",
            model_overrides={"fused_ce": fused, "dtype": jnp.float32},
            mesh=MeshConfig(dp=8),
            optimizer=OptimizerConfig(name="sgd", learning_rate=0.1),
            train=TrainConfig(batch_size=16, num_steps=2),
            data=DataConfig(seq_len=16),
        )
        trainer = build_trainer(cfg)
        state = trainer.init()
        src = SyntheticSource(trainer.bundle.make_batch, cfg.data, 16, seed=5)
        batch = trainer.shard_batch(next(iter(src)))
        out = []
        for _ in range(2):
            state, metrics = trainer.step(state, batch)
            out.append(float(jax.device_get(metrics["loss"])))
        return out

    np.testing.assert_allclose(run(False), run(True), rtol=2e-5)
