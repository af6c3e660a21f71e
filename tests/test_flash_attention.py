"""Pallas flash attention vs dense reference (forward + gradients), run in
interpreter mode on CPU; ``chip_smoke.py``'s flash leg compiles the same
kernels on the chip."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from serverless_learn_tpu.ops.attention import xla_attention
from serverless_learn_tpu.ops.pallas.flash_attention import flash_attention


def _qkv(seed, B, T, H, D, K=None, dtype=jnp.float32):
    K = K or H
    rng = jax.random.PRNGKey(seed)
    q = jax.random.normal(rng, (B, T, H, D), dtype)
    k = jax.random.normal(jax.random.fold_in(rng, 1), (B, T, K, D), dtype)
    v = jax.random.normal(jax.random.fold_in(rng, 2), (B, T, K, D), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_dense(causal):
    q, k, v = _qkv(0, 2, 256, 2, 64)
    ref = xla_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_gqa():
    q, k, v = _qkv(1, 1, 256, 8, 32, K=2)
    ref = xla_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads_match_dense(causal):
    q, k, v = _qkv(2, 1, 256, 2, 32)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=causal) ** 2).sum()

    def loss_ref(q, k, v):
        return (xla_attention(q, k, v, causal=causal) ** 2).sum()

    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_fl, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3)


def test_explicit_flash_raises_and_auto_takes_xla_on_untileable_shapes():
    """seq 100 (and 4100) is no multiple of a block size: an explicit flash
    request raises, and ``auto`` (above its threshold) asks the kernel's
    own tileability test and runs dense attention instead."""
    from serverless_learn_tpu.ops.attention import dot_product_attention

    q, k, v = _qkv(3, 1, 100, 2, 16)
    with pytest.raises(ValueError, match="not multiples of a block size"):
        flash_attention(q, k, v, causal=True)
    with pytest.raises(ValueError, match="not multiples of a block size"):
        dot_product_attention(q, k, v, causal=True, impl="flash")
    general = jnp.ones((1, 1, 128, 128), jnp.int32)  # not a key-padding row
    q2, k2, v2 = _qkv(4, 1, 128, 2, 16)
    with pytest.raises(ValueError, match="key-padding row"):
        flash_attention(q2, k2, v2, mask=general)
    ql, kl, vl = _qkv(5, 1, 4100, 1, 8)
    out = dot_product_attention(ql, kl, vl, causal=True, impl="auto")
    ref = xla_attention(ql, kl, vl, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("mesh_kw", [dict(dp=8), dict(dp=4, tp=2)])
def test_flash_sharded_train_step_matches_xla(devices, mesh_kw):
    """Under a live mesh, flash runs shard_mapped (batch/heads local) and
    must reproduce the GSPMD-partitioned dense path."""
    from serverless_learn_tpu.config import (
        DataConfig, ExperimentConfig, MeshConfig, OptimizerConfig, TrainConfig)
    from serverless_learn_tpu.data.datasets import SyntheticSource
    from serverless_learn_tpu.training.train_step import build_trainer

    def run(impl):
        cfg = ExperimentConfig(
            model="llama_tiny",
            model_overrides={"attention_impl": impl, "dtype": jnp.float32,
                             "max_seq_len": 128},
            mesh=MeshConfig(**mesh_kw),
            optimizer=OptimizerConfig(name="sgd", learning_rate=0.1),
            train=TrainConfig(batch_size=16, num_steps=2),
            data=DataConfig(seq_len=128),
        )
        trainer = build_trainer(cfg)
        state = trainer.init()
        src = SyntheticSource(trainer.bundle.make_batch, cfg.data, 16, seed=9)
        batch = trainer.shard_batch(next(iter(src)))
        losses = []
        for _ in range(2):
            state, metrics = trainer.step(state, batch)
            losses.append(float(jax.device_get(metrics["loss"])))
        return losses

    np.testing.assert_allclose(run("xla"), run("flash"), rtol=2e-5)


def test_flash_inside_pipeline_stage(devices):
    """flash inside a GPipe stage (enclosing shard_map) must run its local
    kernel instead of nesting shard_map over the same mesh (trace error)."""
    from serverless_learn_tpu.config import (
        DataConfig, ExperimentConfig, MeshConfig, OptimizerConfig, TrainConfig)
    from serverless_learn_tpu.data.datasets import SyntheticSource
    from serverless_learn_tpu.training.train_step import build_trainer

    cfg = ExperimentConfig(
        model="llama_tiny",
        model_overrides={"attention_impl": "flash", "dtype": jnp.float32,
                         "max_seq_len": 128, "pipeline": True,
                         "pipeline_microbatches": 2, "n_layers": 4},
        mesh=MeshConfig(dp=4, pp=2),
        optimizer=OptimizerConfig(name="sgd", learning_rate=0.1),
        train=TrainConfig(batch_size=16, num_steps=1),
        data=DataConfig(seq_len=128),
    )
    trainer = build_trainer(cfg)
    state = trainer.init()
    src = SyntheticSource(trainer.bundle.make_batch, cfg.data, 16, seed=2)
    state, metrics = trainer.step(state, trainer.shard_batch(next(iter(src))))
    assert np.isfinite(float(jax.device_get(metrics["loss"])))


def test_transformer_with_flash_impl():
    """llama_tiny forward with attention_impl='flash' (seq 256) matches the
    default dense implementation."""
    from serverless_learn_tpu.models.registry import get_model
    from serverless_learn_tpu.config import DataConfig

    b_flash = get_model("llama_tiny", attention_impl="flash",
                        dtype=jnp.float32, max_seq_len=256)
    b_dense = get_model("llama_tiny", dtype=jnp.float32, max_seq_len=256)
    import numpy as onp

    rng = onp.random.default_rng(0)
    batch = b_dense.make_batch(rng, DataConfig(seq_len=256), 2)
    params = b_dense.module.init(jax.random.PRNGKey(0), batch["tokens"])["params"]
    l_dense, _ = b_dense.loss_fn(params, batch)
    l_flash, _ = b_flash.loss_fn(params, batch)
    np.testing.assert_allclose(float(l_dense), float(l_flash), rtol=1e-4)


# ---------------------------------------------------------------------------
# PR 32: which blocks run which path
# ---------------------------------------------------------------------------


def _dense_census(T, S, bq, bk, causal, vlen, mode):
    """Skipped / interior / masked blocks counted from the dense mask."""
    keys = np.ones((T, S), bool)
    if causal:
        keys &= np.arange(S)[None, :] <= np.arange(T)[:, None]
    if vlen is not None:
        keys &= np.arange(S)[None, :] < vlen
    live = keys.copy()
    if mode == "len":  # self-attention: padding queries are not computed
        live &= np.arange(T)[:, None] < vlen
    out = {"skipped": 0, "interior": 0, "masked": 0}
    for i in range(T // bq):
        for j in range(S // bk):
            blk = np.s_[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk]
            if not live[blk].any():
                out["skipped"] += 1
            elif mode != "rows" and keys[blk].all():
                out["interior"] += 1
            else:
                out["masked"] += 1
    return out


@pytest.mark.parametrize("T,S,bq,bk", [
    (512, 512, 128, 256), (512, 512, 256, 128), (512, 1024, 128, 128),
    (1024, 512, 512, 512)])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("vlen,mode", [
    (None, "none"), (None, "rows"), (300, "len"), (256, "len"), (100, "len"),
    (0, "len"), (300, "klen"), (512, "klen")])
def test_block_census_counts_what_the_dense_mask_holds(T, S, bq, bk, causal,
                                                       vlen, mode):
    from serverless_learn_tpu.ops.pallas.flash_attention import block_census

    got = block_census(T, S, bq, bk, causal, vlen, mask_mode=mode)
    assert got == _dense_census(T, S, bq, bk, causal, vlen, mode)
    assert sum(got.values()) == (T // bq) * (S // bk)


def test_block_census_of_the_training_cell():
    """mistral7b-lora-train-4k: T = S = 4,096 causal in the blocks
    ``_pick_block`` gives. Of 64 blocks a (batch row, head) skips 28, runs
    28 with no mask and 8, the diagonal, with it."""
    from serverless_learn_tpu.ops.pallas.flash_attention import (
        _pick_block, block_census)

    b = _pick_block(4096)
    assert b == 512
    assert block_census(4096, 4096, b, b, True) == {
        "skipped": 28, "interior": 28, "masked": 8}
    assert block_census(4096, 4096, b, b, False) == {
        "skipped": 0, "interior": 64, "masked": 0}


@pytest.mark.parametrize("causal,vlen", [(True, None), (False, 700),
                                         (True, 700)])
def test_interior_scores_equal_masked_scores_to_the_bit(causal, vlen):
    """On a block that ``_block_kind`` calls interior, the path without
    iota, compare and select returns the bits of the path with them:
    scores and probabilities."""
    from serverless_learn_tpu.ops.pallas import flash_attention as fa

    bq, bk, i, j = 128, 256, 2, 0
    active, interior = fa._block_kind(i, j, bq, bk, causal, "len" if vlen
                                      else "none", vlen)
    assert active and interior
    rng = jax.random.PRNGKey(5)
    q = jax.random.normal(rng, (bq, 64), jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(rng, 1), (bk, 64), jnp.bfloat16)
    ref = jax.random.normal(jax.random.fold_in(rng, 2), (bq, 1))
    kw = dict(q0=i * bq, k0=j * bk, causal=causal, vlen=vlen)
    s_int = fa._scores(q, k, 0.125, False, **kw)
    s_msk = fa._scores(q, k, 0.125, True, **kw)
    np.testing.assert_array_equal(np.asarray(s_int), np.asarray(s_msk))
    np.testing.assert_array_equal(np.asarray(fa._probs(s_int, ref, False)),
                                  np.asarray(fa._probs(s_msk, ref, True)))
    # and one block to the right the causal mask bites: not interior
    assert not fa._block_kind(0, 0, bq, bk, True, "none", None)[1]
