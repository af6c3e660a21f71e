"""Flash attention padding masks + Pallas backward (VERDICT round 1 item 4).

Covers the two kernel-resident padding mechanisms (arbitrary [B, S] masks
and suffix-padding kv_lengths), their gradients (the backward is a pair of
Pallas kernels, not an XLA scan), GQA without KV expansion, and the proof
that a BERT train step with a padding mask actually executes the flash
path instead of silently falling back to dense (the round-1 gap)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from serverless_learn_tpu.ops.attention import xla_attention
from serverless_learn_tpu.ops.pallas.flash_attention import flash_attention


def _rand(rng, *shape):
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


def _suffix_mask(lens, T):
    return (np.arange(T)[None, :] < np.asarray(lens)[:, None]).astype(np.int32)


@pytest.fixture
def qkv():
    rng = np.random.default_rng(0)
    B, T, H, D = 2, 256, 4, 64
    return tuple(_rand(rng, B, T, H, D) for _ in range(3))


def _check_grads(f_flash, f_dense, args, weight, tol=2e-4):
    gf = jax.grad(lambda *a: (f_flash(*a) * weight).sum(),
                  tuple(range(len(args))))(*args)
    gx = jax.grad(lambda *a: (f_dense(*a) * weight).sum(),
                  tuple(range(len(args))))(*args)
    for name, a, b in zip("qkv", gf, gx):
        err = float(jnp.abs(a - b).max())
        assert err < tol, f"d{name} err {err}"
        assert not bool(jnp.isnan(a).any())


@pytest.mark.parametrize("how", ["rows", "len"])
def test_padding_parity_and_grads(qkv, how):
    q, k, v = qkv
    B, T = q.shape[:2]
    lens = [T, 100]  # one full row, one padded row (incl. an empty K block)
    mask2 = _suffix_mask(lens, T)
    m4 = jnp.asarray(mask2)[:, None, None, :]
    w = jnp.asarray(mask2)[:, :, None, None]  # score only valid queries
    kwargs = (dict(mask=m4) if how == "rows"
              else dict(kv_lengths=jnp.asarray(lens, jnp.int32)))

    o_f = flash_attention(q, k, v, **kwargs)
    o_x = xla_attention(q, k, v, mask=m4)
    assert float(jnp.abs((o_f - o_x) * w).max()) < 1e-5
    _check_grads(lambda *a: flash_attention(*a, **kwargs),
                 lambda *a: xla_attention(*a, mask=m4), (q, k, v), w)


@pytest.mark.parametrize("how", ["rows", "len"])
def test_padding_composes_with_causal(qkv, how):
    q, k, v = qkv
    T = q.shape[1]
    lens = [200, 100]
    mask2 = _suffix_mask(lens, T)
    m4 = jnp.asarray(mask2)[:, None, None, :]
    w = jnp.asarray(mask2)[:, :, None, None]
    kwargs = (dict(mask=m4) if how == "rows"
              else dict(kv_lengths=jnp.asarray(lens, jnp.int32)))
    o_f = flash_attention(q, k, v, causal=True, **kwargs)
    o_x = xla_attention(q, k, v, causal=True, mask=m4)
    assert float(jnp.abs((o_f - o_x) * w).max()) < 1e-5


def test_non_suffix_rows_mask_is_exact(qkv):
    """The rows path handles arbitrary (non-contiguous) key masks — the
    case kv_lengths must NOT be used for."""
    q, k, v = qkv
    B, T = q.shape[:2]
    rng = np.random.default_rng(3)
    mask2 = (rng.random((B, T)) < 0.7).astype(np.int32)
    mask2[:, 0] = 1  # every query keeps at least one valid key
    m4 = jnp.asarray(mask2)[:, None, None, :]
    o_f = flash_attention(q, k, v, mask=m4)
    o_x = xla_attention(q, k, v, mask=m4)
    assert float(jnp.abs(o_f - o_x).max()) < 1e-5
    _check_grads(lambda *a: flash_attention(*a, mask=m4),
                 lambda *a: xla_attention(*a, mask=m4), (q, k, v),
                 jnp.float32(1.0))


def test_gqa_with_padding_no_kv_expansion(qkv):
    q, _, _ = qkv
    rng = np.random.default_rng(1)
    B, T = q.shape[:2]
    kg, vg = _rand(rng, B, T, 2, 64), _rand(rng, B, T, 2, 64)
    lens = [T, 128]
    mask2 = _suffix_mask(lens, T)
    m4 = jnp.asarray(mask2)[:, None, None, :]
    w = jnp.asarray(mask2)[:, :, None, None]
    o_f = flash_attention(q, kg, vg, kv_lengths=jnp.asarray(lens, jnp.int32))
    o_x = xla_attention(q, kg, vg, mask=m4)
    assert float(jnp.abs((o_f - o_x) * w).max()) < 1e-5
    _check_grads(
        lambda *a: flash_attention(*a, kv_lengths=jnp.asarray(lens, jnp.int32)),
        lambda *a: xla_attention(*a, mask=m4), (q, kg, vg), w)


def test_float_masks_fall_back_to_dense(qkv):
    """A float mask could be additive (zeros mean KEEP); only bool/int
    masks may enter the kernel's nonzero-means-keep contract."""
    from serverless_learn_tpu.ops.pallas.flash_attention import as_kv_mask

    B, T = 2, 256
    assert as_kv_mask(jnp.ones((B, 1, 1, T), jnp.float32), B, T) is None
    assert as_kv_mask(jnp.ones((B, 1, T, T), jnp.int32), B, T) is None
    assert as_kv_mask(jnp.ones((B, 1, 1, T), jnp.int32), B, T) is not None
    assert as_kv_mask(jnp.ones((B, T), jnp.bool_), B, T) is not None


def test_bert_step_executes_flash_path(devices):
    """The round-1 gap: BERT always passes a padding mask, which silently
    forced dense attention. Prove the masked train-step now lowers through
    pallas_call (suffix_padding_mask contract -> kv_lengths path)."""
    from serverless_learn_tpu.config import (
        DataConfig, ExperimentConfig, MeshConfig, OptimizerConfig,
        TrainConfig)
    from serverless_learn_tpu.training.train_step import build_trainer

    cfg = ExperimentConfig(
        model="bert_tiny",
        model_overrides={"max_seq_len": 512},
        mesh=MeshConfig(dp=8),
        optimizer=OptimizerConfig(name="adamw", learning_rate=1e-4),
        train=TrainConfig(batch_size=8, dtype="float32",
                          param_dtype="float32"),
        data=DataConfig(seq_len=512))
    trainer = build_trainer(cfg)
    rng = np.random.default_rng(0)
    batch = trainer.bundle.make_batch(rng, cfg.data, 8)
    batch["attn_mask"][:, 400:] = 0  # suffix padding
    batch["mlm_mask"][:, 400:] = 0

    def loss(params):
        l, _ = trainer.bundle.loss_fn(params, batch)
        return l

    state = trainer.init()
    jaxpr = str(jax.make_jaxpr(jax.grad(loss))(state.params))
    assert "pallas_call" in jaxpr, \
        "masked BERT fwd+bwd must lower through the flash kernels"
    # and it trains without NaNs through the masked backward (jitted: the
    # eager op-by-op dispatch of this graph has aborted the CPU backend
    # with memory churn on the 8-device mesh)
    g = jax.jit(jax.grad(loss))(state.params)
    assert not any(bool(jnp.isnan(x).any())
                   for x in jax.tree_util.tree_leaves(g))


@pytest.mark.parametrize("T", [128, 256, 512])
@pytest.mark.parametrize("impl", ["auto", "xla", "flash"])
def test_dispatcher_honors_kv_lengths_alone(impl, T):
    """Round-3 verdict #5: every dispatch branch must honor kv_lengths even
    when the caller passes NO mask — in particular impl="xla" with T < 512,
    which previously ignored padding silently."""
    from serverless_learn_tpu.ops.attention import dot_product_attention

    rng = np.random.default_rng(7)
    B, H, D = 2, 4, 64
    q, k, v = (_rand(rng, B, T, H, D) for _ in range(3))
    lens = jnp.asarray([T, T // 3], jnp.int32)
    m4 = jnp.asarray(_suffix_mask([T, T // 3], T))[:, None, None, :]
    w = jnp.asarray(_suffix_mask([T, T // 3], T))[:, :, None, None]

    out = dot_product_attention(q, k, v, kv_lengths=lens, impl=impl)
    ref = xla_attention(q, k, v, mask=m4)
    assert float(jnp.abs((out - ref) * w).max()) < 1e-5, \
        f"impl={impl} T={T}: padding ignored on the dispatch path"


def test_fully_padded_row_is_nan_free(qkv):
    """A row with zero valid keys must produce output 0 and, with zero
    upstream gradient (the loss masks it), NaN-free input gradients."""
    q, k, v = qkv
    B, T = q.shape[:2]
    lens = [T, 0]
    mask2 = _suffix_mask(lens, T)
    w = jnp.asarray(mask2)[:, :, None, None]
    out = flash_attention(q, k, v, kv_lengths=jnp.asarray(lens, jnp.int32))
    assert float(jnp.abs(out[1]).max()) == 0.0
    g = jax.grad(lambda *a: (flash_attention(
        *a, kv_lengths=jnp.asarray(lens, jnp.int32)) * w).sum(),
        (0, 1, 2))(q, k, v)
    assert not any(bool(jnp.isnan(x).any()) for x in g)


# ---------------------------------------------------------------------------
# PR 32: skipped, interior and masked blocks in one call, block_q != block_k
# ---------------------------------------------------------------------------

_T, _BQ, _BK = 512, 128, 256  # 4 x 2 blocks: all three kinds under causal


def _mixed_qkv(seed=11, B=2, H=4, K=1, D=128):
    rng = np.random.default_rng(seed)
    return (_rand(rng, B, _T, H, D), _rand(rng, B, _T, K, D),
            _rand(rng, B, _T, K, D))


# second row's valid length: inside a K block, on a K block's edge, and
# shorter than one K block (so the row's only active block is masked).
_VLENS = {"inside": 300, "edge": 256, "short": 100}


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("mode,vlen", [
    ("none", None), ("rows", "inside"), ("len", "inside"), ("len", "edge"),
    ("len", "short")])
def test_mixed_blocks_forward_and_grads(mode, vlen, causal):
    """Forward and all three gradients against dense attention where the
    grid holds skipped, interior and masked blocks at once, with GQA 4:1,
    D 128 and block_q != block_k."""
    q, k, v = _mixed_qkv()
    lens = [_T, _VLENS[vlen]] if vlen else [_T, _T]
    mask2 = _suffix_mask(lens, _T)
    m4 = jnp.asarray(mask2)[:, None, None, :]
    w = jnp.asarray(mask2)[:, :, None, None]
    kwargs = dict(causal=causal, block_q=_BQ, block_k=_BK)
    if mode == "rows":
        kwargs["mask"] = m4
    elif mode == "len":
        kwargs["kv_lengths"] = jnp.asarray(lens, jnp.int32)
    dense = dict(causal=causal, mask=None if mode == "none" else m4)
    o_f = flash_attention(q, k, v, **kwargs)
    o_x = xla_attention(q, k, v, **dense)
    assert float(jnp.abs((o_f - o_x) * w).max()) < 2e-5
    _check_grads(lambda *a: flash_attention(*a, **kwargs),
                 lambda *a: xla_attention(*a, **dense), (q, k, v), w,
                 tol=5e-4)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("vlen", ["inside", "edge", "short"])
def test_mixed_blocks_klen_with_lse(vlen, causal):
    """The ring hop's entry ("klen": the lengths describe the keys alone,
    every Q block computes), output and logsumexp and the gradients
    through both, against dense attention."""
    from serverless_learn_tpu.ops.pallas.flash_attention import (
        flash_with_lse_bhsd)

    q, k, v = (x.transpose(0, 2, 1, 3) for x in _mixed_qkv(B=1))
    n = _VLENS[vlen]
    lens = jnp.asarray([n], jnp.int32)
    keep = jnp.arange(_T)[None, :] < n
    if causal:
        keep = keep & (jnp.arange(_T)[None, :] <= jnp.arange(_T)[:, None])
    keep = jnp.broadcast_to(keep, (_T, _T))

    def dense(q, k, v):
        kk, vv = (jnp.repeat(x, q.shape[1] // x.shape[1], 1) for x in (k, v))
        s = jnp.einsum("bhtd,bhsd->bhts", q, kk) * q.shape[-1] ** -0.5
        s = jnp.where(keep, s, -jnp.inf)
        lse = jax.nn.logsumexp(s, -1)
        return jnp.einsum("bhts,bhsd->bhtd", jnp.exp(s - lse[..., None]),
                          vv), lse

    def flash(q, k, v):
        return flash_with_lse_bhsd(q, k, v, lens, "klen", causal, _BQ, _BK,
                                   True)

    (o_f, l_f), (o_x, l_x) = flash(q, k, v), dense(q, k, v)
    assert float(jnp.abs(o_f - o_x).max()) < 2e-5
    assert float(jnp.abs(l_f - l_x).max()) < 2e-5

    def scalar(f):
        return lambda *a: sum((x ** 2).sum() for x in f(*a))

    gf = jax.grad(scalar(flash), (0, 1, 2))(q, k, v)
    gx = jax.grad(scalar(dense), (0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gx):
        assert float(jnp.abs(a - b).max()) < 5e-4, name


def test_interior_and_masked_kernels_agree():
    """A call whose every block is interior (lengths = the whole row) and
    the same call on the masked path (a "rows" mask of ones) agree to
    rounding, forward and backward: what the interior path leaves out
    changes nothing where nothing can be masked. (The two paths' scores
    and probabilities are the same bits, which
    ``test_interior_scores_equal_masked_scores_to_the_bit`` pins op by op;
    whole kernels differ in the last place on the CPU, whose compiler
    contracts ``qk * scale - m`` into one rounding on the path that has no
    select in between.)"""
    q, k, v = _mixed_qkv(B=1)
    ones = jnp.ones((1, _T), jnp.int32)
    full = jnp.asarray([_T], jnp.int32)
    kw = dict(block_q=_BQ, block_k=_BK)

    def run(**how):
        f = lambda *a: flash_attention(*a, **kw, **how)
        return (f(q, k, v),) + jax.grad(
            lambda *a: (f(*a) ** 2).sum(), (0, 1, 2))(q, k, v)

    for a, b in zip(run(kv_lengths=full), run(mask=ones)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0,
                                   atol=2e-6)
