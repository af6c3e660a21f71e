"""The paged pool's leaves are ``[pages, page_size, kv_heads * head_dim]``.

Two guards, both without a chip:

* the paged branch of ``Attention`` alone, at head widths 64 and 128: the
  leaves' shape, and that ragged appends through the block table read back
  through the gather exactly what a plain ``[B, S, K, D]`` cache holds
  (a row past its window and a padded position land on the sentinel page
  and are dropped);
* compile-only, for a described v5e: the engine's decode chunk and one
  prefill program of a small hybrid model with KV heads of 64 and of a
  small dense model with heads of 128 hold no ``copy`` of a pool leaf.
  With leaves of ``[P, ps, K, D]`` and heads of 64 (half a lane tile) the
  compiler gave the scatter and the gather different layouts and re-laid
  the whole pool out in every decode step and every prefill program.
  Behind a fixture: only the worker that runs these tests loads the TPU
  compiler.
"""

import re
from unittest import mock

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from serverless_learn_tpu.config import KVCacheConfig
from serverless_learn_tpu.inference.continuous import (
    ContinuousBatchingEngine)
from serverless_learn_tpu.models.registry import get_model
from serverless_learn_tpu.models.transformer import (
    Attention, TransformerConfig)

# -- the paged branch alone ---------------------------------------------------

PS, PAGES, WINDOW, HEADS, KV_HEADS = 4, 12, 3, 4, 2


def _attention(head_dim: int):
    cfg = TransformerConfig(
        vocab_size=32, d_model=HEADS * head_dim, n_layers=1, n_heads=HEADS,
        n_kv_heads=KV_HEADS, d_ff=32, max_seq_len=PAGES * PS,
        position="none", dtype=jnp.float32, param_dtype=jnp.float32,
        kv_page_size=PS, kv_pages=PAGES)
    module = Attention(cfg)
    x = jnp.zeros((3, 1, cfg.d_model), jnp.float32)
    variables = module.init(jax.random.PRNGKey(0), x, extend=True)
    return cfg, module, variables["params"], variables["cache"]


def _append(module, params, cache, tbl, x, lens):
    """The cache after one ragged append: ``lens[b]`` real tokens of
    ``x[b]`` at row b's index, through the window ``tbl`` of the row's
    block table."""
    cache = dict(cache, page_tbl=jnp.asarray(tbl, jnp.int32))
    _, upd = module.apply(
        {"params": params, "cache": cache}, x, extend=True,
        mutable=["cache"], seq_lengths=jnp.asarray(lens, jnp.int32))
    return upd["cache"]


@pytest.mark.parametrize("head_dim", [64, 128])
def test_ragged_appends_read_back_what_a_plain_cache_holds(head_dim):
    cfg, module, params, cache = _attention(head_dim)
    K, D, S = KV_HEADS, head_dim, WINDOW * PS
    assert cache["pages_k"].shape == (PAGES, PS, K * D)
    assert cache["pages_v"].shape == (PAGES, PS, K * D)

    # Three rows over disjoint pages, in no order; row 2's table holds one
    # page and then the sentinel.
    tbl = np.array([[7, 2, 9], [4, 11, 0], [5, PAGES, PAGES]], np.int32)
    rng = np.random.default_rng(head_dim)
    project = lambda proj, x: np.asarray(nn.DenseGeneral(
        (K, D), use_bias=False).apply({"params": params[proj]}, x))
    # The plain cache: every token at its own position, as long as a row
    # may grow; what the table's window cannot hold is never written.
    plain = {"pages_k": np.zeros((3, 2 * S, K, D), np.float32),
             "pages_v": np.zeros((3, 2 * S, K, D), np.float32)}
    index = np.zeros(3, np.int64)
    # First append: rows of unequal length (row 1 is padding from its
    # position 2 on). Second, after it: row 1 writes over where its
    # padding was, row 0 runs two positions past its window of 12 (the
    # clipped table entry would put them over its positions 8 and 9) and
    # row 2 two positions onto the sentinel.
    for lens in ((7, 2, 3), (7, 3, 3)):
        x = jnp.asarray(rng.standard_normal((3, 7, cfg.d_model)), jnp.float32)
        for name, cached in plain.items():
            new = project({"pages_k": "k_proj", "pages_v": "v_proj"}[name], x)
            for b, n in enumerate(lens):
                cached[b, index[b]:index[b] + n] = new[b, :n]
        cache = _append(module, params, cache, tbl, x, lens)
        index += lens
        np.testing.assert_array_equal(cache["cache_index"], index)
    assert index[0] > S and index[2] > PS

    held = (np.arange(S)[None, :] < index[:, None]) \
        & (tbl[:, np.arange(S) // PS] < PAGES)
    assert held.sum() == 12 + 5 + 4
    safe = np.clip(tbl, 0, PAGES - 1)
    for name in ("pages_k", "pages_v"):
        pool = np.asarray(cache[name])
        # Read back as the branch's gather does: pages along axis 0, the
        # window flattened to positions, a token's row split into heads.
        got = pool[safe].reshape(3, S, K, D)
        np.testing.assert_array_equal(got[held], plain[name][:, :S][held])
        # And nothing else was written anywhere in the pool: the padded
        # positions, the row past its window and the sentinel's dropped.
        want = np.zeros_like(pool)
        for b, pos in zip(*np.nonzero(held)):
            want[tbl[b, pos // PS], pos % PS] = \
                plain[name][b, pos].reshape(K * D)
        np.testing.assert_array_equal(pool, want)


# -- compile-only, for a described v5e ----------------------------------------

_COPY = re.compile(r"= \w+\[([\d,]*)\]\S* copy\(")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to compile
        pytest.skip(f"no v5e topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _pool_copies(compiled, leaf: tuple) -> list:
    """The ``copy`` ops of the compiled text whose result is as large as a
    pool leaf and shaped like one, under any split of its minor dimension
    (``[P, ps, K * D]`` or ``[P, ps, K, D]``)."""
    out = []
    for m in _COPY.finditer(compiled.as_text()):
        dims = tuple(int(d) for d in m.group(1).split(",") if d)
        if dims[:2] == leaf[:2] and int(np.prod(dims[2:])) == leaf[2]:
            out.append(m.group(0))
    return out


# (registry model, overrides): KV heads of 64 behind Mamba layers, as
# Granite 4.0-H has them, and heads of 128 in a dense model, as Mistral's.
_MODELS = {
    "hybrid-heads-64": ("granite_hybrid_tiny", dict(
        d_model=256, n_heads=4, n_kv_heads=2, d_ff=512, max_seq_len=512)),
    "dense-heads-128": ("llama_tiny", dict(
        d_model=512, n_heads=4, n_kv_heads=2, d_ff=1024, n_layers=2,
        max_seq_len=512)),
}
SLOTS, CHUNK, T, W = 4, 8, 32, 16


@pytest.mark.parametrize("name", sorted(_MODELS))
def test_no_program_copies_a_pool_leaf(one_chip, name):
    model, overrides = _MODELS[name]
    module = get_model(model, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                       **overrides).module
    shaped = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        tree)
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    params = shaped(jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]))
    # An engine with no device state: only its jit factories are used.
    Engine = ContinuousBatchingEngine
    with mock.patch.object(Engine, "_init_state", lambda self: {}), \
            mock.patch.object(Engine, "_fingerprint_params",
                              staticmethod(lambda p: None)):
        eng = Engine(module, None, max_slots=SLOTS, chunk_size=CHUNK,
                     kv=KVCacheConfig(block_size=16, prefill_chunk=T))
    eng.stop()
    state = shaped(jax.eval_shape(lambda: Engine._init_state(eng)))
    cfg = eng._pmod.cfg
    leaf = (eng._pool.num_blocks, 16, cfg.kv_heads * cfg.head_dim)
    assert any(a.shape[0] == leaf[0]
               for a in jax.tree_util.tree_leaves(state["pages"]))
    i32 = lambda: s((SLOTS,), jnp.int32)
    with mock.patch("jax.default_backend", lambda: "tpu"):
        programs = {
            "decode chunk": eng._paged_chunk_jit(SLOTS, W).lower(
                params, state["pages"], state["vecs"],
                s((SLOTS, W), jnp.int32), i32()),
            "prefill program": eng._paged_prefill_jit(SLOTS, T, W).lower(
                params, state["pages"], state["vecs"],
                s((SLOTS, W), jnp.int32), i32(), s((SLOTS, T), jnp.int32),
                i32(), i32(), s((SLOTS,), jnp.bool_),
                s((SLOTS,), jnp.float32), i32(), i32(),
                s((SLOTS,), jnp.uint32), i32(), i32())}
        for what, lowered in programs.items():
            copies = _pool_copies(lowered.compile(), leaf)
            assert not copies, (
                f"{name}: the {what} re-lays the pool out: {copies}")
