"""The expert layer and the window layers that serving runs
(``ops/moe.py`` ``route`` / ``dropless_experts``, ``models/transformer.py``
``Attention`` kinds), at a test's size on the CPU, in float32: the
dropless layer against routing each token by hand, a batch whose every
token picks the same experts among them; window layers through the paged
pool, with a table window wider than the pages a window touches and with
one that is not, against a band mask over the whole sequence; the
engine's count of the experts a decode step read."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from serverless_learn_tpu.inference import kvcache
from serverless_learn_tpu.inference.generate import init_cache
from serverless_learn_tpu.models.registry import get_model
from serverless_learn_tpu.ops import moe

F32 = dict(dtype=jnp.float32, param_dtype=jnp.float32)


@pytest.fixture(scope="module")
def tiny():
    """afmoe_tiny in float32 with a non-zero expert bias (seeded at the
    spacing of the scores, as the benchmark seeds it)."""
    module = get_model("afmoe_tiny", **F32).module
    params = module.init(jax.random.PRNGKey(0),
                         jnp.zeros((1, 8), jnp.int32))["params"]
    cfg = module.cfg
    for i in range(cfg.moe_dense_layers, cfg.n_layers):
        params[f"layer_{i}"]["moe"]["expert_bias"] = jax.random.normal(
            jax.random.PRNGKey(100 + i), (cfg.n_experts,)) / cfg.n_experts
    return module, params


def _dense_by_hand(x, gate_w, idx, wg, wu, wd):
    """Each token through its own k experts, one at a time."""
    out = np.zeros(x.shape, np.float64)
    for n in range(x.shape[0]):
        for j in range(idx.shape[1]):
            e = int(idx[n, j])
            h = x[n] @ wg[e]
            h = h / (1 + np.exp(-h)) * (x[n] @ wu[e])
            out[n] += gate_w[n, j] * (h @ wd[e])
    return out


@pytest.mark.parametrize("same", [False, True],
                         ids=["spread", "every_token_the_same_experts"])
def test_dropless_experts_match_routing_each_token_by_hand(same):
    N, D, F, E, K = 24, 16, 8, 6, 2
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(ks[0], (N, D))
    logits = jax.random.normal(ks[1], (N, E))
    bias = jnp.zeros((E,))
    if same:    # the bias alone decides: every token picks experts 4 and 1
        bias = bias.at[4].set(10.0).at[1].set(9.0)
    _, gate_w, idx = moe.route(logits, K, "sigmoid", bias, True, 2.5)
    if same:
        assert (np.asarray(idx) == [4, 1]).all()
    wg, wu = (jax.random.normal(k, (E, D, F)) for k in ks[2:4])
    wd = jax.random.normal(ks[4], (E, F, D))
    got = moe.dropless_experts(x, gate_w, idx, wg, wu, wd)
    want = _dense_by_hand(*(np.asarray(a, np.float64) for a in
                            (x, gate_w, idx, wg, wu, wd)))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


def test_route_sigmoid_bias_norm_and_scale():
    """The bias chooses and does not weigh; the weights are the chosen
    scores, renormalised, times the scale."""
    logits = jnp.array([[2.0, 1.0, 0.0, -1.0]])
    bias = jnp.array([0.0, 0.0, 0.0, 5.0])
    scores, w, idx = moe.route(logits, 2, "sigmoid", bias, True, 3.0)
    assert sorted(np.asarray(idx)[0].tolist()) == [0, 3]
    s = jax.nn.sigmoid(logits)[0]
    want = {0: s[0], 3: s[3]}
    total = s[0] + s[3]
    for j, e in enumerate(np.asarray(idx)[0]):
        assert float(w[0, j]) == pytest.approx(3.0 * want[int(e)] / total)
    _, w_raw, _ = moe.route(logits, 2, "sigmoid", bias, False, 1.0)
    assert float(w_raw.sum()) == pytest.approx(float(total))


def test_the_dropless_layer_matches_per_token_capacity_routing(tiny):
    """The serving path and the training path's capacity dispatch at
    groups of one token (capacity 1 a chosen expert: nothing drops)
    compute the same layer: the model's logits agree."""
    module, params = tiny
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, 256)
    twin = type(module)(dataclasses.replace(module.cfg, moe_group_size=1))
    full = twin.apply({"params": params}, tokens)
    cache = init_cache(module, 2)
    logits, _ = module.apply({"params": params, "cache": cache}, tokens,
                             prefill=True, mutable=["cache"])
    np.testing.assert_allclose(np.asarray(logits), np.asarray(full),
                               rtol=2e-5, atol=2e-5)


def _paged_logits(module, params, tokens, ps, width, chunk):
    """Prefill ``tokens`` [1, T] through a paged pool in chunks of
    ``chunk``, the engine's way, with a table window of ``width`` pages;
    returns the logits of every position."""
    pmod = kvcache.paged_module(module, ps, 64)
    pages, _ = kvcache.split_cache(init_cache(pmod, 1))
    tbl = jnp.arange(width, dtype=jnp.int32)[None]
    out, T = [], tokens.shape[1]
    for start in range(0, T, chunk):
        piece = tokens[:, start:start + chunk]
        cache = kvcache.with_tables(pages, tbl,
                                    jnp.array([start], jnp.int32))
        logits, upd = pmod.apply({"params": params, "cache": cache}, piece,
                                 extend=True, mutable=["cache"])
        pages, _ = kvcache.split_cache(upd["cache"])
        out.append(logits)
    return jnp.concatenate(out, axis=1)


@pytest.mark.parametrize("ps,width,chunk", [(4, 64, 8), (16, 4, 32)],
                         ids=["W_wider_than_the_window",
                              "W_no_wider_than_the_window"])
def test_window_layers_in_the_paged_pool_match_a_band_mask(tiny, ps, width,
                                                           chunk):
    """Window 16, 60 tokens. Pages of 4 and chunks of 8: a window layer
    can touch ceil((16 + 8 - 1) / 4) + 1 = 7 pages of the table's 64, so
    it gathers those 7 from a per-row start. Pages of 16 and chunks of
    32: it can touch 4, the table has 4, so only the mask differs. Both
    read what a band mask over the whole sequence (the model's own full
    forward) reads."""
    module, params = tiny
    tokens = jax.random.randint(jax.random.PRNGKey(5), (1, 60), 0, 256)
    twin = type(module)(dataclasses.replace(module.cfg, moe_group_size=1))
    full = twin.apply({"params": params}, tokens)
    got = _paged_logits(module, params, tokens, ps, width, chunk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(full),
                               rtol=2e-5, atol=2e-5)


def test_the_window_gathers_only_the_pages_it_can_touch(tiny):
    """Past the window a window layer's gather is of span pages, not of
    the table window: its keys are [B, span * ps, K, D]."""
    module, params = tiny
    seen = []
    real = jnp.take

    def spy(a, idx, axis=None, **kw):
        if axis == 0 and getattr(a, "ndim", 0) == 3:
            seen.append(tuple(idx.shape))
        return real(a, idx, axis=axis, **kw)

    tokens = jax.random.randint(jax.random.PRNGKey(6), (1, 8), 0, 256)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jnp, "take", spy)
        _paged_logits(module, params, tokens, 4, 64, 8)
    kinds = module.cfg.layer_types
    # k and v of each layer: window layers 7 pages, the full layer 64.
    assert seen == [(1, 7 if k == "window_rope" else 64)
                    for k in kinds for _ in "kv"]


def test_a_window_layer_sees_no_key_behind_its_window(tiny):
    """One window layer of 16: a token changed at position 0 moves the
    logits up to position 15 and none from 16 on."""
    module, params = tiny
    one = type(module)(dataclasses.replace(
        module.cfg, n_layers=1, layer_types=("window_rope",),
        moe_dense_layers=1))
    p1 = {k: v for k, v in params.items()
          if k in ("embedder", "lm_head", "norm_f", "layer_0")}
    a = jax.random.randint(jax.random.PRNGKey(7), (1, 40), 0, 256)
    b = a.at[0, 0].set((a[0, 0] + 1) % 256)
    la, lb = (one.apply({"params": p1}, t) for t in (a, b))
    np.testing.assert_array_equal(np.asarray(la[0, 16:]),
                                  np.asarray(lb[0, 16:]))
    assert not np.allclose(np.asarray(la[0, 15]), np.asarray(lb[0, 15]))


def test_the_engine_counts_the_experts_its_decode_steps_read(tiny):
    """Per harvested decode step and expert layer, the distinct experts
    its rows were routed to: one request of one row reads top_k experts a
    layer a step; the engine's model info says how many layers and
    experts there are."""
    from serverless_learn_tpu.config import KVCacheConfig
    from serverless_learn_tpu.inference.continuous import (
        ContinuousBatchingEngine)

    module, params = tiny
    cfg = module.cfg
    eng = ContinuousBatchingEngine(module, params, max_slots=2,
                                   chunk_size=4,
                                   kv=KVCacheConfig(block_size=4))
    try:
        r = eng.submit([5, 9, 11, 3], 9, temperature=0.0, top_k=0,
                       eos_id=None, seed=0)
        assert len(r["new_tokens"]) == 9
        kv = eng.kv_stats()
        assert kv["moe_layers"] == cfg.n_layers - cfg.moe_dense_layers == 4
        assert kv["n_experts"] == 8
        # 8 tokens after the first: two chunks of 4 steps, one row each.
        assert eng.chunks_run == 2
        assert eng.experts_read_total == 2 * 4 * 4 * cfg.moe_top_k
    finally:
        eng.stop()


def test_a_stopped_engine_frees_its_weights_when_dropped(tiny):
    """No compiled program of the engine holds the engine: once it is
    stopped and dropped, its weights go at once, by reference counts and
    without waiting for the cycle collector (the benchmark's reference
    runs on the same chip right after the window and needs their room)."""
    import gc
    import weakref

    from serverless_learn_tpu.config import KVCacheConfig
    from serverless_learn_tpu.inference.continuous import (
        ContinuousBatchingEngine)

    module, params = tiny
    own = jax.tree_util.tree_map(lambda a: a + 0, params)
    gone = weakref.ref(own["layer_2"]["moe"]["expert_gate"])
    eng = ContinuousBatchingEngine(module, own, max_slots=2, chunk_size=4,
                                   kv=KVCacheConfig(block_size=4))
    r = eng.submit([5, 9, 11], 6, temperature=0.0, top_k=0, eos_id=None,
                   seed=0)
    assert len(r["new_tokens"]) == 6 and eng.chunks_run >= 1
    eng.stop()
    gc.collect()
    gc.disable()
    try:
        del eng, own
        assert gone() is None
    finally:
        gc.enable()
