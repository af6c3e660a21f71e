"""A model with recurrent layers behind the continuous engine: a tiny
hybrid (Mamba, Mamba, attention, Mamba) whose slots hold a recurrent
state and carried convolution inputs beside the paged pool.

CPU, float32. The plain reference is the benchmark's
(``chipbench/arch/hybrid_ssm.py``: the recurrence token by token, nothing
of the program imported), at this test's sizes.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import cell as cell_mod
from chipbench import weights
from serverless_learn_tpu.config import KVCacheConfig
from serverless_learn_tpu.inference import kvcache
from serverless_learn_tpu.inference.continuous import (
    ContinuousBatchingEngine, _Request)
from serverless_learn_tpu.inference.generate import generate, init_cache
from serverless_learn_tpu.models.registry import get_model
from serverless_learn_tpu.telemetry import MetricsRegistry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "chipbench")
VOCAB = 256


@pytest.fixture(scope="module")
def arch():
    return cell_mod.load_arch("hybrid_ssm", BENCH)


@pytest.fixture(scope="module")
def tiny_config():
    """The published configuration's keys at a test's sizes."""
    with open(os.path.join(BENCH, "configs",
                           "granite-4.0-h-micro-serve.json")) as f:
        published = json.load(f)
    return dict(
        published, hidden_size=64, shared_intermediate_size=128,
        num_hidden_layers=4,
        layer_types=["mamba", "mamba", "attention", "mamba"],
        num_attention_heads=4, num_key_value_heads=2, mamba_n_heads=8,
        mamba_d_head=16, mamba_d_state=16, mamba_chunk_size=8,
        vocab_size=VOCAB, max_position_embeddings=64)


@pytest.fixture(scope="module")
def hybrid(devices, arch, tiny_config):
    """(module, the program's tree of seeded weights, canonical weights,
    sizes): the benchmark's own weights, so that program and reference
    hold the same values."""
    sz = arch.sizes(tiny_config)
    w = weights.make_weights(arch, sz, weights.seed_u32(11), jnp.float32)
    module = get_model(
        "granite_4_0_h_micro", dtype=jnp.float32, param_dtype=jnp.float32,
        **arch.model_overrides(tiny_config)).module
    return module, arch.to_program_tree(w), w, sz


def _engine(module, params, max_slots=2, **kw):
    kv = kw.pop("kv", None) or KVCacheConfig(block_size=4, prefill_chunk=4)
    return ContinuousBatchingEngine(
        module, params, max_slots=max_slots, chunk_size=4, kv=kv,
        registry=MetricsRegistry(), **kw)


def _prompts(n, lo=5, hi=19, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(0, VOCAB, int(length))]
            for length in rng.integers(lo, hi, n)]


def _reference_logits(arch, w, sz, tokens):
    return np.asarray(arch.head(w, arch.trunk(
        w, jnp.asarray([tokens], jnp.int32), sz), sz)[0])


def _solo(module, params, prompt, n):
    toks = generate(module, params, jnp.asarray([prompt], jnp.int32), n)
    return [int(t) for t in jax.device_get(toks)[0][len(prompt):]]


def test_chunked_prefill_then_decode_gives_the_references_logits(
        hybrid, arch):
    """The serving twin of the module itself, slot leaves beside pages: a
    prompt fed in three uneven pieces (right-padded, with its lengths),
    then token by token, against ONE full forward of the reference."""
    module, params, w, sz = hybrid
    tokens = _prompts(1, 30, 31)[0]
    ref = _reference_logits(arch, w, sz, tokens)
    paged = kvcache.paged_module(module, 4, 16)
    pages, ci = kvcache.split_cache(init_cache(paged, 1))
    tbl = jnp.asarray(kvcache.sequential_table(1, 16, 16))

    def feed(piece, **mode):
        nonlocal pages, ci
        logits, upd = paged.apply(
            {"params": params,
             "cache": kvcache.with_tables(pages, tbl, ci)},
            jnp.asarray(piece), mutable=["cache"], **mode)
        pages, ci = kvcache.split_cache(upd["cache"])
        return np.asarray(logits[0])

    got, lo = [], 0
    for hi in (7, 8, 21):
        piece = np.zeros((1, 16), np.int32)
        piece[0, :hi - lo] = tokens[lo:hi]
        got.append(feed(piece, extend=True,
                        seq_lengths=jnp.asarray([hi - lo]))[:hi - lo])
        lo = hi
    for t in range(21, 30):
        got.append(feed([[tokens[t]]], decode=True))
    np.testing.assert_allclose(np.concatenate(got), ref, rtol=1e-4,
                               atol=1e-4)


def test_the_engines_tokens_are_the_references_and_solo_generates(
        hybrid, arch):
    """Six requests over two slots, prompts of one to five prefill
    chunks: every served token is the reference's own greedy choice at
    its position (its reference logit within 1e-4 of the best), and the
    reply is what solo ``generate`` gives."""
    module, params, w, sz = hybrid
    eng = _engine(module, params)
    try:
        for prompt in _prompts(6):
            served = eng.submit(prompt, 9, 0.0, 0, None, 0)["new_tokens"]
            assert served == _solo(module, params, prompt, 9)
            ref = _reference_logits(arch, w, sz, prompt + served)
            at = ref[len(prompt) - 1:-1]
            gap = at.max(-1) - at[np.arange(9), served]
            assert gap.max() <= 1e-4, gap
    finally:
        eng.stop()


def test_a_slots_second_request_gets_what_a_fresh_engine_gives(hybrid):
    """One slot: the second request finds the first one's state in the
    slot's rows, and starts from zero all the same."""
    module, params, _, _ = hybrid
    first, second = _prompts(2, seed=3)
    eng = _engine(module, params, max_slots=1)
    try:
        eng.submit(first, 10, 0.0, 0, None, 0)
        reused = eng.submit(second, 10, 0.0, 0, None, 0)["new_tokens"]
        assert eng.state_resets_total == 2
    finally:
        eng.stop()
    fresh = _engine(module, params, max_slots=1)
    try:
        assert reused == fresh.submit(second, 10, 0.0, 0, None,
                                      0)["new_tokens"]
    finally:
        fresh.stop()
    assert reused == _solo(module, params, second, 10)


class _Sink:
    def __init__(self):
        self.records = []

    def emit(self, rec):
        self.records.append(rec)


def _by_hand(module, params, requests, **kw):
    """An engine whose dispatcher has stopped, stepped by the test until
    every request is answered. Returns (engine, requests, the (nb, T, W)
    key of every prefill program, the ``sched_iter`` records)."""
    sink = _Sink()
    eng = _engine(module, params, event_log=sink, **kw)
    eng.stop()
    keys, fetch = [], eng._paged_prefill_jit

    def counting(nb, T, W):
        keys.append((nb, T, W))
        return fetch(nb, T, W)

    eng._paged_prefill_jit = counting
    sent = [_Request(prompt=np.asarray(p, np.int32), max_new=n,
                     temperature=0.0, top_k=0, eos_id=None, seed=0)
            for p, n in requests]
    for r in sent:
        eng._q.put(r)
    seq = 0
    while not all(r.done.is_set() for r in sent):
        seq += 1
        assert seq < 400, "the scheduler made no progress"
        eng._iterate(seq)
    records = [r for r in sink.records if r.get("event") == "sched_iter"]
    return eng, sent, keys, records


def test_a_preempted_request_restarts_token_identical(hybrid):
    """A pool of one max-length sequence under four long requests: the
    youngest slots are preempted and re-admitted from their first token,
    onto a zeroed state; every reply is its solo run, and the records'
    ``state_resets`` sum to admissions plus re-admissions."""
    module, params, _, _ = hybrid
    requests = [(p, 24) for p in _prompts(4, 8, 9, seed=5)]
    eng, sent, _, records = _by_hand(
        module, params, requests, max_slots=4,
        kv=KVCacheConfig(block_size=4, num_blocks=16, prefill_chunk=4))
    assert eng.preemptions > 0, "a 16-block pool never felt pressure?"
    for r, (prompt, n) in zip(sent, requests):
        assert r.result["new_tokens"] == _solo(module, params, prompt, n)
    resets = sum(r["state_resets"] for r in records)
    assert resets == eng.state_resets_total == len(sent) + eng.preemptions
    assert eng._pool.free_blocks == 16


def test_one_row_chunk_a_slot_a_program_and_no_trie(hybrid):
    """A prompt of five chunks takes five programs of one row where a
    model of attention layers takes one program of five rows; the trie
    is off whatever the configuration asks for, and ``kv_stats`` says
    why and what the slots hold."""
    module, params, _, sz = hybrid
    long_prompt = _prompts(1, 18, 19, seed=7)[0]
    eng, _, keys, records = _by_hand(
        module, params, [(long_prompt, 3), (long_prompt[:6], 3)],
        kv=KVCacheConfig(block_size=4, prefill_chunk=4, prefix_cache=True))
    # Both slots prefill side by side, a row each; the longer goes on
    # alone. Never more rows than slots mid-prefill.
    assert keys == [(2, 4, 1), (2, 4, 4), (1, 4, 4), (1, 4, 4), (1, 4, 16)]
    assert sum(r["prefill_row_chunks"] for r in records) == 5 + 2
    assert all(r["prefill_row_chunks"] <= r["prefill_steps"] * 2
               for r in records)
    assert eng._trie is None
    st = eng.kv_stats()
    assert st["prefix_cache"].startswith("off: a page hit cannot restore")
    assert st["prefix_blocks_cached"] == 0 and "prefix_digest" not in st
    per_slot = 3 * (8 * 16 * 16 * 4 + 3 * (128 + 2 * 16) * 4)
    assert st["state_slots"] == 2
    assert st["state_bytes_per_slot"] == per_slot
    assert st["state_bytes"] == 2 * per_slot
    # The auto pool has no row of slack for a trie that is not there.
    assert st["blocks_total"] == 2 * 16


def test_an_attention_only_model_packs_and_keeps_its_trie(devices):
    """Packing and the trie are untouched for a model whose every layer
    attends: the same prompts dispatch the program keys they did before
    this engine knew slot leaves, ``state_resets`` stays 0, and
    ``kv_stats`` reports no slot state."""
    module = get_model("llama_tiny", dtype=jnp.float32,
                       param_dtype=jnp.float32, max_seq_len=64).module
    params = module.init(jax.random.PRNGKey(0),
                         jnp.zeros((1, 8), jnp.int32))["params"]
    long_prompt = _prompts(1, 18, 19, seed=7)[0]
    eng, sent, keys, records = _by_hand(
        module, params, [(long_prompt, 3), (long_prompt[:6], 3)],
        kv=KVCacheConfig(block_size=4, prefill_chunk=4, prefix_cache=True))
    # Rows are row-chunks: the long prompt's five chunks two to a program
    # (two slots), its last beside the short prompt's first. The keys are
    # those the parent commit (2675f4e) dispatches for these requests.
    assert keys == [(2, 4, 4), (2, 4, 4), (2, 4, 16), (1, 4, 4)]
    assert sum(r["prefill_row_chunks"] for r in records) == 7
    assert all(r["state_resets"] == 0 for r in records)
    assert eng._trie is not None and eng._trie.blocks_held > 0
    st = eng.kv_stats()
    assert st["prefix_cache"] == "on" and st["state_slots"] == 0
    assert st["state_bytes_per_slot"] == st["state_bytes"] == 0
    assert st["blocks_total"] == 2 * 16 + 16
    for r, n in zip(sent, (len(long_prompt), 6)):
        assert r.result["new_tokens"] == _solo(module, params,
                                               long_prompt[:n], 3)


def test_a_sentinel_slot_gathers_clipped_and_scatters_dropped():
    """``take_slots`` / ``put_slots`` on a tree of both kinds of leaf: a
    sentinel slot id reads some real row and writes nothing; a fresh row
    starts from zero; pool leaves pass through."""
    names = ("ssm_state", "conv_state")
    tree = {"layer_0": {"mamba": {
                "ssm_state": jnp.arange(12.0).reshape(4, 3),
                "conv_state": jnp.arange(8.0).reshape(4, 2)}},
            "layer_1": {"attn": {"pages_k": jnp.ones((5, 2)),
                                 "pages_v": jnp.ones((5, 2))}}}
    ids = jnp.asarray([2, 4, 0])            # 4 == max_slots: the sentinel
    rows = kvcache.take_slots(tree, names, ids,
                              fresh=jnp.asarray([False, False, True]))
    got = rows["layer_0"]["mamba"]["ssm_state"]
    np.testing.assert_array_equal(got, [[6, 7, 8], [9, 10, 11], [0, 0, 0]])
    assert rows["layer_1"]["attn"]["pages_k"] \
        is tree["layer_1"]["attn"]["pages_k"]
    new = jax.tree_util.tree_map(lambda a: a + 100.0, rows)
    back = kvcache.put_slots(tree, new, names, ids)
    np.testing.assert_array_equal(
        back["layer_0"]["mamba"]["ssm_state"],
        [[100, 100, 100], [3, 4, 5], [106, 107, 108], [9, 10, 11]])
    np.testing.assert_array_equal(back["layer_1"]["attn"]["pages_k"],
                                  np.full((5, 2), 101.0))
    assert kvcache.slot_bytes(tree, names) == (3 + 2) * 4
    assert kvcache.take_slots(tree, (), ids) is tree
