"""Paged KV cache: allocator/trie primitives, block-table padding
semantics, and the engine's exactness suite (greedy + seeded, mixed slot
configs, chunked prefill, shared prefixes, exhaustion backpressure,
preemption).

The exactness bar: the engine must be byte-identical to solo ``generate``
(greedy), its seeded streams blind to page and chunk size, and a paged
``generate`` identical to the model's monolithic cache — paging changes
WHERE K/V live, never what attention reads.
"""

import copy
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from serverless_learn_tpu.config import ExperimentConfig, KVCacheConfig
from serverless_learn_tpu.inference import kvcache
from serverless_learn_tpu.inference.continuous import (
    ContinuousBatchingEngine)
from serverless_learn_tpu.inference.generate import generate, init_cache
from serverless_learn_tpu.inference.kvcache import (BlockPool,
                                                    KVBlocksExhausted,
                                                    PrefixTrie, pages_for)
from serverless_learn_tpu.models.registry import get_model
from serverless_learn_tpu.telemetry.registry import MetricsRegistry


# -- allocator / trie primitives (jax-free) ----------------------------------


def test_block_pool_alloc_refcount_exhaustion():
    pool = BlockPool(4, block_size=8)
    a = pool.alloc(3)
    assert len(a) == 3 and pool.free_blocks == 1
    # All-or-nothing: a failed alloc leaves the pool untouched.
    with pytest.raises(KVBlocksExhausted) as ei:
        pool.alloc(2)
    assert ei.value.need == 2 and ei.value.free == 1
    assert pool.free_blocks == 1
    # Sharing: a second ref keeps the block allocated through one decref.
    pool.incref(a[:1])
    assert pool.decref(a[:1]) == 0
    assert pool.decref(a[:1]) == 1
    assert pool.free_blocks == 2
    # Double-free is a typed error, not silent corruption.
    with pytest.raises(kvcache.KVCacheError):
        pool.decref(a[:1])
    assert pages_for(0, 8) == 0 and pages_for(1, 8) == 1
    assert pages_for(8, 8) == 1 and pages_for(9, 8) == 2


def test_prefix_trie_lookup_register_cow_evict():
    pool = BlockPool(16, block_size=4)
    trie = PrefixTrie(pool)
    prompt = list(range(10))  # 2 full blocks + remainder [8, 9]
    blocks = pool.alloc(3)
    assert trie.register(prompt, blocks[:2]) == 2  # full blocks only
    assert trie.blocks_held == 2
    assert pool.refcount(blocks[0]) == 2  # owner + trie
    # Full-prefix hit.
    hit = trie.lookup(prompt)
    assert hit.blocks == blocks[:2] and hit.tokens_matched == 8
    # Divergent mid-block: first full block matches, second diverges.
    other = [0, 1, 2, 3, 99, 98, 97, 96]
    hit = trie.lookup(other)
    assert hit.blocks == blocks[:1] and hit.tokens_matched == 4
    # COW donor: remainder [4, 5] matches block 1's first two tokens.
    hit = trie.lookup([0, 1, 2, 3, 4, 5])
    assert hit.blocks == blocks[:1]
    assert hit.cow_src == blocks[1] and hit.cow_tokens == 2
    # Retire the owner; trie refs keep the blocks allocated.
    pool.decref(blocks)
    assert pool.free_blocks == 16 - 2
    # Eviction prefers trie-only leaves and frees real memory.
    freed = trie.release(1)
    assert freed == 1 and trie.blocks_held == 1
    assert trie.clear() == 1
    assert pool.free_blocks == 16


def test_trie_eviction_respects_live_refs():
    pool = BlockPool(8, block_size=2)
    trie = PrefixTrie(pool, max_blocks=1)
    b1 = pool.alloc(1)
    trie.register([1, 2], b1)
    b2 = pool.alloc(1)
    trie.register([3, 4], b2)  # max_blocks=1 -> evicts the LRU node
    assert trie.blocks_held == 1
    # The evicted block was still owned by its slot: NOT freed.
    assert pool.refcount(b1[0]) == 1
    pool.decref(b1)
    pool.decref(b2)
    assert pool.refcount(b2[0]) == 1  # trie still holds it


def _evict_by_fresh_walks(trie, n):
    """Eviction as a fresh walk of the whole trie picks each leaf: the
    order ``PrefixTrie.release`` has to keep."""
    order = []
    for _ in range(n):
        leaves = []
        stack = [trie._root]
        while stack:
            node = stack.pop()
            for key, child in node.children.items():
                if child.children:
                    stack.append(child)
                else:
                    leaves.append((node, child, key))
        if not leaves:
            break
        parent, child, key = min(leaves, key=lambda pck: (
            trie.pool.refcount(pck[1].block) > 1, pck[1].stamp))
        del parent.children[key]
        trie.pool.decref([child.block])
        trie._count -= 1
        order.append(child.block)
    return order


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_trie_release_evicts_in_the_order_of_fresh_walks(seed):
    rng = np.random.default_rng(seed)
    pool = BlockPool(512, block_size=2)
    trie = PrefixTrie(pool)
    for _ in range(40):
        # Few token values: prompts share prefixes, so evicting a leaf
        # turns its parent into one.
        prompt = rng.integers(0, 3, size=int(rng.integers(2, 13))).tolist()
        blocks = pool.alloc(len(prompt) // 2)
        trie.register(prompt, blocks)
        if rng.random() < 0.5:
            pool.decref(blocks)  # owner retired: some leaves trie-only
        if rng.random() < 0.3:
            trie.lookup(rng.integers(0, 3, size=8).tolist())
    held = trie.blocks_held
    want_trie = copy.deepcopy(trie)
    for n in (3, held // 2, held):
        got = []
        decref = trie.pool.decref
        trie.pool.decref = lambda bs: (got.extend(bs), decref(bs))[1]
        freed = trie.release(n)
        del trie.pool.decref
        assert got == _evict_by_fresh_walks(want_trie, n)
        assert freed == sum(1 for b in got if pool.refcount(b) == 0)
        assert trie.blocks_held == want_trie.blocks_held
    assert trie.blocks_held == 0
    assert pool.free_blocks == want_trie.pool.free_blocks


def test_kv_config_roundtrip():
    cfg = ExperimentConfig.from_json(json.dumps({
        "model": "llama_tiny",
        "kv": {"block_size": 8, "num_blocks": 64,
               "prefill_chunk": 16, "prefix_cache": False}}))
    assert cfg.kv.block_size == 8 and cfg.kv.num_blocks == 64
    assert not cfg.kv.prefix_cache
    back = json.loads(cfg.to_json())
    assert back["kv"]["prefill_chunk"] == 16


def test_kv_config_refuses_the_removed_layout_switch():
    """A config file written when ``kv.paged`` chose between two layouts
    must not be silently reinterpreted: the field is refused by name."""
    with pytest.raises(TypeError, match="paged"):
        ExperimentConfig.from_dict({"kv": {"paged": False}})


def test_doctor_names_kv_pressure(tmp_path):
    """Satellite: the verdict names a KV-pressure incident (blocks
    exhausted -> admit_wait badput) from metrics + events alone."""
    from serverless_learn_tpu.telemetry.doctor import diagnose

    now = time.time()
    events = tmp_path / "events.jsonl"
    recs = [
        {"event": "alert", "alert": "kv.blocks_exhausted",
         "severity": "warning", "detector": "kvcache", "state": "firing",
         "message": "KV block pool exhausted (0/64 free)",
         "labels": {"engine": "continuous"}, "node": "serve-1",
         "value": 0.0, "threshold": 0.0, "count": 3,
         "first_fired_unix_s": now - 30, "last_fired_unix_s": now},
        # The symptom: admissions waiting, little decode.
        {"event": "phase", "phase": "admit_wait", "node": "serve-1",
         "t0_unix_s": now - 30, "duration_s": 20.0, "self_s": 20.0},
        {"event": "phase", "phase": "decode", "node": "serve-1",
         "t0_unix_s": now - 10, "duration_s": 5.0, "self_s": 5.0},
    ]
    events.write_text("\n".join(json.dumps(r) for r in recs) + "\n")
    rep = diagnose(paths=[str(events)])
    verdict = rep["summary"]["verdict"]
    assert "KV pressure" in verdict and "serve-1" in verdict
    assert "admit" in verdict  # badput correlation named
    assert any(a["alert"] == "kv.blocks_exhausted" for a in rep["alerts"])


# -- model-backed equivalence ------------------------------------------------


@pytest.fixture(scope="module")
def model(devices):
    bundle = get_model("llama_tiny", dtype=jnp.float32,
                       param_dtype=jnp.float32, max_seq_len=64)
    params = bundle.module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return bundle.module, params


def _solo(module, params, prompt, n, eos_id=None):
    toks = generate(module, params, jnp.asarray([prompt], jnp.int32), n,
                    eos_id=eos_id)
    return [int(t) for t in jax.device_get(toks)[0][len(prompt):]]


def _paged_engine(module, params, **kw):
    kv = kw.pop("kv", None) or KVCacheConfig(block_size=4,
                                             prefill_chunk=4,
                                             prefill_budget=8)
    kw.setdefault("max_slots", 4)
    kw.setdefault("chunk_size", 4)
    kw.setdefault("registry", MetricsRegistry())
    return ContinuousBatchingEngine(module, params, kv=kv, **kw)


def test_engine_without_kv_is_the_default_paged_pool(model):
    """``kv=None`` means ``KVCacheConfig()``, as ``GenerationServer`` has
    it: the engine has one KV layout and no argument selects another."""
    module, params = model
    eng = ContinuousBatchingEngine(module, params, max_slots=2,
                                   registry=MetricsRegistry())
    try:
        kv = KVCacheConfig()
        assert eng.kv == kv
        pages = pages_for(module.cfg.max_seq_len, kv.block_size)
        st = eng.kv_stats()
        assert isinstance(st, dict) and st["block_size"] == kv.block_size
        # max_slots rows of the window plus one row of slack for the trie.
        assert st["blocks_total"] == eng._pool.num_blocks == 3 * pages
        assert st["blocks_free"] == st["blocks_total"]
        assert eng._trie is not None
        assert eng.prefill_chunk == kv.prefill_chunk
    finally:
        eng.stop()


def test_paged_generate_matches_monolithic(model):
    """Module-level equivalence: the paged cache path of ``generate``
    (dense row-major tables, the static engine's shape) is byte-identical
    to the monolithic cache — greedy AND sampled (same PRNG stream)."""
    module, params = model
    ps, B = 8, 2
    max_pages = pages_for(module.cfg.max_seq_len, ps)
    pm = kvcache.paged_module(module, ps, B * max_pages)
    prompts = jnp.asarray([[5, 9, 11, 3], [7, 3, 2, 1]], jnp.int32)
    lengths = jnp.asarray([4, 2], jnp.int32)

    def paged_cache():
        tbl = jnp.asarray(kvcache.sequential_table(B, max_pages,
                                                   pm.cfg.kv_pages))
        return kvcache.with_tables(init_cache(pm, B), tbl,
                                   jnp.zeros((B,), jnp.int32))

    for kw in ({}, {"temperature": 0.8, "top_k": 8,
                    "rng": jax.random.PRNGKey(3)}):
        mono = generate(module, params, prompts, 10,
                        prompt_lengths=lengths, **kw)
        paged = generate(pm, params, prompts, 10, prompt_lengths=lengths,
                         cache=paged_cache(), **kw)
        assert np.array_equal(np.asarray(mono), np.asarray(paged)), \
            f"paged generate diverged ({kw or 'greedy'})"


def test_paged_engine_greedy_exact_with_chunked_prefill(model):
    """Concurrent unequal prompts — including one long enough to prefill
    in 4 chunks — are byte-identical to solo generate through the paged
    engine's admit/prefill/decode scheduler."""
    module, params = model
    eng = _paged_engine(module, params)
    try:
        prompts = [[5, 9, 11],
                   [7, 3, 2, 8, 1, 30, 12, 9, 4, 2, 6, 1, 8],  # 13 toks
                   [4], [1, 2]]
        results = [None] * len(prompts)

        def client(i):
            results[i] = eng.submit(prompts[i], 6, temperature=0.0,
                                    top_k=0, eos_id=None, seed=0)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        for i, p in enumerate(prompts):
            assert "error" not in results[i], results[i]
            assert results[i]["new_tokens"] == _solo(module, params, p, 6), \
                f"request {i} diverged under the paged engine"
        assert eng.prefill_chunks_run > 0
        # Retirement returned every non-cached block to the free list.
        st = eng.kv_stats()
        assert (st["blocks_total"] - st["blocks_free"]
                == st["prefix_blocks_cached"])
    finally:
        eng.stop()


def test_paged_engine_greedy_exact_with_every_slot_fed(model):
    """The derived prefill quota (``prefill_budget`` left at 0): eight
    concurrent unequal prompts, each longer than one chunk, go through
    programs of up to eight rows, several an iteration, and stay
    byte-identical to solo generate."""
    module, params = model
    eng = _paged_engine(module, params, max_slots=8,
                        kv=KVCacheConfig(block_size=4, prefill_chunk=4))
    assert eng.prefill_budget == 0
    try:
        prompts = [[(7 * i + 3 * j) % 97 + 1 for j in range(5 + 3 * i)]
                   for i in range(8)]   # 5, 8, ... 26 tokens: 2-7 chunks
        results = [None] * len(prompts)

        def client(i):
            results[i] = eng.submit(prompts[i], 5 + i, temperature=0.0,
                                    top_k=0, eos_id=None, seed=0)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        for i, p in enumerate(prompts):
            assert "error" not in results[i], results[i]
            assert results[i]["new_tokens"] == _solo(module, params, p,
                                                     5 + i), \
                f"request {i} diverged under the derived prefill quota"
        assert eng.prefill_chunks_run == sum(
            pages_for(len(p), 4) for p in prompts)
        st = eng.kv_stats()
        assert (st["blocks_total"] - st["blocks_free"]
                == st["prefix_blocks_cached"])
    finally:
        eng.stop()


def _stopped_engine_with_prefilling(module, params, prompts, seqs, kv,
                                    max_slots=4, decoding=False):
    """A paged engine whose dispatcher has stopped, with ``prompts``
    placed mid-prefill in slots 0.. and admitted in the order ``seqs``:
    the scheduler's prefill functions can then be called by hand.
    ``decoding``: the last slot holds a request that is past its prefill,
    so the derived quota is ``chunk_size // 2`` programs."""
    from serverless_learn_tpu.inference.continuous import _Request

    eng = ContinuousBatchingEngine(module, params, max_slots=max_slots,
                                   chunk_size=4, kv=kv,
                                   registry=MetricsRegistry())
    eng.stop()
    for sid, (prompt, seq) in enumerate(zip(prompts, seqs)):
        eng._slots[sid] = _Request(
            prompt=np.asarray(prompt, np.int32), max_new=4,
            temperature=0.0, top_k=0, eos_id=None, seed=0, admitted=True,
            prefilling=True, admit_seq=seq)
    if decoding:
        eng._slots[max_slots - 1] = _Request(
            prompt=np.asarray([1, 2], np.int32), max_new=40,
            temperature=0.0, top_k=0, eos_id=None, seed=0, admitted=True,
            prefill_pos=2, admit_seq=0)
    return eng


def _rows(fut) -> list:
    """The slots of a prefill program's rows, in row order."""
    return [sid for sid, _, _, _ in fut[2]]


def test_prefill_feeds_oldest_admitted_first(model):
    """FIFO among admitted rows is by admission order, not slot order,
    and a slot takes every row it can before the next slot gets one:
    under a budget of one chunk the only row fed is the oldest slot's;
    with the derived quota a program's rows are the oldest prompt's
    chunks, then the next prompt's; while a slot decodes the quota of
    ``chunk_size // 2`` programs goes to the oldest, and a slot is left
    unfed only when older slots spent it."""
    module, params = model
    prompts = [list(range(1 + 10 * i, 9 + 10 * i)) for i in range(3)]
    kv = KVCacheConfig(block_size=4, prefill_chunk=4, prefill_budget=4,
                       prefix_cache=False)
    eng = _stopped_engine_with_prefilling(module, params, prompts,
                                          seqs=[3, 1, 2], kv=kv)
    fed = []
    for _ in range(6):
        (fut,) = eng._prefill_steps()
        fed.append(_rows(fut))
    assert fed == [[1], [1], [2], [2], [0], [0]]
    assert eng._prefill_steps() == []

    derived = KVCacheConfig(block_size=4, prefill_chunk=4,
                            prefix_cache=False)
    eng = _stopped_engine_with_prefilling(module, params, prompts,
                                          seqs=[3, 1, 2], kv=derived)
    futs = eng._prefill_steps()   # no slot decodes: no bound
    assert [_rows(f) for f in futs] == [[1, 1, 2, 2], [0, 0]]
    assert not any(r.prefilling for r in eng._slots if r is not None)
    # Only a slot's last row carries ``fin``; here each ends its prompt.
    assert [[fin for _, _, fin, _ in f[2]] for f in futs] \
        == [[False, True, False, True], [False, True]]

    # A slot decodes: two programs of four rows. Prompts of 5, 2 and 2
    # chunks: the oldest takes five rows, the next two, the one left of
    # the eight goes to the youngest, whose second chunk waits.
    prompts = [list(range(1, 9)), list(range(11, 31)), list(range(41, 49))]
    eng = _stopped_engine_with_prefilling(module, params, prompts,
                                          seqs=[3, 1, 2], kv=derived,
                                          decoding=True)
    futs = eng._prefill_steps()
    assert [_rows(f) for f in futs] == [[1, 1, 1, 1], [1, 2, 2, 0]]
    assert [r.prefill_pos for r in eng._slots[:3]] == [4, 20, 8]
    assert [r.prefilling for r in eng._slots[:3]] == [True, False, False]
    # Seven chunks where five fit the quota: the youngest is not fed.
    prompts = [list(range(1, 9)), list(range(11, 31)), list(range(41, 53))]
    eng = _stopped_engine_with_prefilling(module, params, prompts,
                                          seqs=[3, 1, 2], kv=derived,
                                          decoding=True)
    futs = eng._prefill_steps()
    assert [_rows(f) for f in futs] == [[1, 1, 1, 1], [1, 2, 2, 2]]
    assert [r.prefill_pos for r in eng._slots[:3]] == [0, 20, 12]
    assert [_rows(f) for f in eng._prefill_steps()] == [[0, 0]]


def test_refused_pages_sit_out_the_iteration(model):
    """Back-pressure inside an iteration of several programs: a slot the
    pool refuses pages keeps the rows it got pages for, is counted once
    and sits out the rest of the iteration (nothing frees pages before
    its decode dispatch); the slots behind it are still asked, and the
    loop ends when every remaining slot is refused."""
    module, params = model
    kv = KVCacheConfig(block_size=4, num_blocks=18, prefill_chunk=4,
                       prefix_cache=False)
    prompts = [list(range(1, 41)), list(range(41, 81)),   # 10 pages each
               list(range(81, 89))]
    eng = _stopped_engine_with_prefilling(module, params, prompts,
                                          seqs=[1, 2, 3], kv=kv)
    futs = eng._prefill_steps()
    # 18 pages: ten to the oldest, eight to the next, whose ninth row is
    # refused inside the fifth program; the youngest is refused too.
    assert [_rows(f) for f in futs] == [[0] * 4, [0] * 4, [0, 0, 1, 1],
                                        [1] * 4, [1, 1]]
    assert [r.prefill_pos for r in eng._slots[:3]] == [40, 32, 0]
    assert eng._pool.free_blocks == 0
    assert int(eng._m_kv_blocked.value) == 2    # once a slot, not a program
    assert [r.prefilling for r in eng._slots[:3]] == [False, True, True]
    assert eng.prefill_chunks_run == 18
    # The next iteration asks again, and is refused again.
    assert eng._prefill_steps() == []
    assert int(eng._m_kv_blocked.value) == 4


def test_seeded_sampling_is_blind_to_page_and_chunk_size(model):
    """Seeded sampling: identical tokens whatever the pool's block size
    and the prefill chunk (the fold_in(seed, position) streams see
    positions, not pages), beside a greedy neighbour."""
    module, params = model
    req = dict(prompt=[7, 3, 2, 9, 1, 4], max_new=6, temperature=0.9,
               top_k=8, eos_id=None, seed=42)

    def run(kv):
        eng = ContinuousBatchingEngine(module, params, max_slots=3,
                                       chunk_size=2, kv=kv,
                                       registry=MetricsRegistry())
        try:
            res = {}

            def target():
                res["r"] = eng.submit(req["prompt"], req["max_new"],
                                      req["temperature"], req["top_k"],
                                      req["eos_id"], req["seed"])

            ts = [threading.Thread(target=target),
                  threading.Thread(target=lambda: eng.submit(
                      [5, 9, 11, 4], 8, 0.0, 0, None, 0))]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=300)
            assert "error" not in res["r"], res["r"]
            return res["r"]["new_tokens"]
        finally:
            eng.stop()

    assert run(KVCacheConfig(block_size=4, prefill_chunk=4)) \
        == run(KVCacheConfig()), \
        "seeded sampling depends on the page or the chunk size"


def test_paged_engine_eos_retires_and_frees_blocks(model):
    module, params = model
    prompt = [5, 9, 11]
    first_tok = _solo(module, params, prompt, 1)[0]
    want = _solo(module, params, prompt, 8, eos_id=first_tok)
    eng = _paged_engine(module, params, kv=KVCacheConfig(
        block_size=4, prefill_chunk=4, prefix_cache=False))
    try:
        r = eng.submit(prompt, 8, 0.0, 0, first_tok, 0)
        assert r["new_tokens"] == want
        st = eng.kv_stats()
        assert st["blocks_free"] == st["blocks_total"], \
            "EOS retirement must return every block to the free list"
    finally:
        eng.stop()


def test_shared_prefix_reuse_hits_and_stays_exact(model):
    """Two prompts sharing a 12-token system prefix: the second admission
    reuses the published blocks (hit counters move) and both replies stay
    byte-identical to solo generate. A third prompt diverging mid-block
    exercises the COW path."""
    module, params = model
    reg = MetricsRegistry()
    eng = _paged_engine(module, params, registry=reg)
    try:
        sysp = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8]
        a = eng.submit(sysp + [11, 2], 5, 0.0, 0, None, 0)
        assert a["new_tokens"] == _solo(module, params, sysp + [11, 2], 5)
        hits0 = eng._trie.hits
        b = eng.submit(sysp + [9, 7], 5, 0.0, 0, None, 0)
        assert b["new_tokens"] == _solo(module, params, sysp + [9, 7], 5)
        assert eng._trie.hits > hits0, "second prompt missed the trie"
        # Mid-block divergence: shares sysp[:8] fully, then diverges
        # inside the third block -> COW donor.
        c_prompt = sysp[:10] + [44, 45]
        c = eng.submit(c_prompt, 5, 0.0, 0, None, 0)
        assert c["new_tokens"] == _solo(module, params, c_prompt, 5)
        snap = reg.snapshot()
        hits = sum(s["value"] for s in
                   snap["slt_kv_prefix_hits_total"]["series"])
        toks = sum(s["value"] for s in
                   snap["slt_kv_prefix_tokens_total"]["series"])
        assert hits >= 2 and toks > 0
    finally:
        eng.stop()


def test_exhaustion_backpressure_and_preemption_stay_exact(model):
    """A pool sized for ONE max-length sequence under 4 concurrent
    long-budget requests: admissions defer (typed backpressure, counted),
    decode-time pressure preempts the youngest (deterministic restart),
    and every reply is still byte-identical. No crash, no leak."""
    module, params = model
    reg = MetricsRegistry()
    kv = KVCacheConfig(block_size=4, num_blocks=16, prefill_chunk=4,
                       prefix_cache=False)
    eng = ContinuousBatchingEngine(module, params, max_slots=4,
                                   chunk_size=4, kv=kv, registry=reg)
    try:
        prompts = [[i + 1, i + 2, 3, 4, 5, 1, 2, 9] for i in range(4)]
        results = [None] * 4

        def client(i):
            results[i] = eng.submit(prompts[i], 24, 0.0, 0, None, 0,
                                    timeout_s=300)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        for i, p in enumerate(prompts):
            assert results[i] is not None and "error" not in results[i], \
                (i, results[i])
            assert results[i]["new_tokens"] == _solo(module, params, p, 24)
        st = eng.kv_stats()
        assert st["blocks_free"] == st["blocks_total"], "blocks leaked"
        snap = reg.snapshot()
        blocked = sum(s["value"] for s in
                      snap["slt_kv_admit_blocked_total"]["series"])
        assert blocked > 0 or eng.preemptions > 0, \
            "a 16-block pool under 4x32-token demand never felt pressure?"
    finally:
        eng.stop()


def test_decode_cost_tracks_live_slots(model):
    """Satellite (retired-slot FLOP burn): the paged decode chunk runs a
    COMPACTED live batch, so after the short request retires, boundaries
    decode 1 row, not max_slots. decoded_rows_total is the step-cost
    proxy: it must be far below chunks_run * max_slots."""
    module, params = model
    eng = _paged_engine(module, params, max_slots=4, chunk_size=2)
    try:
        res = {}

        def long_client():
            res["long"] = eng.submit([5, 9, 11], 24, 0.0, 0, None, 0)

        def short_client():
            res["short"] = eng.submit([7, 3], 2, 0.0, 0, None, 0)

        ts = [threading.Thread(target=long_client),
              threading.Thread(target=short_client)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=300)
        assert res["long"]["new_tokens"] == _solo(module, params,
                                                  [5, 9, 11], 24)
        assert res["short"]["new_tokens"] == _solo(module, params,
                                                   [7, 3], 2)
        # 4 slots, ~14 chunks: the monolithic engine would have decoded
        # chunks_run * 4 rows. Compaction must keep it near the live
        # count (2 rows briefly, then 1).
        assert eng.chunks_run >= 2
        assert eng.decoded_rows_total <= eng.chunks_run + 4, \
            (f"decode cost not tracking live slots: "
             f"{eng.decoded_rows_total} rows over {eng.chunks_run} chunks")
    finally:
        eng.stop()


# -- a slot is released when its request's budget is dispatched --------------
#
# Driven by hand: the dispatcher thread is stopped and the test calls
# ``_iterate`` itself, so what is in flight at each step is known.


class _ByHand:
    """A paged engine whose dispatcher has stopped; requests go on its
    queue as the engine's own ``_Request`` and the test steps the
    scheduler. Two slots, chunks of 4, pages of 4, a pool of 16 pages
    (one max-length sequence), no prefix cache."""

    def __init__(self, module, params, max_slots=2, pipeline_depth=2,
                 kv=None):
        self.module, self.params = module, params
        self.eng = ContinuousBatchingEngine(
            module, params, max_slots=max_slots, chunk_size=4,
            pipeline_depth=pipeline_depth, registry=MetricsRegistry(),
            kv=kv or KVCacheConfig(block_size=4, num_blocks=16,
                                   prefill_chunk=4, prefix_cache=False))
        self.eng.stop()
        self.requests = []  # everything ``put``, in order
        self.programs = []  # every prefill program's (nb, T, W) key
        fetch = self.eng._paged_prefill_jit

        def counting(nb, T, W):
            self.programs.append((nb, T, W))
            return fetch(nb, T, W)

        self.eng._paged_prefill_jit = counting
        self.seq = 0
        self.retired = []   # (slot, its pages) at every retirement
        real = self.eng._retire_slot

        def retire(sid):
            self.retired.append((sid, list(self.eng._slot_pages[sid])))
            real(sid)

        self.eng._retire_slot = retire

    def put(self, prompt, max_new, eos_id=None, temperature=0.0, top_k=0,
            seed=0):
        from serverless_learn_tpu.inference.continuous import _Request

        r = _Request(prompt=np.asarray(prompt, np.int32), max_new=max_new,
                     temperature=temperature, top_k=top_k, eos_id=eos_id,
                     seed=seed)
        self.eng._q.put(r)
        self.requests.append(r)
        return r

    def step(self, n=1):
        for _ in range(n):
            self.seq += 1
            self.eng._iterate(self.seq)

    def run_out(self, *requests, limit=200):
        """Step until every request is answered: the test's time limit,
        in iterations."""
        while not all(r.done.is_set() for r in requests):
            assert self.seq < limit, "the scheduler made no progress"
            self.step()

    def slot_of(self, r):
        return [x is r for x in self.eng._slots].index(True)

    def in_flight(self, r) -> bool:
        return any(entry[1] is r for _, _, snapshot in self.eng._futures
                   for entry in snapshot)

    def assert_exact(self, *requests):
        for r in requests:
            assert r.result is not None and "error" not in r.result, \
                r.result
            if r.temperature > 0:
                continue    # solo generate draws from another stream
            assert r.result["new_tokens"] == _solo(
                self.module, self.params, [int(t) for t in r.prompt],
                r.max_new, eos_id=r.eos_id)


N_PROMPT, A_PROMPT = [7, 3, 2, 8], [5, 9, 11, 3, 1, 4, 1, 5]
B_SHORT, B_LONG = [6, 2, 8, 3, 1, 8], list(range(20, 32))


def _released_in_flight(model, b_prompt=B_LONG, depth=2):
    """Two iterations in: a long-running neighbour N holds slot 0; A's
    second and last chunk has just been dispatched, so slot 1 is free
    and A's pages are back in the pool while A waits for its harvest; B
    has waited in the queue for a slot."""
    h = _ByHand(*model, pipeline_depth=depth)
    n = h.put(N_PROMPT, 30)
    a = h.put(A_PROMPT, 9)       # owes ceil(8 / 4) = 2 chunks
    b = h.put(b_prompt, 6)
    h.step()
    assert (h.slot_of(n), h.slot_of(a)) == (0, 1) and not b.admitted
    assert h.eng.slots_released_total == 0
    h.step()
    return h, n, a, b


@pytest.mark.parametrize("depth", [1, 2])
def test_released_pages_go_to_successor_with_last_chunk_in_flight(model,
                                                                  depth):
    """The slot and the pages leave A at the dispatch of its last chunk;
    B takes both in the next iteration, while that chunk is still in
    flight. The device runs B's prefill after the chunk, so all three
    replies equal their solo runs."""
    h, n, a, b = _released_in_flight(model, depth=depth)
    eng = h.eng
    assert eng.slots_released_total == 1 and eng._slots[1] is None
    assert not a.done.is_set() and not a.finished and h.in_flight(a)
    (sid, a_pages), = h.retired
    assert sid == 1 and len(a_pages) == 4
    assert eng._pool.free_blocks == 16 - len(eng._slot_pages[0])
    seen = {}
    real = eng._admit_paged

    def admit(staged):
        out = real(staged)
        if b.admitted and not seen:
            seen.update(a_done=a.done.is_set(), a_in_flight=h.in_flight(a),
                        pages=list(eng._slot_pages[1]))
        return out

    eng._admit_paged = admit
    h.step()
    assert eng._slots[1] is b
    assert seen["a_in_flight"] and not seen["a_done"]
    assert set(seen["pages"]) <= set(a_pages), \
        "the LIFO pool hands the successor the released row's pages"
    h.run_out(n, a, b)
    h.assert_exact(n, a, b)
    assert eng.preemptions == 0
    assert eng._pool.free_blocks == 16
    # Paid equals owed: ceil((max_new - 1) / 4) row-chunks a reply.
    assert eng.decoded_rows_total == 8 + 2 + 2
    assert eng.slots_released_total == 3 == eng.requests_finished


def test_successor_joins_the_chunk_after_the_released_rows_last(model):
    """A prompt that fits the iteration's prefill quota: the slot goes
    from one reply's last chunk to the next reply's first with no chunk
    between."""
    h, n, a, b = _released_in_flight(model, b_prompt=B_SHORT)
    chunks = h.eng.chunks_run
    h.step()
    assert h.eng.chunks_run == chunks + 1
    kind, _, snapshot = h.eng._futures[-1]
    assert kind == "pchunk"
    assert [(sid, r) for sid, r, _ in snapshot] == [(0, n), (1, b)]
    h.run_out(n, a, b)
    h.assert_exact(n, a, b)


def test_staged_successor_keeps_the_released_chunk_in_flight(model):
    """Every slot released and a request waiting: the harvest does not
    drain ahead of it (the successor's prefill is queued behind the
    chunk on the device); with nobody waiting it drains at once."""
    h = _ByHand(*model, max_slots=1)
    a, b = h.put(A_PROMPT, 5), h.put(B_SHORT, 5)
    h.step()
    assert h.eng._slots == [None] and h.eng.slots_released_total == 1
    assert not a.done.is_set() and h.in_flight(a)
    h.step()
    assert a.done.is_set() and h.eng.slots_released_total == 2
    assert h.eng._slots == [None] and b.done.is_set(), \
        "nobody waits: the iteration that released b also answered it"
    h.assert_exact(a, b)
    assert h.eng.decoded_rows_total == 2


def test_max_new_one_is_released_at_its_last_prefill_program(model):
    h = _ByHand(*model)
    n = h.put(N_PROMPT, 30)
    h.step()
    # Five chunks, two rows a program, two programs a step while the
    # neighbour decodes: four chunks in this step, the last in the next.
    one = h.put(list(range(20, 40)), 1)
    h.step()
    assert h.eng._slots[1] is one and one.prefilling
    assert one.prefill_pos == 16
    h.step()
    assert h.eng._slots[1] is None and h.eng.slots_released_total == 1
    assert one.chunks_dispatched == 0
    h.run_out(one)
    h.assert_exact(one)
    rows = h.eng.decoded_rows_total
    assert rows == h.eng.chunks_run, "only the neighbour ever decoded"
    h.run_out(n)
    h.assert_exact(n)


def test_eos_before_the_budget_is_found_at_harvest(model):
    """EOS ends a reply sooner than its budget: found at the harvest of
    its chunk as before (no release at dispatch), filled to ``max_new``
    like solo generate, its slot and pages retired there."""
    module, params = model
    first = _solo(module, params, A_PROMPT, 1)[0]
    h = _ByHand(module, params)
    n = h.put(N_PROMPT, 30)
    e = h.put(A_PROMPT, 24, eos_id=first)
    h.run_out(e)
    assert e.result["new_tokens"] == [first] * 24
    h.assert_exact(e)
    assert h.eng.slots_released_total == 0
    assert e.chunks_dispatched < 6, "it never reached its budget"
    assert [sid for sid, _ in h.retired] == [1]
    h.run_out(n)
    h.assert_exact(n)
    assert h.eng._pool.free_blocks == 16


def test_cancelled_neighbour_while_a_released_request_is_in_flight(model):
    h, n, a, b = _released_in_flight(model)
    n.cancelled = True          # its submitter timed out
    h.run_out(a, b)
    assert n.finished and "cancelled" in n.result["error"]
    assert h.eng.requests_cancelled == 1
    h.assert_exact(a, b)
    assert h.eng._slots == [None, None]
    assert h.eng._pool.free_blocks == 16
    assert h.eng.slots_released_total == 2


def test_preempting_neighbour_while_a_released_request_is_in_flight(model):
    """The pool is squeezed to one free page, one that A just gave back.
    B is admitted onto it and prefills into it; N's next chunk needs a
    page, so N preempts B (the youngest) and decodes into that same
    page, all while A's last chunk is in flight. A released request is
    no preemption victim, and every reply equals its solo run."""
    h, n, a, b = _released_in_flight(model)
    eng = h.eng
    (_, a_pages), = h.retired
    ballast = eng._pool.alloc(eng._pool.free_blocks)
    ballast.remove(a_pages[0])
    eng._pool.decref(a_pages[:1])
    h.step()
    assert eng.preemptions == 1 and not b.admitted and b.gen == 1
    assert eng._slots[1] is None and h.retired[-1][1][0] in a_pages
    assert eng._slot_pages[0][-1] in a_pages
    eng._pool.decref(ballast)
    h.run_out(n, a, b)
    h.assert_exact(n, a, b)
    assert eng.preemptions == 1 and eng._pool.free_blocks == 16
    # B's first residency was preempted before it decoded: nothing paid
    # twice, and its second residency is released at dispatch too.
    assert eng.decoded_rows_total == 8 + 2 + 2
    assert eng.slots_released_total == 3


def test_stop_answers_a_released_request_in_flight(model):
    """A request released at dispatch lives in a future's snapshot
    alone; ``stop()`` still answers it, as it answers the slots' and the
    staged requests."""
    h, n, a, b = _released_in_flight(model)
    assert h.in_flight(a) and h.eng._slots[1] is None
    assert not b.admitted and h.eng._staged[0] is b
    t0 = time.time()
    h.eng.stop()
    assert time.time() - t0 < 10
    for r in (n, a, b):
        assert r.done.is_set() and r.finished
        assert r.result == {"error": "server shutting down"}


# -- a prefill program's rows are consecutive chunks of a prompt -------------
#
# Served by hand like the tests above, so the schedule is the same on
# every run. "Unpacked" is the same traffic under a ``prefill_budget`` of
# one chunk: every row-chunk in a program of its own, which is what a
# prompt alone in the engine got before a program's rows were row-chunks.

SYSTEM = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8]
SYSTEM16 = SYSTEM + [9, 7, 9, 3]
LONG_A = [(5 * j + 3) % 97 + 1 for j in range(26)]     # 7 chunks of 4
LONG_B = [(7 * j + 11) % 89 + 1 for j in range(13)]    # 4 chunks
SAMPLED = dict(temperature=0.9, top_k=8, seed=42)
# case -> (kv of the packed engine, waves of (prompt, max_new, sampling)).
# A wave is queued when the wave before it has been answered.
_NO_TRIE = dict(block_size=4, prefill_chunk=4, prefix_cache=False)
PACKED_CASES = {
    "alone": (_NO_TRIE, [[(LONG_A, 6, {})], [(LONG_A, 6, SAMPLED)]]),
    "beside_a_second_prompt": (_NO_TRIE, [[(LONG_A, 6, SAMPLED),
                                           (LONG_B, 7, {})],
                                          [(LONG_B, 5, SAMPLED),
                                           (LONG_A, 6, {})]]),
    # The trie copies on write only where the prompt ENDS inside the
    # divergent block, so chunks shorter than a block are what gives a
    # slot a pending copy and two rows: 16 tokens shared and 3 copied,
    # then [50, 51], [52]; beside it 16 shared and seven chunks.
    "trie_hit_and_cow": (dict(block_size=8, prefill_chunk=2),
                         [[(SYSTEM16 + [11, 2, 7, 9, 4, 6, 1, 3], 5, {})],
                          [(SYSTEM16 + [11, 2, 7, 50, 51, 52], 6, {}),
                           (SYSTEM16 + LONG_B, 6, SAMPLED)]]),
    "prefill_budget": (dict(_NO_TRIE, prefill_budget=8),
                       [[(LONG_A, 6, {})], [(LONG_A, 6, SAMPLED)]]),
    "pool_grants_part_of_the_rows": (
        dict(_NO_TRIE, num_blocks=16),
        [[(LONG_A, 6, {})], [(LONG_A, 6, SAMPLED)]]),
}


def _serve_by_hand(model, kv: dict, waves, squeeze_to=None) -> _ByHand:
    """``squeeze_to``: before each wave, ballast takes all but so many
    pages of the pool, and gives them back after the wave's second
    iteration: the pool then grants a prompt only part of its rows."""
    h = _ByHand(*model, max_slots=4, kv=KVCacheConfig(**kv))
    for wave in waves:
        rs = [h.put(p, n, **sampling) for p, n, sampling in wave]
        if squeeze_to is not None:
            ballast = h.eng._pool.alloc(h.eng._pool.free_blocks
                                        - squeeze_to)
            h.step(2)
            assert any(r.prefilling for r in rs), "the pool was not short"
            h.eng._pool.decref(ballast)
        h.run_out(*rs)
    return h


@pytest.mark.parametrize("case", sorted(PACKED_CASES))
def test_packed_prefill_is_token_exact(model, case):
    """Consecutive chunks of one prompt as the rows of one program give
    the tokens of solo ``generate`` (greedy) and the tokens of the same
    chunks in programs of their own (greedy and sampled at a fixed
    seed)."""
    kv, waves = PACKED_CASES[case]
    squeeze = 3 if case == "pool_grants_part_of_the_rows" else None
    packed = _serve_by_hand(model, kv, waves, squeeze_to=squeeze)
    plain = _serve_by_hand(
        model, dict(kv, prefill_budget=kv["prefill_chunk"]), waves)
    packed.assert_exact(*packed.requests)
    for a, b in zip(packed.requests, plain.requests):
        assert a.result == b.result, (case, a.prompt.tolist())
    eng = packed.eng
    assert eng.prefill_chunks_run == plain.eng.prefill_chunks_run \
        == len(plain.programs), "the same row-chunks, one a program there"
    assert len(packed.programs) < eng.prefill_chunks_run, \
        "no program carried two rows of a prompt"
    assert all(nb <= 4 and T <= 8 for nb, T, _ in packed.programs)
    if case == "alone":
        # Seven chunks: a program of four rows and one of three.
        assert len(packed.programs) == 2 * 2
    if case == "prefill_budget":
        # Two chunks an iteration, in one program of two rows.
        assert len(packed.programs) == 2 * 4
    if case == "trie_hit_and_cow":
        assert eng._trie.hits == 2
        assert int(eng._m_kv_hit_tokens.value) == (16 + 3) + 16
        # The second wave's nine rows: the copy's slot has the first
        # two of a program of four.
        assert packed.programs[3:] == [(4, 2, 4), (4, 2, 4), (1, 2, 4)]
    if case == "pool_grants_part_of_the_rows":
        assert int(eng._m_kv_blocked.value) >= 2
        assert eng._pool.free_blocks == 16


def test_packed_prefill_reaches_no_program_outside_the_warmed_set(model):
    """Dispatch creates no compile key that ``warm_shapes`` did not: a
    program has at most ``max_slots`` rows of at most ``prefill_chunk``
    tokens, over the table windows the prompts' page counts reach."""
    h = _ByHand(*model, max_slots=4,
                kv=KVCacheConfig(block_size=4, prefill_chunk=4))
    eng = h.eng
    work = [(LONG_A, 6), (LONG_B, 7), ([4], 3), (SYSTEM + [11, 2], 9),
            (LONG_A[:9], 5), (SYSTEM + LONG_B, 6), ([1, 2], 12)]
    assert eng.warm_shapes([(len(p), n) for p, n in work]) > 0
    pre, dec = set(eng._prefill_jits), set(eng._chunk_jits)
    rs = [h.put(p, n) for p, n in work]
    h.run_out(*rs)
    h.assert_exact(*rs)
    assert set(h.programs) <= pre and len(set(h.programs)) > 3
    assert set(eng._prefill_jits) == pre and set(eng._chunk_jits) == dec
    assert max(nb for nb, _, _ in h.programs) == 4
    assert all(T <= 4 or T == 8 for _, T, _ in h.programs)


def test_block_table_write_padding_drops(model):
    """Gather/scatter padding semantics: a ragged paged extend must not
    write beyond a row's valid length — pages belong to OTHER sequences.
    Proven by diffing the pool before/after an extend whose second row is
    pure padding."""
    module, params = model
    ps = 4
    pm = kvcache.paged_module(module, ps, 8)
    cache = init_cache(pm, 2)
    # Row 0 owns page 0; row 1 owns page 1. Window W=1.
    tbl = jnp.asarray([[0], [1]], jnp.int32)
    cache = kvcache.with_tables(cache, tbl, jnp.zeros((2,), jnp.int32))
    toks = jnp.asarray([[5, 9, 11], [7, 7, 7]], jnp.int32)
    lens = jnp.asarray([3, 0], jnp.int32)  # row 1: all padding
    _, upd = pm.apply({"params": params, "cache": cache}, toks,
                      extend=True, mutable=["cache"], seq_lengths=lens)
    pages, ci = kvcache.split_cache(upd["cache"])
    leaf = jax.tree_util.tree_leaves(pages)[0]
    assert np.asarray(ci).tolist() == [3, 0]
    # Row 1's page (id 1) must still be all zeros: every write dropped.
    assert not np.asarray(leaf[1]).any(), \
        "padding row wrote K/V into the shared pool"
    # Row 0's page has real K/V at offsets 0..2.
    assert np.asarray(leaf[0][:3]).any()


def test_server_ping_reports_kv_and_prompt_histogram(model):
    """The serving wire's admin ping carries paged-pool pressure (the
    router's memory-aware picking input) and submit() feeds the
    prompt-length histogram (the prefix-hit-rate denominator)."""
    from serverless_learn_tpu.inference.server import (GenerationServer,
                                                       request)

    module, params = model
    reg = MetricsRegistry()
    srv = GenerationServer(module, params, registry=reg,
                           kv=KVCacheConfig(block_size=4,
                                            prefill_chunk=4)).start()
    try:
        rep = request(srv.addr, {"prompt": [5, 9, 11],
                                 "max_new_tokens": 3})
        assert rep.get("new_tokens") == _solo(module, params, [5, 9, 11],
                                              3)
        ping = request(srv.addr, {"op": "ping"})
        assert ping["ok"] and "kv" in ping
        assert ping["kv"]["blocks_total"] > 0
        assert ping["kv"]["blocks_free"] <= ping["kv"]["blocks_total"]
        snap = reg.snapshot()
        fam = snap.get("slt_request_prompt_tokens")
        assert fam and sum(s["count"] for s in fam["series"]) >= 1
    finally:
        srv.stop()
