"""Continuous batching: slot-level scheduling must not change greedy
results, must admit mid-stream, retire at EOS, and never starve a
request behind a mismatched neighbour.

Exactness model: greedy continuations are byte-identical to solo
``generate`` calls; sampled continuations are REPRODUCIBLE and
BATCH-INVARIANT (per-slot ``fold_in(seed, position)`` streams, asserted
here by re-running the same seed under different traffic).
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from serverless_learn_tpu.config import KVCacheConfig
from serverless_learn_tpu.inference.continuous import (
    ContinuousBatchingEngine)
from serverless_learn_tpu.inference.generate import generate
from serverless_learn_tpu.models.registry import get_model
from serverless_learn_tpu.telemetry.registry import MetricsRegistry


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


def _engine(module, params, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("chunk_size", 4)
    return ContinuousBatchingEngine(module, params, **kw)


def test_padded_batch_generate_matches_solo(model):
    """The primitive under every batched path: one call over right-padded
    unequal prompts (``prompt_lengths`` gives each row its own cache
    index) reproduces each solo greedy continuation exactly."""
    module, params = model
    prompts = [[5, 9, 11], [7, 3, 2, 8, 1, 30, 12], [4]]
    P = max(len(p) for p in prompts)
    padded = np.zeros((3, P), np.int32)
    lens = np.zeros(3, np.int32)
    for i, p in enumerate(prompts):
        padded[i, :len(p)] = p
        lens[i] = len(p)
    toks = generate(module, params, jnp.asarray(padded), 6,
                    prompt_lengths=jnp.asarray(lens))
    new = np.asarray(jax.device_get(toks))[:, P:]
    for i, p in enumerate(prompts):
        assert new[i].tolist() == _solo(module, params, p, 6), f"row {i}"


def test_concurrent_greedy_exact(model):
    """Several unequal prompts submitted together: every reply equals the
    solo greedy continuation, and they shared the slot pool."""
    module, params = model
    eng = _engine(module, params)
    try:
        prompts = [[5, 9, 11], [7, 3, 2, 8, 1, 30, 12], [4], [1, 2]]
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
                f"request {i} diverged under continuous batching"
        assert eng.requests_finished == len(prompts)
        assert max(r["batch_size"] for r in results) > 1, \
            "requests never shared the slot pool"
    finally:
        eng.stop()


def test_mid_stream_admission_exact(model):
    """A request arriving while another is mid-decode joins at a chunk
    boundary and BOTH match their solo continuations — the static engine
    would have made the late arrival wait out the whole group."""
    module, params = model
    eng = _engine(module, params, chunk_size=2)
    try:
        long_prompt, short_prompt = [5, 9, 11, 7], [8, 2]
        res = {}

        def first():
            res["long"] = eng.submit(long_prompt, 20, temperature=0.0,
                                     top_k=0, eos_id=None, seed=0)

        def second():
            res["short"] = eng.submit(short_prompt, 4, temperature=0.0,
                                      top_k=0, eos_id=None, seed=0)

        t1 = threading.Thread(target=first)
        t1.start()
        # Let the first request start decoding before the second arrives.
        deadline = time.time() + 60
        while eng.chunks_run < 2 and time.time() < deadline:
            time.sleep(0.01)
        assert eng.chunks_run >= 2, "first request never started decoding"
        t2 = threading.Thread(target=second)
        t2.start()
        t1.join(timeout=300)
        t2.join(timeout=300)
        assert res["long"]["new_tokens"] == _solo(module, params,
                                                  long_prompt, 20)
        assert res["short"]["new_tokens"] == _solo(module, params,
                                                   short_prompt, 4)
    finally:
        eng.stop()


def test_eos_retires_slot_early(model):
    """A sequence hitting EOS frees its slot while others keep decoding;
    the reply is EOS-filled to max_new exactly like solo generate."""
    module, params = model
    # Find the first greedy token of this prompt, then use it as the EOS
    # id so the request retires on its very first decode chunk.
    prompt = [5, 9, 11]
    first_tok = _solo(module, params, prompt, 1)[0]
    want = _solo(module, params, prompt, 8, eos_id=first_tok)
    eng = _engine(module, params, chunk_size=2)
    try:
        res = {}

        def eos_client():
            res["eos"] = eng.submit(prompt, 8, temperature=0.0, top_k=0,
                                    eos_id=first_tok, seed=0)

        def long_client():
            res["long"] = eng.submit([7, 3, 2], 16, temperature=0.0,
                                     top_k=0, eos_id=None, seed=0)

        ts = [threading.Thread(target=eos_client),
              threading.Thread(target=long_client)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=300)
        assert res["eos"]["new_tokens"] == want
        assert res["eos"]["new_tokens"][0] == first_tok
        assert all(t == first_tok for t in res["eos"]["new_tokens"])
        assert res["long"]["new_tokens"] == _solo(module, params,
                                                  [7, 3, 2], 16)
    finally:
        eng.stop()


def test_more_requests_than_slots(model):
    """6 requests through 2 slots: retirement must recycle slots until
    the queue drains; all replies exact."""
    module, params = model
    eng = _engine(module, params, max_slots=2, chunk_size=2)
    try:
        prompts = [[i + 1, i + 2] for i in range(6)]
        results = [None] * 6

        def client(i):
            results[i] = eng.submit(prompts[i], 4, temperature=0.0,
                                    top_k=0, eos_id=None, seed=0)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        for i, p in enumerate(prompts):
            assert results[i]["new_tokens"] == _solo(module, params, p, 4)
    finally:
        eng.stop()


def test_mixed_sampling_in_one_batch_no_starvation(model):
    """The static engine's documented failure (round-4 verdict): sustained
    compatible traffic starves a mismatched request behind new arrivals.
    Here a sampled request rides the SAME slot pool as a stream of greedy
    traffic and completes promptly."""
    module, params = model
    eng = _engine(module, params, max_slots=4, chunk_size=2)
    try:
        stop_feeding = threading.Event()
        greedy_done = []

        def greedy_stream():
            while not stop_feeding.is_set():
                r = eng.submit([5, 9], 4, temperature=0.0, top_k=0,
                               eos_id=None, seed=0)
                greedy_done.append(r)

        feeders = [threading.Thread(target=greedy_stream)
                   for _ in range(2)]
        for t in feeders:
            t.start()
        res = eng.submit([7, 3, 2], 6, temperature=0.9, top_k=8,
                         eos_id=None, seed=123, timeout_s=120.0)
        stop_feeding.set()
        for t in feeders:
            t.join(timeout=300)
        assert "error" not in res, res
        assert len(res["new_tokens"]) == 6
        assert all("error" not in r for r in greedy_done)
    finally:
        eng.stop()


def test_sampled_is_reproducible_and_batch_invariant(model):
    """fold_in(seed, position) streams: the same request returns the same
    tokens whether it runs alone or alongside other traffic."""
    module, params = model
    req = dict(prompt=[7, 3, 2], max_new=6, temperature=0.9, top_k=8,
               eos_id=None, seed=42)

    def run_once(with_traffic: bool):
        eng = _engine(module, params, chunk_size=2)
        try:
            res = {}

            def target():
                res["r"] = eng.submit(req["prompt"], req["max_new"],
                                      req["temperature"], req["top_k"],
                                      req["eos_id"], req["seed"])

            ts = [threading.Thread(target=target)]
            if with_traffic:
                ts.append(threading.Thread(
                    target=lambda: eng.submit([5, 9, 11, 4], 10, 0.0, 0,
                                              None, 0)))
                ts.append(threading.Thread(
                    target=lambda: eng.submit([1, 2], 8, 0.7, 4, None, 7)))
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=300)
            assert "error" not in res["r"], res["r"]
            return res["r"]["new_tokens"]
        finally:
            eng.stop()

    alone = run_once(False)
    crowded = run_once(True)
    again = run_once(True)
    assert alone == crowded == again, \
        "sampled output must not depend on batch composition"


def test_validation_errors(model):
    module, params = model
    eng = _engine(module, params)
    try:
        assert "error" in eng.submit([], 4, 0.0, 0, None, 0)
        assert "error" in eng.submit([1] * 60, 10, 0.0, 0, None, 0)
        assert "error" in eng.submit([1], 4, 0.9, eng.max_top_k + 1,
                                     None, 0)
        assert eng.submit([1], 0, 0.0, 0, None, 0)["new_tokens"] == []
        # The engine still serves after rejections.
        r = eng.submit([5, 9], 3, 0.0, 0, None, 0)
        assert r["new_tokens"] == _solo(module, params, [5, 9], 3)
    finally:
        eng.stop()


def test_long_prompt_near_window_still_serves(model):
    """Padding must never push a valid request past ``max_seq_len``.
    llama_tiny's window here is 64 tokens = 4 pages of 16: 40 + 8 tokens
    need 3 pages, which ``_wbucket`` rounds to 4, and 61 + 3 sit exactly
    at the window, where the last chunk's page demand and the table
    window are both capped by the window's own page count."""
    module, params = model
    eng = _engine(module, params)
    try:
        for n_prompt, n_new in ((40, 8), (61, 3)):
            prompt = [(i % 37) + 1 for i in range(n_prompt)]
            r = eng.submit(prompt, n_new, temperature=0.0, top_k=0,
                           eos_id=None, seed=0)
            assert "error" not in r, r
            assert r["new_tokens"] == _solo(module, params, prompt, n_new)
    finally:
        eng.stop()


def test_server_with_continuous_engine(model):
    """End to end over the wire with engine='continuous'."""
    from serverless_learn_tpu.inference.server import (
        GenerationServer, request)

    module, params = model
    srv = GenerationServer(module, params, engine="continuous").start()
    try:
        prompts = [[5, 9, 11], [7, 3, 2, 8], [4, 4], [1, 2, 3, 4, 5]]
        reps = [None] * 4

        def client(i):
            reps[i] = request(srv.addr, {"prompt": prompts[i],
                                         "max_new_tokens": 4})

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        for i, p in enumerate(prompts):
            assert reps[i].get("new_tokens") == _solo(module, params, p, 4)
    finally:
        srv.stop()


# -- a paged slot is released when its request's budget is dispatched --------
#
# Threaded, through ``submit``: whatever order the clients arrive in, the
# engine pays one row-chunk per row-chunk owed and the replies do not
# depend on how many chunks it keeps in flight.

CHUNK = 4
# (prompt, max_new, temperature, top_k, seed): more requests than slots,
# budgets from 1 token (no chunk owed) to 17 (four chunks), prompts of one
# to four prefill chunks; every reply ends by its budget (no EOS id).
MIX = [([5, 9, 11], 1, 0.0, 0, 0),
       ([7, 3, 2, 8, 1, 30, 12, 9, 4, 2, 6, 1, 8], 2, 0.0, 0, 0),
       ([4], 5, 0.0, 0, 0),
       ([1, 2], 6, 0.9, 8, 11),
       ([9, 8, 7, 6, 5, 4], 9, 0.0, 0, 0),
       ([2, 2, 3, 3, 4, 4, 5], 4, 0.7, 0, 12),
       ([6, 1], 13, 0.0, 0, 0),
       ([3, 1, 4, 1, 5, 9, 2, 6, 5], 3, 0.0, 0, 0),
       ([8, 8, 1], 8, 1.0, 4, 13),
       ([11, 12, 13, 14, 15], 17, 0.0, 0, 0)]
LIMIT_S = 120.0     # each client's own time limit, and each join's


def _paged(module, params, depth):
    return ContinuousBatchingEngine(
        module, params, max_slots=4, chunk_size=CHUNK,
        pipeline_depth=depth, registry=MetricsRegistry(),
        kv=KVCacheConfig(block_size=4, prefill_chunk=4))


def _start_clients(eng, mix):
    """One client thread per request of ``mix``, started; the replies
    land in the returned list."""
    replies = [None] * len(mix)

    def client(i):
        prompt, n, temp, topk, seed = mix[i]
        replies[i] = eng.submit(prompt, n, temp, topk, None, seed,
                                timeout_s=LIMIT_S)

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(len(mix))]
    for t in threads:
        t.start()
    return threads, replies


def _join_clients(threads, limit_s):
    for t in threads:
        t.join(timeout=limit_s)
    assert not any(t.is_alive() for t in threads), "a client hung"


def _submit_all(eng, mix):
    threads, replies = _start_clients(eng, mix)
    _join_clients(threads, LIMIT_S + 30)
    return replies


@pytest.fixture(scope="module")
def mix_run(model):
    """One run of MIX through a paged engine per ``pipeline_depth``."""
    runs = {}

    def get(depth: int) -> dict:
        if depth not in runs:
            eng = _paged(*model, depth)
            try:
                replies = _submit_all(eng, MIX)
            finally:
                eng.stop()
            runs[depth] = {"engine": eng, "replies": replies}
        return runs[depth]

    return get


@pytest.mark.parametrize("depth", [1, 2])
def test_row_chunks_paid_equal_row_chunks_owed(mix_run, depth):
    """Prefill yields a reply's first token and each chunk CHUNK more, so
    a reply of n tokens owes ceil((n - 1) / CHUNK) row-chunks; the engine
    dispatches exactly those, because a slot is released at the dispatch
    that exhausts its budget and not at that chunk's harvest."""
    eng = mix_run(depth)["engine"]
    owed = sum(-(-(n - 1) // CHUNK) for _, n, _, _, _ in MIX)
    assert eng.decoded_rows_total == owed == 17
    assert eng.slots_released_total == len(MIX) == eng.requests_finished
    assert eng.preemptions == 0
    st = eng.kv_stats()
    assert (st["blocks_total"] - st["blocks_free"]
            == st["prefix_blocks_cached"])


@pytest.mark.parametrize("depth", [1, 2])
def test_released_greedy_replies_match_solo_generate(model, mix_run, depth):
    module, params = model
    for (prompt, n, temp, _, _), rep in zip(MIX, mix_run(depth)["replies"]):
        assert rep is not None and "error" not in rep, rep
        assert len(rep["new_tokens"]) == n
        if temp == 0.0:
            assert rep["new_tokens"] == _solo(module, params, prompt, n)


def test_replies_do_not_depend_on_pipeline_depth(model, mix_run):
    """Greedy and sampled alike: a row's stream depends on its request
    alone (``fold_in(seed, position)``), so two chunks in flight give the
    tokens one gives, and a sampled request alone gives them again."""
    one, two = mix_run(1)["replies"], mix_run(2)["replies"]
    assert [r["new_tokens"] for r in one] == [r["new_tokens"] for r in two]
    sampled = [m for m in MIX if m[2] > 0.0]
    assert len(sampled) == 3
    eng = _paged(*model, 2)
    try:
        for m in sampled:
            alone = _submit_all(eng, [m])[0]
            assert alone["new_tokens"] == two[MIX.index(m)]["new_tokens"]
    finally:
        eng.stop()


def test_stop_mid_traffic_answers_every_client(model):
    """``stop()`` while requests are queued, in slots and (released at
    dispatch) in flight: it returns, and every client gets its answer or
    an error; none waits out its timeout."""
    eng = _paged(*model, 2)
    mix = [(p, 24, 0.0, 0, 0) for p, _, _, _, _ in MIX]
    threads, replies = _start_clients(eng, mix)
    deadline = time.time() + LIMIT_S
    while ((eng._m_requests.value < len(mix) or eng.chunks_run < 3)
           and time.time() < deadline):
        time.sleep(0.005)
    t0 = time.time()
    eng.stop()
    _join_clients(threads, 20)
    assert time.time() - t0 < 40
    for rep in replies:
        assert rep is not None and ("new_tokens" in rep or "error" in rep)
    assert any("error" in rep for rep in replies), \
        "ten replies of 24 tokens cannot all have been done by chunk 3"
