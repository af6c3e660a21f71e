"""The scheduler's own account: one ``sched_iter`` record per working
iteration of the continuous engine's dispatch loop, sent to the event
sink beside the request spans.

CPU, tiny model, a list sink. The pool is large enough that nothing is
preempted and no request has an EOS, so every sum below is exact.
"""

import threading

import jax
import jax.numpy as jnp
import pytest

from serverless_learn_tpu.config import KVCacheConfig
from serverless_learn_tpu.inference.continuous import (
    ContinuousBatchingEngine)
from serverless_learn_tpu.models.registry import get_model
from serverless_learn_tpu.telemetry import MetricsRegistry, flight

MAX_SLOTS, CHUNK = 4, 4
SYSTEM = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8]   # three whole blocks of 4
# (prompt, max_new): more requests than slots, mixed lengths, one prompt
# that prefills in four chunks.
UNIQUE = [([5, 9, 11], 6), ([7, 3, 2, 8, 1, 30, 12, 9, 4, 2, 6, 1, 8], 9),
          ([4], 3), ([1, 2], 5), ([9, 8, 7, 6, 5, 4], 7),
          ([2, 2, 3, 3, 4, 4, 5], 4), ([6, 1], 10)]
SHARED = [(SYSTEM + tail, n) for tail, n in
          [([11, 2], 5), ([9, 7], 6), ([44, 45, 46], 4), ([8], 7),
           ([7, 7, 7, 7, 7], 3), ([1, 2], 5)]]


class ListSink:
    def __init__(self):
        self.records = []

    def emit(self, rec: dict) -> None:
        self.records.append(rec)


@pytest.fixture(scope="module")
def model(devices):
    bundle = get_model("llama_tiny", dtype=jnp.float32,
                       param_dtype=jnp.float32, max_seq_len=64)
    params = bundle.module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return bundle.module, params


def _engine(module, params, paged: bool = True, **kw):
    kv = (KVCacheConfig(block_size=4, prefill_chunk=4, prefill_budget=8)
          if paged else KVCacheConfig(paged=False))
    return ContinuousBatchingEngine(
        module, params, max_slots=MAX_SLOTS, chunk_size=CHUNK, kv=kv,
        registry=MetricsRegistry(), **kw)


def _drive(eng, requests, first_alone: bool = False) -> list:
    """Submit every request from its own thread; ``first_alone`` lets the
    first one finish before the rest arrive (its prompt's blocks are then
    in the prefix trie). Returns the replies in request order."""
    replies = [None] * len(requests)

    def client(i):
        prompt, n = requests[i]
        replies[i] = eng.submit(prompt, n, temperature=0.0, top_k=0,
                                eos_id=None, seed=0)

    start = 0
    if first_alone:
        client(0)
        start = 1
    threads = [threading.Thread(target=client, args=(i,))
               for i in range(start, len(requests))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert all(r is not None and "error" not in r for r in replies), replies
    return replies


@pytest.fixture(scope="module", params=["unique", "shared_prefix",
                                        "monolithic"])
def run(request, model):
    """One engine run per scenario, its sink's records split by kind."""
    module, params = model
    requests = SHARED if request.param == "shared_prefix" else UNIQUE
    sink = ListSink()
    eng = _engine(module, params, paged=request.param != "monolithic",
                  event_log=sink)
    ring0 = len(flight.events())
    try:
        replies = _drive(eng, requests,
                         first_alone=request.param == "shared_prefix")
    finally:
        eng.stop()
    return {"scenario": request.param, "requests": requests,
            "replies": replies, "engine": eng,
            "iters": [r for r in sink.records
                      if r.get("event") == "sched_iter"],
            "spans": [r for r in sink.records if r.get("event") == "span"],
            "ring": flight.events()[ring0:]}


def _total(run, field):
    return sum(r[field] for r in run["iters"])


def test_one_record_per_working_iteration_in_order(run):
    iters = run["iters"]
    assert len(iters) >= 3
    seqs = [r["seq"] for r in iters]
    assert seqs == sorted(set(seqs)), "seq repeats or runs backwards"
    for r in iters:
        assert r["engine"] == "continuous" and r["dur_s"] > 0
        worked = (r["prefill_rows"] or r["decode_rows"] or r["tokens_out"]
                  or r["requests_finished"] or r["prefill_hit_tokens"]
                  or r["slots_prefilling"] or r["slots_decoding"])
        assert worked, f"an idle iteration was recorded: {r}"
    starts = [r["t0_s"] for r in iters]
    assert starts == sorted(starts)


def test_census_sums_to_max_slots(run):
    for r in run["iters"]:
        assert r["max_slots"] == MAX_SLOTS
        census = [r[k] for k in ("slots_decoding", "slots_prefilling",
                                 "slots_free", "slots_other")]
        assert min(census) >= 0 and sum(census) == MAX_SLOTS, r
        assert r["queued"] >= 0
    # Seven requests over four slots: some iteration saw a queue, and
    # some saw every slot taken.
    if run["scenario"] == "unique":
        assert max(r["queued"] for r in run["iters"]) > 0
        assert min(r["slots_free"] for r in run["iters"]) == 0


def test_prompt_tokens_are_prefilled_or_hit_exactly_once(run):
    prompt_tokens = sum(len(p) for p, _ in run["requests"])
    sent, hit = _total(run, "prefill_tokens"), _total(run,
                                                      "prefill_hit_tokens")
    if run["scenario"] == "monolithic":
        # Its admit program prefills whole prompts: the fields stay 0.
        assert (sent, hit, _total(run, "prefill_rows")) == (0, 0, 0)
        return
    assert sent + hit == prompt_tokens
    assert _total(run, "prefill_rows") == run["engine"].prefill_chunks_run
    if run["scenario"] == "shared_prefix":
        # Every later request skips the system prompt's whole blocks.
        assert hit >= (len(SHARED) - 1) * 8
    else:
        assert hit == 0
    for r in run["iters"]:
        # The engine's own rule: at most prefill_budget tokens a step.
        assert r["prefill_tokens"] <= 8
        assert r["prefill_rows"] <= r["slots_prefilling"]


def test_decode_rows_match_the_engines_counters(run):
    eng = run["engine"]
    assert _total(run, "decode_rows") == eng.decoded_rows_total
    assert _total(run, "decode_steps") == eng.chunks_run * CHUNK
    for r in run["iters"]:
        assert r["decode_rows"] <= MAX_SLOTS
        assert r["decode_steps"] in (0, CHUNK)
        assert bool(r["decode_rows"]) == bool(r["decode_steps"])


def test_tokens_out_are_the_replies(run):
    reply_tokens = sum(len(r["new_tokens"]) for r in run["replies"])
    assert reply_tokens == sum(n for _, n in run["requests"])
    assert _total(run, "tokens_out") == reply_tokens
    assert _total(run, "requests_finished") == len(run["requests"])


def test_requests_name_emitted_request_spans(run):
    span_ids = {s["span_id"] for s in run["spans"]}
    assert len(span_ids) == len(run["requests"])
    named = set()
    for r in run["iters"]:
        assert len(r["requests"]) <= 2 * MAX_SLOTS
        assert len(set(r["requests"])) == len(r["requests"])
        if r["prefill_rows"] or r["decode_rows"]:
            assert r["requests"]
        named.update(r["requests"])
    assert named == span_ids


def test_phases_fit_inside_the_iteration(run):
    names = {"queue_idle", "admit", "prefill", "decode", "harvest_wait",
             "harvest"}
    for r in run["iters"]:
        assert set(r["phases_s"]) == names
        assert min(r["phases_s"].values()) >= -1e-9, r["phases_s"]
        assert sum(r["phases_s"].values()) <= r["dur_s"] + 1e-9
    if run["scenario"] == "monolithic":
        assert all(r["phases_s"]["prefill"] == 0.0 for r in run["iters"])


def test_records_stay_out_of_the_span_readers_way_and_the_ring(run):
    for r in run["iters"]:
        assert "marks_s" not in r and "waterfall" not in r
    assert run["ring"], "request spans still reach the flight ring"
    assert not [e for e in run["ring"] if e.get("event") == "sched_iter"]


def test_no_sink_no_record(model, monkeypatch):
    """Off means off: without an event sink the builder never runs."""
    module, params = model
    built = []
    real = ContinuousBatchingEngine._sched_record

    def spy(self, *a, **kw):
        built.append(1)
        return real(self, *a, **kw)

    monkeypatch.setattr(ContinuousBatchingEngine, "_sched_record", spy)
    eng = _engine(module, params)
    try:
        _drive(eng, UNIQUE[:3])
        assert not built
        # A sink set on a live engine (as the benchmark's harness does)
        # is honoured from the next iteration on.
        eng.event_log = ListSink()
        _drive(eng, UNIQUE[3:5])
        assert built
        assert len([r for r in eng.event_log.records
                    if r["event"] == "sched_iter"]) == len(built)
    finally:
        eng.stop()


def test_generation_server_takes_a_sink(model, tmp_path):
    """``event_sink=`` reaches the engine: request spans and the
    scheduler's records land in the caller's own object."""
    from serverless_learn_tpu.inference.server import (GenerationServer,
                                                       request)

    module, params = model
    sink = ListSink()
    kv = KVCacheConfig(block_size=4, prefill_chunk=4, prefill_budget=8)
    with pytest.raises(ValueError, match="not both"):
        GenerationServer(module, params, registry=MetricsRegistry(), kv=kv,
                         event_sink=sink,
                         event_log_path=str(tmp_path / "e.jsonl"))
    srv = GenerationServer(module, params, registry=MetricsRegistry(),
                           kv=kv, event_sink=sink).start()
    try:
        assert srv.engine.event_log is sink
        rep = request(srv.addr, {"prompt": [5, 9, 11], "max_new_tokens": 3})
        assert len(rep["new_tokens"]) == 3
    finally:
        srv.stop()
    kinds = {r.get("event") for r in sink.records}
    assert {"span", "sched_iter"} <= kinds
