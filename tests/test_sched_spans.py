"""The scheduler's own account: one ``sched_iter`` record per working
iteration of the continuous engine's dispatch loop, sent to the event
sink beside the request spans.

CPU, tiny model, a list sink. The pool is large enough that nothing is
preempted and no request has an EOS, so every sum below is exact.

Scenarios ``unique``, ``shared_prefix`` and ``no_prefix_cache`` (the
``unique`` requests on a pool without a prefix trie) run under an
explicit ``prefill_budget`` (a cap on an iteration's prompt tokens);
``derived`` runs the default quota: a program's rows are consecutive
chunks of the mid-prefill prompts, oldest admitted first,
``chunk_size // 2`` programs at most while a slot decodes. Its schedule
is stepped by hand, so that it is the same on every run.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from serverless_learn_tpu.config import KVCacheConfig
from serverless_learn_tpu.inference.continuous import (
    ContinuousBatchingEngine, _Request)
from serverless_learn_tpu.models.registry import get_model
from serverless_learn_tpu.telemetry import MetricsRegistry, Span, flight

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
# The derived quota's mix. The first request runs alone: its 8 chunks go
# through an idle engine, in two programs. The rest are queued at once:
# four prefill in full while no slot decodes, the others are admitted
# beside decoding slots (CHUNK // 2 = 2 programs an iteration), and once
# the 30-token prompt's eight chunks leave a younger slot unfed.
LONG = ([(list(range(100, 129)), 5)] + UNIQUE
        + [(list(range(40, 10, -1)), 8), (list(range(50, 59)), 6),
           (list(range(60, 71)), 3)])
BUDGET = 8


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


def _engine(module, params, budget: int = BUDGET,
            prefix_cache: bool = True, **kw):
    kv = KVCacheConfig(block_size=4, prefill_chunk=4,
                       prefill_budget=budget, prefix_cache=prefix_cache)
    return ContinuousBatchingEngine(
        module, params, max_slots=MAX_SLOTS, chunk_size=CHUNK, kv=kv,
        registry=MetricsRegistry(), **kw)


def _count_prefill_programs(eng) -> list:
    """Every prefill program the engine dispatches fetches its jit by
    ``_paged_prefill_jit`` once: count the fetches."""
    calls = []
    real = eng._paged_prefill_jit

    def counting(nb, T, W):
        calls.append((nb, T, W))
        return real(nb, T, W)

    eng._paged_prefill_jit = counting
    return calls


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


def _drive_by_hand(eng, requests) -> list:
    """The dispatcher stopped and the scheduler stepped from here: the
    first request alone, then the others queued at once in their order,
    so the schedule is the same on every run. The requests are built as
    ``submit`` builds them."""
    eng.stop()

    def put(prompt, n):
        r = _Request(prompt=np.asarray(prompt, np.int32), max_new=n,
                     temperature=0.0, top_k=0, eos_id=None, seed=0,
                     span=Span("request"), wf=eng._new_waterfall())
        eng._q.put(r)
        return r

    seq = 0
    rs = []
    for wave in (requests[:1], requests[1:]):
        rs += [put(p, n) for p, n in wave]
        while not all(r.done.is_set() for r in rs):
            seq += 1
            assert seq < 500, "the scheduler made no progress"
            eng._iterate(seq)
    assert all("error" not in r.result for r in rs), [r.result for r in rs]
    return [r.result for r in rs]


def _run_scenario(model, name: str) -> dict:
    module, params = model
    requests = {"shared_prefix": SHARED, "derived": LONG}.get(name, UNIQUE)
    sink = ListSink()
    eng = _engine(module, params,
                  budget=0 if name == "derived" else BUDGET,
                  prefix_cache=name != "no_prefix_cache", event_log=sink)
    programs = _count_prefill_programs(eng)
    ring0 = len(flight.events())
    try:
        if name == "derived":
            replies = _drive_by_hand(eng, requests)
        else:
            replies = _drive(eng, requests,
                             first_alone=name == "shared_prefix")
    finally:
        eng.stop()
    return {"scenario": name, "requests": requests,
            "replies": replies, "engine": eng, "programs": programs,
            "iters": [r for r in sink.records
                      if r.get("event") == "sched_iter"],
            "spans": [r for r in sink.records if r.get("event") == "span"],
            "ring": flight.events()[ring0:]}


@pytest.fixture(scope="module")
def scenario(model):
    """One engine run per scenario, its sink's records split by kind."""
    runs = {}

    def get(name: str) -> dict:
        if name not in runs:
            runs[name] = _run_scenario(model, name)
        return runs[name]

    return get


@pytest.fixture(scope="module", params=["unique", "shared_prefix",
                                        "no_prefix_cache", "derived"])
def run(request, scenario):
    return scenario(request.param)


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
    # More requests than slots: some iteration saw a queue, and some saw
    # every slot taken.
    if run["scenario"] in ("unique", "no_prefix_cache", "derived"):
        assert max(r["queued"] for r in run["iters"]) > 0
        assert min(r["slots_free"] for r in run["iters"]) == 0


def test_prompt_tokens_are_prefilled_or_hit_exactly_once(run):
    prompt_tokens = sum(len(p) for p, _ in run["requests"])
    sent, hit = _total(run, "prefill_tokens"), _total(run,
                                                      "prefill_hit_tokens")
    assert sent + hit == prompt_tokens
    assert (run["engine"]._trie is None) \
        == (run["scenario"] == "no_prefix_cache")
    if run["scenario"] == "shared_prefix":
        # Every later request skips the system prompt's whole blocks.
        assert hit >= (len(SHARED) - 1) * 8
    else:
        assert hit == 0
    for r in run["iters"]:
        if run["scenario"] != "derived":
            # An explicit prefill_budget still caps an iteration's tokens.
            assert r["prefill_tokens"] <= BUDGET
        assert r["prefill_rows"] <= r["slots_prefilling"]


def _row_chunks(run) -> int:
    """Row-chunks as the requests' own waterfalls count them."""
    n = 0
    for s in run["spans"]:
        for ph in s["waterfall"]["phases"]:
            if ph["phase"] == "prefill":
                n += len(ph.get("chunks", ()))
    return n


def test_prefill_steps_are_the_programs_dispatched(run):
    """``prefill_steps`` counts programs, ``prefill_row_chunks`` the rows
    they carried (the engine's ``prefill_chunks_run``, the requests' own
    waterfall chunks), ``prefill_rows`` the distinct slots they fed."""
    eng = run["engine"]
    assert _total(run, "prefill_steps") == len(run["programs"])
    assert len(run["programs"]) > 0
    assert _total(run, "prefill_row_chunks") == eng.prefill_chunks_run \
        == _row_chunks(run)
    assert _total(run, "prefill_rows") <= eng.prefill_chunks_run
    for r in run["iters"]:
        assert bool(r["prefill_steps"]) == bool(r["prefill_rows"]) \
            == bool(r["prefill_tokens"]) == bool(r["prefill_row_chunks"])
        # No program is wider than the programs the engine has.
        assert (r["prefill_steps"] <= r["prefill_row_chunks"]
                <= r["prefill_steps"] * MAX_SLOTS)
        assert r["prefill_rows"] <= min(r["slots_prefilling"],
                                        r["prefill_row_chunks"])
        assert r["prefill_tokens"] <= r["prefill_row_chunks"] * 4
    assert all(nb <= MAX_SLOTS and T <= 8 for nb, T, _ in run["programs"])


def test_derived_quota_feeds_every_prefilling_slot(scenario):
    """The default quota, on a schedule stepped by hand: oldest admitted
    first, each slot taking every row it can. A mid-prefill slot goes
    unfed in an iteration only when the quota went to slots admitted
    before it (the pool refuses no pages here), and then every program
    of that iteration is full; while a slot decodes an iteration
    dispatches at most ``chunk_size // 2`` prefill programs; while none
    does, every prompt finishes its prefill in that iteration and joins
    its decode chunk."""
    run = scenario("derived")
    assert int(run["engine"]._m_kv_blocked.value) == 0
    stalled = unbounded = capped = unfed = 0
    for r in run["iters"]:
        if not r["slots_prefilling"]:
            assert r["prefill_steps"] == 0
            continue
        if r["slots_decoding"]:
            stalled += 1
            assert 1 <= r["prefill_steps"] <= CHUNK // 2, r
            capped += r["prefill_steps"] == CHUNK // 2
            if r["prefill_rows"] < r["slots_prefilling"]:
                unfed += 1
                assert r["prefill_row_chunks"] \
                    == (CHUNK // 2) * MAX_SLOTS, r
        else:
            unbounded += 1
            assert r["prefill_rows"] == r["slots_prefilling"], r
            assert r["decode_rows"] == r["slots_prefilling"], r
    # Both regimes occurred, the cap was reached, once with a slot left
    # unfed, and the first prompt's eight chunks went through in one
    # iteration, in ceil(8 / max_slots) programs.
    assert stalled and unbounded and capped and unfed
    first = run["iters"][0]
    assert first["prefill_steps"] == -(-8 // MAX_SLOTS)
    assert first["prefill_row_chunks"] == 8
    assert first["prefill_tokens"] == len(LONG[0][0])


def test_decode_rows_match_the_engines_counters(run):
    eng = run["engine"]
    assert _total(run, "decode_rows") == eng.decoded_rows_total
    assert _total(run, "decode_steps") == eng.chunks_run * CHUNK
    for r in run["iters"]:
        assert r["decode_rows"] <= MAX_SLOTS
        assert r["decode_steps"] in (0, CHUNK)
        assert bool(r["decode_rows"]) == bool(r["decode_steps"])


def test_slots_released_are_the_requests_that_ended_by_budget(run):
    """No request has an EOS id, so every reply ends by its budget: the
    engine releases each slot at the dispatch that exhausts it, and so
    dispatches exactly the row-chunks the replies owe."""
    eng = run["engine"]
    assert _total(run, "slots_released") == eng.slots_released_total
    for r in run["iters"]:
        # At most the rows of its chunk and its finishing prefill rows.
        assert 0 <= r["slots_released"] <= 2 * MAX_SLOTS
    owed = sum(-(-(n - 1) // CHUNK) for _, n in run["requests"])
    assert _total(run, "slots_released") == len(run["requests"]) \
        == _total(run, "requests_finished")
    assert _total(run, "decode_rows") == owed
    # A released request holds no slot: nothing is found finished and
    # not yet retired, and no submitter timed out.
    assert _total(run, "slots_other") == 0
    for r in run["iters"]:
        assert r["slots_released"] <= r["decode_rows"] + r["prefill_rows"]


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


# ---- ``state_resets``: slots whose recurrent state started from zero ------

def test_a_model_without_slot_state_resets_nothing(run):
    assert _total(run, "state_resets") == 0
    assert run["engine"].state_resets_total == 0


@pytest.fixture(scope="module")
def recurrent_model(devices):
    """The registry's tiny hybrid: Mamba, Mamba, attention, Mamba."""
    bundle = get_model("granite_hybrid_tiny", dtype=jnp.float32,
                       param_dtype=jnp.float32, max_seq_len=64)
    params = bundle.module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return bundle.module, params


@pytest.mark.parametrize("num_blocks", [0, 16],
                         ids=["roomy_pool", "one_sequence_of_pool"])
def test_state_resets_are_admissions_plus_readmissions(recurrent_model,
                                                       num_blocks):
    """A prefill row at position 0 starts its slot's state from zero: one
    per admission and one per re-admission after a preemption, exactly.
    The tight pool (one max-length sequence under long budgets) preempts;
    the roomy one never does."""
    module, params = recurrent_model
    sink = ListSink()
    eng = ContinuousBatchingEngine(
        module, params, max_slots=MAX_SLOTS, chunk_size=CHUNK,
        kv=KVCacheConfig(block_size=4, prefill_chunk=4,
                         num_blocks=num_blocks),
        registry=MetricsRegistry(), event_log=sink)
    requests = [(p, 24) for p, _ in UNIQUE]
    replies = _drive_by_hand(eng, requests)
    assert [len(r["new_tokens"]) for r in replies] == [24] * len(requests)
    iters = [r for r in sink.records if r.get("event") == "sched_iter"]
    resets = sum(r["state_resets"] for r in iters)
    assert (eng.preemptions > 0) == bool(num_blocks)
    assert resets == eng.state_resets_total \
        == len(requests) + eng.preemptions
    for r in iters:
        # One row a slot a program: never more resets than rows, never
        # more rows than the slots mid-prefill times the programs.
        assert r["state_resets"] <= r["prefill_row_chunks"] \
            <= r["prefill_steps"] * MAX_SLOTS
        assert r["prefill_rows"] <= r["slots_prefilling"]
