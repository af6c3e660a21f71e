"""The six readers of the scheduler's ``sched_iter`` records: each gives
the hand-computed number on hand-made records and nothing where the
window holds none; the request-span readers do not see the records; and
on the tiny serving cell's own record (CPU: counts only, no device
number) they agree with the engine's counters."""

import pytest

import tiny
from chipbench import run
from chipbench.cell import load_cell

STEMS = ("decode_slot_occupancy", "prefill_slot_share", "free_slot_share",
         "prefill_served_share", "prefill_tokens_per_s", "sched_host_ms_p50")


def _iter(dur_ms, wait_ms, idle_ms=0.0, **fields):
    rec = {"event": "sched_iter", "engine": "continuous", "max_slots": 8,
           "dur_s": dur_ms / 1e3,
           "phases_s": {"queue_idle": idle_ms / 1e3, "admit": 0.0,
                        "prefill": 0.0, "decode": 0.0,
                        "harvest_wait": wait_ms / 1e3, "harvest": 0.0},
           "slots_decoding": 0, "slots_prefilling": 0, "slots_free": 0,
           "slots_other": 0, "prefill_rows": 0, "prefill_tokens": 0,
           "decode_rows": 0, "decode_steps": 0}
    rec.update(fields)
    return rec


# Four iterations of an 8-slot engine in a 2 s window.
ITERS = [
    _iter(360, 350, slots_decoding=1, slots_prefilling=6, slots_free=1,
          prefill_rows=2, prefill_tokens=64, decode_rows=1, decode_steps=32),
    _iter(358, 352, slots_decoding=2, slots_prefilling=6,
          prefill_rows=2, prefill_tokens=48, decode_rows=3, decode_steps=32),
    # Admitted and prefilled only: no chunk, so not in the occupancy.
    _iter(20, 2, idle_ms=15, slots_prefilling=1, slots_free=7,
          prefill_rows=1, prefill_tokens=32),
    # Nothing mid-prefill: not in the served share.
    _iter(370, 366, slots_decoding=4, slots_free=4, decode_rows=4,
          decode_steps=32),
]
EXPECTED = {
    "decode_slot_occupancy": 100.0 * (1 + 3 + 4) / (3 * 8),
    "prefill_slot_share": 100.0 * (6 + 6 + 1) / (4 * 8),
    "free_slot_share": 100.0 * (1 + 7 + 4) / (4 * 8),
    "prefill_served_share": 100.0 * (2 + 2 + 1) / (6 + 6 + 1),
    "prefill_tokens_per_s": (64 + 48 + 32) / 2.0,
    "sched_host_ms_p50": (4.0 + 6.0) / 2,    # of 10, 6, 3, 4 ms
}
REQUEST_SPANS = [
    {"event": "span", "span": "request", "span_id": f"{i:016x}",
     "marks_s": {"admit": 0.5 * i, "first_token": 0.5 * i + 1.0,
                 "done": 0.5 * i + 2.0},
     "waterfall": {"phases": [{"chunks": [
         {"tokens": 32, "prefix_hit_tokens": 16 * i}]}]}}
    for i in (1, 2, 3)]


def _run(spans, window_s=2.0):
    return {"record": {"spans": spans, "window_s": window_s}}


def _read(stem, spans):
    return run.load_metric_reader(stem + ".tput", tiny.BENCH)(
        _run(spans), None)


@pytest.mark.parametrize("stem", STEMS)
def test_reader_gives_the_hand_computed_number(stem):
    # Request spans and other events in the list are not in its way.
    spans = REQUEST_SPANS[:1] + ITERS + [{"event": "weight_swap"}]
    assert _read(stem, spans) == pytest.approx(EXPECTED[stem], rel=1e-9)


@pytest.mark.parametrize("stem", STEMS)
def test_reader_is_silent_without_sched_records(stem):
    assert _read(stem, REQUEST_SPANS) is None
    assert _read(stem, []) is None
    assert run.load_metric_reader(stem + ".tput", tiny.BENCH)(
        {"record": {"window_s": 2.0}}, None) is None


@pytest.mark.parametrize("stem", ["decode_slot_occupancy",
                                  "prefill_served_share"])
def test_share_with_nothing_to_divide_by_is_silent(stem):
    assert _read(stem, [ITERS[2] | {"slots_prefilling": 0}]) is None


@pytest.mark.parametrize("stem,expected", [
    ("queue_wait_ms_p50", 1000.0), ("ttft_ms_p50", 2000.0),
    ("prefix_hit_share", 100.0 * 96 / (96 + 96))])
def test_request_span_readers_do_not_see_sched_records(stem, expected):
    alone = _read(stem, REQUEST_SPANS)
    mixed = _read(stem, [ITERS[0], REQUEST_SPANS[0], ITERS[1],
                         *REQUEST_SPANS[1:], *ITERS[2:]])
    assert alone == mixed == pytest.approx(expected)


@pytest.fixture(scope="module")
def tiny_run(tiny_root):
    """The tiny closed-loop serving cell's own record, as ``run_cell``
    hands it to the readers."""
    cell = load_cell("tiny-backlog", tiny_root)
    tracer = run.Tracer(False, "", {}, run.CompileCounter())
    record = run.load_driver(cell.kind).run(cell, 2 ** 31 + 26, 0.6, tracer)
    return {"cell": cell, "record": record}


def _values(tiny_run):
    bench = tiny_run["cell"].bench_dir
    return {stem: run.load_metric_reader(stem + ".tput", bench)(
        tiny_run, None) for stem in STEMS}


def test_tiny_cell_readers_agree_with_the_engines_counters(tiny_run):
    """The counters are read as the window opens and closes, the records
    arrive as iterations end: the two may differ by the iterations that
    straddle the window's edges, no more."""
    record = tiny_run["record"]
    c, slots = record["counters"], 4        # tiny-serve: max_batch 4
    iters = [r for r in record["spans"] if r.get("event") == "sched_iter"]
    assert len(iters) > 10
    assert all(r["max_slots"] == slots for r in iters)
    chunks = [r for r in iters if r["decode_steps"]]
    assert abs(len(chunks) - c["chunks_run"]) <= 2
    assert abs(sum(r["decode_rows"] for r in iters)
               - c["decoded_rows"]) <= 2 * slots
    v = _values(tiny_run)
    assert v["decode_slot_occupancy"] == pytest.approx(
        100.0 * c["decoded_rows"] / (c["chunks_run"] * slots), abs=5.0)
    # A saturated closed loop: the prompt tokens that enter are those of
    # the replies that leave, up to the requests in flight at the edges.
    assert v["prefill_tokens_per_s"] * record["window_s"] == pytest.approx(
        c["prompt_tokens_arrived"], rel=0.25)
    assert sum(r["tokens_out"] for r in iters) == pytest.approx(
        c["output_tokens_arrived"], rel=0.25)


def test_tiny_cell_slot_shares_are_consistent(tiny_run):
    v = _values(tiny_run)
    assert all(x is not None for x in v.values()), v
    for stem in ("decode_slot_occupancy", "prefill_slot_share",
                 "free_slot_share", "prefill_served_share"):
        assert 0.0 <= v[stem] <= 100.0
    assert v["prefill_slot_share"] + v["free_slot_share"] <= 100.0
    assert v["prefill_tokens_per_s"] > 0 and v["sched_host_ms_p50"] > 0
    # The accepted span readers still find their request spans beside
    # the scheduler's records.
    bench = tiny_run["cell"].bench_dir
    for stem in ("queue_wait_ms_p50", "ttft_ms_p50"):
        assert run.load_metric_reader(stem + ".tput", bench)(
            tiny_run, None) > 0
