"""``chipbench/program_records.py`` and its three readers
(``prefill_rows_per_program``, ``prefill_ms_per_token``,
``prefill_device_share``): the window's ``programs`` entries lined up with
a planted device trace by name and clock; events cut at either edge left
out of durations; a pattern that repeats settled by the clock alone; a
trace that cannot be lined up gives nothing and says why; a window with no
prefill program; a program whose records list no programs (the parent's);
and the tiny serving cell on the CPU, where the record reads and the two
trace readers read nothing."""

import pytest

import tiny
from chipbench import program_records, run
from chipbench.cell import load_cell

STEMS = ("prefill_rows_per_program", "prefill_ms_per_token",
         "prefill_device_share")
P = 0.4            # an iteration, host and device alike (saturated)
T_WS = 1000.0      # the window's opening instant on the host's clock
CHUNK = "jit_chunk_n8w64"
# Prefill programs: (name, rows, tokens, device seconds).
A = ("jit_pre_n8t32w64", 8, 240, 0.016)
B = ("jit_pre_n4t32w16", 3, 80, 0.012)
C = ("jit_pre_n1t32w4", 1, 20, 0.010)
D = ("jit_pre_n2t32w16", 2, 50, 0.011)


def _read(stem, r):
    return run.load_metric_reader(stem + ".tput", tiny.BENCH)(r, None)


def _engine(cycle, lag, n_iters=40, trace_at=4.05, trace_s=1.5,
            zero=program_records.TRACE_ZERO_S):
    """A saturated engine: iteration i launches ``cycle[i % len(cycle)]``'s
    prefill programs, then a chunk, 1 ms apart from ``T_WS - 2 + i * P``;
    the device runs each program when the one before has ended, never
    before its launch, and ``lag`` behind the first launch, and every
    iteration's programs take ``P`` on it (the chunk takes the rest). The
    window's records are the iterations that end in (T_WS, T_WS + 6]; the
    trace starts ``trace_at`` s after its zero, ``zero`` after the window
    opens, and lasts ``trace_s``. Returns (the run a reader gets,
    [(launch, device start, device end)])."""
    t_dev, truth, records = None, [], []
    for i in range(n_iters):
        t0 = T_WS - 2 + i * P
        pre = cycle[i % len(cycle)]
        progs = list(pre) + [(CHUNK, 8, 256, P - sum(p[3] for p in pre))]
        entries = []
        for j, (name, rows, tokens, secs) in enumerate(progs):
            t_s = t0 + 1e-3 * (j + 1)
            start = t_s + lag if t_dev is None else max(t_s, t_dev)
            t_dev = start + secs
            entry = {"prog": name, "rows": rows, "tokens": tokens,
                     "t_s": t_s, "compiled": False}
            entries.append(entry)
            truth.append((entry, start, t_dev))
        if T_WS < t0 + P <= T_WS + 6:
            records.append({"event": "sched_iter", "t0_s": t0, "dur_s": P,
                            "programs": entries})
    zero_at = T_WS + zero
    lo, hi = zero_at + trace_at, zero_at + trace_at + trace_s
    events = [(max(s, lo) - zero_at, min(e, hi) - zero_at, entry["prog"])
              for entry, s, e in truth if e > lo and s < hi]
    reduced = {"module_events": events,
               "recorded": (events[0][0], events[-1][1])}
    spans = [{"event": "span", "span_id": "x"}] + records
    return ({"record": {"spans": spans, "t_window_start": T_WS},
             "trace": reduced, "notes": {}}, truth)


def _truth_pairs(r, truth):
    """The (entry, whole event) pairs the trace really holds."""
    first, last = r["trace"]["recorded"]
    zero_at = T_WS + program_records.TRACE_ZERO_S
    out = []
    for entry, s, e in truth:
        if s - zero_at > first + 1e-6 and e - zero_at < last - 1e-6:
            out.append((entry, e - s))
    return out


VARIED = [(A, A, B), (A, C), (D, A, A, B), (C,)]


def test_lines_up_a_varied_window_and_gives_hand_numbers():
    r, truth = _engine(VARIED, lag=0.9 * P)
    pairs = program_records.aligned(r)
    assert pairs is not None
    whole = _truth_pairs(r, truth)
    got = [(p, e - s) for p, (s, e, _, w) in pairs if w]
    assert [p for p, _ in got] == [p for p, _ in whole]
    for (_, a), (_, b) in zip(got, whole):
        assert a == pytest.approx(b, abs=1e-9)
    pre = [(p, d) for p, d in whole if p["prog"].startswith("jit_pre_")]
    assert _read("prefill_ms_per_token", r) == pytest.approx(
        1e3 * sum(d for _, d in pre) / sum(p["tokens"] for p, _ in pre))
    notes = r["notes"]
    assert notes["programs_compiled"] == 0
    assert notes["program_alignment"]["matched"] == len(pairs)
    assert notes["prefill_events_whole"] == len(pre)
    table = notes["program_buckets"]
    assert list(table)[0] == CHUNK and len(table) <= 8
    assert table["jit_pre_n8t32w64"]["rows"] == 8
    assert table["jit_pre_n8t32w64"]["ms_p50"] == pytest.approx(16.0)


def test_rows_per_program_is_the_windows_mean():
    r, _ = _engine(VARIED, lag=0.9 * P)
    pre = program_records.prefill_entries(r)
    assert _read("prefill_rows_per_program", r) == pytest.approx(
        sum(p["rows"] for p in pre) / len(pre))
    assert r["notes"]["prefill_programs"] == len(pre)


def test_device_share_counts_each_entry_at_its_kinds_traced_mean():
    r, truth = _engine(VARIED, lag=0.9 * P)
    launched = program_records.entries(r)
    whole = _truth_pairs(r, truth)

    def mean(prefix):
        d = [x for p, x in whole if p["prog"].startswith(prefix)]
        return sum(d) / len(d)

    n_pre = sum(p["prog"].startswith("jit_pre_") for p in launched)
    n_chunk = len(launched) - n_pre
    pre, chunk = n_pre * mean("jit_pre_"), n_chunk * mean("jit_chunk_")
    assert _read("prefill_device_share", r) == pytest.approx(
        100.0 * pre / (pre + chunk))


@pytest.mark.parametrize("edge", ["start", "end"])
def test_an_event_cut_at_either_edge_gives_no_duration(edge):
    """The trace starts (ends) inside a chunk: that event is matched but
    gives no duration, and the numbers are those of the whole ones."""
    at = {"start": 4.05 + 0.2, "end": 4.05}[edge]
    span = {"start": 1.5, "end": 1.5 + 0.25}[edge]
    r, truth = _engine(VARIED, lag=0.9 * P, trace_at=at, trace_s=span)
    pairs = program_records.aligned(r)
    assert pairs is not None
    first, last = r["trace"]["recorded"]
    cut = [ev for _, ev in pairs if not ev[3]]
    assert cut and all(ev[0] <= first + 1e-6 or ev[1] >= last - 1e-6
                       for ev in cut)
    chunk_secs = [e - s for _, (s, e, n, w) in pairs if w and n == CHUNK]
    assert chunk_secs and all(d > 0.3 for d in chunk_secs)
    whole = [p for p, _ in _truth_pairs(r, truth)]
    assert [p for p, ev in pairs if ev[3]] == whole


def test_a_repeating_pattern_is_settled_by_the_clock():
    """Every fourth iteration alike: the names leave an offset every
    cycle; only the one where each launch precedes its event, and the
    next does not, is taken."""
    cycle = [(A, B), (A,), (C, D), (B,)]
    r, truth = _engine(cycle, lag=0.9 * P)
    pairs = program_records.aligned(r)
    assert pairs is not None
    note = r["notes"]["program_alignment"]
    assert note["offsets"] > 1
    assert note["margin_s"] >= 0.5 * P
    assert [p for p, ev in pairs if ev[3]] \
        == [p for p, _ in _truth_pairs(r, truth)]


def test_iterations_all_alike_leave_too_little_margin():
    """One iteration's pattern throughout, the device almost an iteration
    behind: the next later offset misses by a few milliseconds, well
    under half an iteration, so nothing is taken."""
    r, _ = _engine([(A, B)], lag=0.98 * P)
    assert program_records.aligned(r) is None
    assert "under half an iteration" in r["notes"]["program_alignment"]
    for stem in ("prefill_ms_per_token",):
        assert _read(stem, r) is None


def test_a_trace_that_cannot_be_lined_up_gives_nothing():
    """An event whose name no launch has at that place: no offset
    agrees by name."""
    r, _ = _engine(VARIED, lag=0.9 * P)
    s, e, _ = r["trace"]["module_events"][3]
    r["trace"]["module_events"][3] = (s, e, "jit_pre_n8t32w1024")
    assert _read("prefill_ms_per_token", r) is None
    assert "no offset" in r["notes"]["program_alignment"]
    # The window's own mean still reads: it needs no trace.
    assert _read("prefill_rows_per_program", r) is not None


def test_a_clock_that_puts_every_event_before_its_launch_gives_nothing():
    r, _ = _engine(VARIED, lag=0.9 * P)
    r["record"]["t_window_start"] -= 20.0
    assert program_records.aligned(r) is None
    assert "no offset puts every launch" in \
        r["notes"]["program_alignment"]


def test_a_window_with_no_prefill_program():
    r, _ = _engine([()], lag=0.5 * P)
    assert program_records.prefill_entries(r) == []
    assert _read("prefill_rows_per_program", r) is None
    assert _read("prefill_ms_per_token", r) is None
    assert _read("prefill_device_share", r) == 0.0


@pytest.mark.parametrize("stem", STEMS)
def test_records_without_programs_read_nothing(stem):
    """The parent's program: records with no ``programs`` key, programs
    named ``jit_pre`` / ``jit_chunk`` without their bucket."""
    r, _ = _engine(VARIED, lag=0.9 * P)
    for rec in r["record"]["spans"]:
        rec.pop("programs", None)
    r["trace"]["module_events"] = [
        (s, e, "jit_pre" if n.startswith("jit_pre_") else "jit_chunk")
        for s, e, n in r["trace"]["module_events"]]
    assert _read(stem, r) is None


@pytest.mark.parametrize("stem", STEMS)
def test_no_trace_and_no_records_read_nothing(stem):
    assert _read(stem, {"record": {"spans": []}, "notes": {}}) is None


@pytest.fixture(scope="module")
def tiny_run(tiny_root):
    """The tiny closed-loop serving cell's own record (CPU: no device
    trace)."""
    cell = load_cell("tiny-backlog", tiny_root)
    tracer = run.Tracer(False, "", {}, run.CompileCounter())
    record = run.load_driver(cell.kind).run(cell, 2 ** 31 + 38, 0.6, tracer)
    return {"cell": cell, "record": record, "notes": {}}


def test_tiny_cell_reads_its_rows_and_no_device_number(tiny_run):
    bench = tiny_run["cell"].bench_dir
    values = {stem: run.load_metric_reader(stem + ".tput", bench)(
        tiny_run, None) for stem in STEMS}
    c = tiny_run["record"]["counters"]
    pre = program_records.prefill_entries(tiny_run)
    assert pre and 1.0 <= values["prefill_rows_per_program"] <= 4.0
    # The window's rows are the engine's row-chunks, up to the
    # iterations that straddle its edges.
    assert sum(p["rows"] for p in pre) == pytest.approx(
        c["prefill_chunks"], abs=2 * 4)
    assert values["prefill_ms_per_token"] is None
    assert values["prefill_device_share"] is None
    assert tiny_run["notes"]["program_alignment"] \
        == "no jit_pre_/jit_chunk_ event traced"
