"""Readers' helper: the serving programs the scheduler launched in the
window, and the device trace's events of the same programs, lined up.

Host side: the ``programs`` entries of the window's ``sched_iter``
records, in dispatch order (``prog``, the program's module name, which
carries its bucket: ``jit_pre_n8t32w64``, ``jit_chunk_n8w64``; ``rows``;
``tokens``; ``t_s``, the launch's ``perf_counter`` instant; ``compiled``).
Device side: device 0's ``XLA Modules`` events whose names start
``jit_pre_`` or ``jit_chunk_``, in order (``trace_reduce``'s
``module_events``).

One dispatcher thread launches into one device queue, so the trace's
events are a run of consecutive launches. The run is found by the names:
an offset into the launches at which every event that has a launch
opposite it has that launch's name. Up to ``HEAD`` events at the trace's
head may have none: the device runs behind the host, and the trace opens
after the window's first record. An iteration ends with at most the
engine's ``pipeline_depth`` programs in flight (2, the default, which
the harness's servers keep), so at most that many programs launched by
iterations whose records precede the window are still to run when the
trace opens. Where the names leave several offsets
(the traffic repeats its length cycle), the clock settles it: a launch
precedes its event, and the device never runs a whole period behind, so
the offset taken is the latest one at which every launch precedes its
event. An event's instant on the host's clock is the window's opening
instant (``record["t_window_start"]``) plus ``TRACE_ZERO_S`` plus the
event's start as the trace gives it (seconds from the trace's zero).

``TRACE_ZERO_S``: how far the trace's zero lies after the window's
opening instant. The harness opens the trace from the thread that waits
for the window to open (``Tracer.window_opens``: a directory removed,
``jax.profiler.start_trace``), so the zero follows the instant by that
thread's wake-up and the profiler's start. Measured on a TPU v5 lite
(PR 38, both serving cells, change and parent): each traced
``sched.iter`` annotation against its record's ``t0_s`` puts the zero
0.52-0.99 ms after the instant (0.1-0.4 ms after ``window_opens`` is
called; ``start_trace`` returns some 45 ms later). The device runs
0.39-0.45 s behind the host there, so an error of a millisecond moves no
alignment.

The margin of an alignment is how far that clock could be wrong before
another offset would be taken: the least time by which a launch precedes
its event where an earlier offset also holds, and the least time by
which the next later offset puts a launch after its event. An alignment
with less margin than half an iteration (the median ``dur_s`` of the
window's records) is not taken, nor one where no offset holds: the
helper then returns nothing and says why in the line's ``notes``. Only
whole events (``trace_reduce.whole_events``' rule: one that touches the
first or the last recorded instant is cut) give durations.
"""

from __future__ import annotations

import statistics

from chipbench import trace_reduce
from chipbench.sched_records import iterations

PREFILL, CHUNK = "jit_pre_", "jit_chunk_"
TRACE_ZERO_S = 0.001
HEAD = 2               # the engine's pipeline_depth
MAX_BUCKETS = 8        # rows of the per-bucket table in the notes


def entries(run) -> list:
    """The window's launches: every record's ``programs`` entries, in
    order. Empty for a program whose records do not list them."""
    return [p for rec in iterations(run) for p in rec.get("programs", ())]


def prefill_entries(run) -> list:
    return [p for p in entries(run) if p["prog"].startswith(PREFILL)]


def events(run) -> list:
    """[(start_s, end_s, name, whole)] of device 0's serving programs, in
    order; ``whole``: the trace holds the launch from start to end."""
    reduced = run.get("trace") or {}
    if not reduced.get("recorded"):
        return []
    first, last = reduced["recorded"]
    gap = trace_reduce.MIN_GAP_S
    return [(s, e, name, s > first + gap and e < last - gap)
            for s, e, name in reduced["module_events"]
            if name.startswith((PREFILL, CHUNK))]


def _slack(run, launches, evs, k):
    """Least (event instant on the host's clock - launch instant) over the
    events that have launch ``k + j`` opposite them and whose start the
    trace holds (the first event may have begun before the trace did);
    ``None`` where a name differs or no such event is opposite."""
    zero = run["record"]["t_window_start"] + TRACE_ZERO_S
    first = run["trace"]["recorded"][0]
    slacks = []
    for j, (s, _, name, _) in enumerate(evs):
        i = k + j
        if not 0 <= i < len(launches):
            continue
        if launches[i]["prog"] != name:
            return None
        if s > first + trace_reduce.MIN_GAP_S:
            slacks.append(zero + s - launches[i]["t_s"])
    return min(slacks, default=None)


def _align(run):
    """(pairs [(entry, event)], notes) or (None, notes)."""
    launches, evs = entries(run), events(run)
    notes = {"programs_compiled": sum(bool(p["compiled"])
                                      for p in launches)}
    if not launches:
        notes["program_alignment"] = "no programs entries in the window"
        return None, notes
    if not evs:
        notes["program_alignment"] = "no jit_pre_/jit_chunk_ event traced"
        return None, notes
    cands = [(k, sl) for k in range(-HEAD, len(launches))
             if (sl := _slack(run, launches, evs, k)) is not None]
    held = [i for i, (_, sl) in enumerate(cands) if sl >= 0]
    if not held:
        notes["program_alignment"] = (
            f"no offset puts every launch before its event "
            f"({len(cands)} agree by name)")
        return None, notes
    i = held[-1]
    k = cands[i][0]
    sides = (([cands[i][1]] if len(held) > 1 else [])
             + ([-cands[i + 1][1]] if i + 1 < len(cands) else []))
    margin = min(sides, default=None)
    half = 0.5 * statistics.median(rec["dur_s"] for rec in iterations(run))
    if margin is not None and margin < half:
        notes["program_alignment"] = (
            f"margin {margin:.4f} s under half an iteration ({half:.4f} s)"
            f" among {len(cands)} offsets that agree by name")
        return None, notes
    pairs = [(launches[k + j], ev) for j, ev in enumerate(evs)
             if 0 <= k + j < len(launches)]
    notes["program_alignment"] = {
        "matched": len(pairs), "events": len(evs), "offsets": len(cands),
        "margin_s": "names alone" if margin is None else round(margin, 4),
        "half_iteration_s": round(half, 4)}
    notes["program_buckets"] = _buckets(pairs)
    return pairs, notes


def _buckets(pairs) -> dict:
    """By program name: events matched, whole events, their median
    milliseconds, the matched entries' mean rows and tokens; the names
    whose whole events took the most device time first."""
    by = {}
    for entry, (s, e, name, whole) in pairs:
        by.setdefault(name, []).append((entry, e - s if whole else None))
    rows = []
    for name, got in by.items():
        secs = [d for _, d in got if d is not None]
        rows.append((-sum(secs), name, {
            "matched": len(got), "whole": len(secs),
            "ms_p50": round(1e3 * statistics.median(secs), 4)
            if secs else None,
            "rows": round(statistics.fmean(p["rows"] for p, _ in got), 3),
            "tokens": round(statistics.fmean(p["tokens"] for p, _ in got),
                            3)}))
    rows.sort(key=lambda r: r[:2])
    return {name: row for _, name, row in rows[:MAX_BUCKETS]}


def aligned(run):
    """The window's launches lined up with the trace's events: [(entry,
    (start_s, end_s, name, whole))], or ``None``. Says in the run's notes
    how it went and, where it went, the per-bucket table."""
    pairs, notes = _align(run)
    run.setdefault("notes", {}).update(notes)
    return pairs


def mean_whole_seconds(run, prefix: str):
    """Mean device seconds of the trace's whole events of one kind
    (``PREFILL`` or ``CHUNK``); ``None`` where there is none."""
    secs = [e - s for s, e, name, whole in events(run)
            if whole and name.startswith(prefix)]
    return statistics.fmean(secs) if secs else None
