"""Per-request serving attribution (round 21): `slt waterfall`.

The serving plane exported only aggregate histograms
(`slt_request_ttft_seconds`, `slt_decode_seconds_per_token`) — enough to
see THAT p99 moved, useless for saying WHY. This module is the serving
twin of `slt xray`: instead of step-interior hardware attribution, it
does request-interior time attribution.

Two halves, one schema:

**Recording** (runs inside the engines/router, stdlib-only, no jax):
:class:`RequestWaterfall` is a per-request ledger owned by the request —
like :class:`~.registry.Span`, no locks, writers hand off with the
request. It accumulates the phase timeline (queue wait, admission,
compile-on-new-bucket charged separately, per-chunk prefill with
prefix-hit tokens) and a per-token decode trace: every inter-token gap
above an EWMA baseline is attributed to named causes by intersecting the
gap window with the engine's own boundary events, which land in a shared
:class:`BoundaryEvents` ring (this one IS locked — the dispatcher and
admission paths both write it). The finished ledger rides the request
span's ``meta["waterfall"]`` into the node's JSONL event log, so no new
log stream or sink exists — `slt trace` / `slt doctor` pick it up from
the same files they already read.

**Analysis** (`slt waterfall`, offline): merge engine span records with
the router's ``waterfall_hop`` records by W3C ``trace_id`` into fleet-
wide per-request waterfalls, then decompose: TTFT p99 = queue + admit +
compile + prefill (the decomposition is EXACT by construction — prefill
is the remainder of the admit->first_token window after carving out
measured compile and admission work, so the invariant check below is a
schema check, not a float-luck check), and ITL p99 with a stall-cause
breakdown where ``base_s + sum(causes) == gap_s`` for every recorded
stall.

Attribution contract: interval causes (compile, prefill_steal,
compaction, harvest_drain) claim their measured overlap with the gap
window, scaled down proportionally if they over-explain the excess;
marker causes (preempt, kv_exhausted — instants, not intervals) split
whatever excess remains unexplained; a residual with no marker present
is reported honestly as ``other`` rather than smeared onto the nearest
named cause.

The ``spec_verify`` phase is RESERVED here (schema + taxonomy) for the
ROADMAP speculative-decode integration: when spec decode joins the
continuous engine, its verify passes slot into the existing schema with
no version bump.
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

SCHEMA_VERSION = 1

# Stall-cause taxonomy (ITL gap attribution). Interval causes carry a
# measured [t0, t1); marker causes are instants whose cost shows up only
# as the gap's unexplained excess.
STALL_CAUSES = (
    "compile",         # new-bucket jit (admit/prefill/decode bucket miss)
    "preempt",         # KV-pressure preemption / restart of a victim
    "prefill_steal",   # a prefill chunk ran between decode steps
    "kv_exhausted",    # KV block pool exhausted; decode backpressured
    "compaction",      # live decode batch re-packed after retire/preempt
    "harvest_drain",   # dispatcher blocked draining an earlier future
    "weight_swap",     # in-place params swap (canary rollout, round 23)
)
MARKER_CAUSES = frozenset({"preempt", "kv_exhausted"})
# "other": residual excess with no boundary event in the window — kept
# out of STALL_CAUSES so the taxonomy stays a list of *named* causes.
OTHER_CAUSE = "other"

# Phase taxonomy. ``spec_verify`` is reserved for speculative decode
# (satellite of this round; see inference/speculative.py metrics).
PHASES = ("queue", "admit", "compile", "prefill", "decode",
          "spec_verify")

_EPS = 1e-9


class BoundaryEvents:
    """Bounded ring of the engine's own boundary events, as absolute
    ``time.perf_counter()`` intervals ``(t0, t1, cause)``.

    Shared across all in-flight requests of one engine, hence locked
    (admission, prefill, decode and harvest all note into it). Readers
    (:meth:`overlap`) snapshot under the lock and intersect outside it.
    Marker causes are noted with ``t1 == t0``.
    """

    def __init__(self, window: int = 256):
        self._lock = threading.Lock()
        self._events: collections.deque = collections.deque(
            maxlen=max(16, int(window)))

    def note(self, cause: str, t0: float, t1: Optional[float] = None):
        t0 = float(t0)
        t1 = t0 if t1 is None else float(t1)
        with self._lock:
            self._events.append((t0, max(t0, t1), str(cause)))

    def overlap(self, g0: float, g1: float) -> Dict[str, float]:
        """Per-cause overlap seconds with the window ``[g0, g1]``.
        Marker causes present in the window appear with value 0.0 (a
        presence flag — they claim residual excess, not overlap)."""
        with self._lock:
            events = list(self._events)
        out: Dict[str, float] = {}
        for t0, t1, cause in events:
            if t1 < g0 or t0 > g1:
                continue
            if cause in MARKER_CAUSES or t1 - t0 <= _EPS:
                out.setdefault(cause, 0.0)
            else:
                out[cause] = out.get(cause, 0.0) \
                    + max(0.0, min(t1, g1) - max(t0, g0))
        return out


class RequestWaterfall:
    """One request's lifecycle ledger. Owned by the request (no locks;
    same ownership discipline as ``Span``). All timestamps passed in are
    absolute ``time.perf_counter()`` values; :meth:`finalize` rebases to
    span-relative seconds for the record.

    ``overhead_s`` self-accounts the ledger's own decode-path host time
    (the <2%-of-decode-wall-clock budget is asserted in tests from this
    number, not hand-waved).
    """

    __slots__ = ("engine", "ewma_alpha", "stall_mult", "min_stall_s",
                 "max_stall_events", "max_gap_samples",
                 "prefill_chunks", "events", "gap_s", "gap_tokens",
                 "stalls", "stall_totals", "compile_s", "admit_s",
                 "itl_ewma", "last_t", "itl_count", "itl_sum", "itl_max",
                 "overhead_s")

    def __init__(self, engine: str = "continuous",
                 ewma_alpha: float = 0.3,
                 stall_mult: float = 2.0,
                 min_stall_s: float = 0.002,
                 max_stall_events: int = 64,
                 max_gap_samples: int = 256):
        self.engine = engine
        self.ewma_alpha = float(ewma_alpha)
        self.stall_mult = float(stall_mult)
        self.min_stall_s = float(min_stall_s)
        self.max_stall_events = int(max_stall_events)
        self.max_gap_samples = int(max_gap_samples)
        self.prefill_chunks: List[dict] = []
        self.events: List[Tuple[float, float, str]] = []
        self.gap_s: List[float] = []
        self.gap_tokens: List[int] = []
        self.stalls: List[dict] = []
        self.stall_totals: Dict[str, float] = {}
        self.compile_s = 0.0
        self.admit_s = 0.0
        self.itl_ewma: Optional[float] = None
        self.last_t: Optional[float] = None
        self.itl_count = 0
        self.itl_sum = 0.0
        self.itl_max = 0.0
        self.overhead_s = 0.0

    # -- recording (engine side) ------------------------------------------

    def note_admit(self, t0: float, t1: float):
        """Host-side admission work (slot/KV alloc, staging)."""
        self.admit_s += max(0.0, t1 - t0)

    def note_compile(self, t0: float, t1: float):
        """A new-bucket jit this request sat behind on its way to first
        token — charged separately so TTFT decomposition can name it."""
        self.compile_s += max(0.0, t1 - t0)

    def note_prefill_chunk(self, t0: float, t1: float, tokens: int,
                           prefix_hit_tokens: int = 0,
                           compiled: bool = False,
                           stall_s: Optional[float] = None):
        """One prefill chunk: tokens fed, tokens served by the prefix
        cache, and the budget-stall gap since the previous chunk
        (computed here when not supplied — the wait this chunk spent
        parked behind the per-boundary prefill budget)."""
        if stall_s is None:
            stall_s = (max(0.0, float(t0) - self.prefill_chunks[-1]["t1"])
                       if self.prefill_chunks else 0.0)
        if len(self.prefill_chunks) < 128:
            self.prefill_chunks.append({
                "t0": float(t0), "t1": float(t1),
                "tokens": int(tokens),
                "prefix_hit_tokens": int(prefix_hit_tokens),
                "compiled": bool(compiled),
                "stall_s": round(max(0.0, stall_s), 6)})

    def note_event(self, cause: str, t0: float, t1: Optional[float] = None):
        """A per-request boundary event (e.g. this request's own preempt
        -> re-admission window) — merged with the engine-global ring at
        attribution time."""
        t0 = float(t0)
        if len(self.events) < 128:
            self.events.append((t0, t0 if t1 is None else float(t1),
                                str(cause)))

    def first_token(self, t: float):
        """Anchor the decode trace at first-token arrival."""
        if self.last_t is None:
            self.last_t = float(t)

    def note_decode(self, t: float, n_tokens: int,
                    boundary: Optional[BoundaryEvents] = None,
                    ) -> Optional[Tuple[float, Optional[Dict[str, float]]]]:
        """One harvest delivering ``n_tokens`` for this request at
        absolute time ``t``. Returns ``(itl_s, causes)`` — the per-token
        latency of this gap, plus the per-cause stall attribution
        (seconds summing to the above-baseline excess) when the gap
        stalled, else None. The engine feeds ``itl_s`` into
        ``slt_decode_itl_seconds`` and the dict straight into
        ``slt_decode_stall_seconds_total{cause}``. Returns None for the
        anchoring first call."""
        t_in = time.perf_counter()
        try:
            if self.last_t is None:
                self.last_t = float(t)
                return None
            gap = max(0.0, float(t) - self.last_t)
            self.last_t = float(t)
            n = max(1, int(n_tokens))
            itl = gap / n
            self.itl_count += n
            self.itl_sum += gap
            self.itl_max = max(self.itl_max, itl)
            if len(self.gap_s) < self.max_gap_samples:
                self.gap_s.append(gap)
                self.gap_tokens.append(n)
            base = self.itl_ewma
            if base is None:
                self.itl_ewma = itl
                return (itl, None)
            expected = base * n
            excess = gap - expected
            if excess <= max(self.min_stall_s,
                             expected * (self.stall_mult - 1.0)):
                # Baseline tracks only unstalled gaps, so one compile
                # can't inflate it into masking the next stall.
                self.itl_ewma = base + self.ewma_alpha * (itl - base)
                return (itl, None)
            causes = self._attribute(float(t) - gap, float(t), excess,
                                     boundary)
            for c, v in causes.items():
                self.stall_totals[c] = self.stall_totals.get(c, 0.0) + v
            if len(self.stalls) < self.max_stall_events:
                self.stalls.append({
                    "t": float(t), "gap_s": round(gap, 6),
                    "tokens": n,
                    "base_s": round(gap - excess, 6),
                    "causes": {c: round(v, 6)
                               for c, v in sorted(causes.items())}})
            return (itl, causes)
        finally:
            self.overhead_s += time.perf_counter() - t_in

    def _attribute(self, g0: float, g1: float, excess: float,
                   boundary: Optional[BoundaryEvents],
                   ) -> Dict[str, float]:
        """Split ``excess`` seconds across causes whose events intersect
        [g0, g1]. Interval causes claim measured overlap (scaled down if
        they over-explain); markers split the remainder; a bare residual
        is ``other``. Sum over the result == excess (the per-gap
        breakdown invariant)."""
        ov: Dict[str, float] = {}
        if boundary is not None:
            ov.update(boundary.overlap(g0, g1))
        for t0, t1, cause in self.events:
            if t1 < g0 or t0 > g1:
                continue
            if cause in MARKER_CAUSES or t1 - t0 <= _EPS:
                ov.setdefault(cause, 0.0)
            else:
                ov[cause] = ov.get(cause, 0.0) \
                    + max(0.0, min(t1, g1) - max(t0, g0))
        causes: Dict[str, float] = {}
        interval_total = sum(v for v in ov.values() if v > _EPS)
        if interval_total > _EPS:
            scale = min(1.0, excess / interval_total)
            for c, v in ov.items():
                if v > _EPS:
                    causes[c] = v * scale
        leftover = excess - sum(causes.values())
        if leftover > _EPS:
            markers = sorted(c for c, v in ov.items() if v <= _EPS)
            if markers:
                for c in markers:
                    causes[c] = causes.get(c, 0.0) + leftover / len(markers)
            else:
                causes[OTHER_CAUSE] = causes.get(OTHER_CAUSE, 0.0) + leftover
        return causes

    # -- finalize ---------------------------------------------------------

    def finalize(self, span) -> dict:
        """The JSONL-ready ledger, rebased to span-relative seconds.
        Stored by the engines in ``span.meta["waterfall"]`` so it rides
        the existing request-span record."""
        t_in = time.perf_counter()
        t0 = span.t0
        marks = span.marks
        admit_t = marks.get("admit", 0.0)
        ft = marks.get("first_token")
        done = marks.get("done", span.duration_s)
        phases: List[dict] = [
            {"phase": "queue", "t0_s": 0.0, "t1_s": round(admit_t, 6),
             "s": round(admit_t, 6)}]
        decomp: Dict[str, float] = {}
        if ft is not None:
            # Exact-by-construction decomposition: compile and admission
            # are measured and clamped into the admit->first_token
            # window; prefill is the remainder. queue+admit+compile+
            # prefill == TTFT with no float luck.
            window = max(0.0, ft - admit_t)
            compile_s = min(self.compile_s, window)
            admit_s = min(self.admit_s, window - compile_s)
            prefill_s = window - compile_s - admit_s
            decomp = {"queue": round(admit_t, 6),
                      "admit": round(admit_s, 6),
                      "compile": round(compile_s, 6),
                      "prefill": round(prefill_s, 6)}
            phases.append({"phase": "admit", "s": round(admit_s, 6)})
            phases.append({"phase": "compile", "s": round(compile_s, 6)})
            work = {"phase": "prefill",
                    "t1_s": round(ft, 6), "s": round(prefill_s, 6)}
            if self.prefill_chunks:
                work["chunks"] = [
                    {"t0_s": round(c["t0"] - t0, 6),
                     "t1_s": round(c["t1"] - t0, 6),
                     "tokens": c["tokens"],
                     "prefix_hit_tokens": c["prefix_hit_tokens"],
                     "compiled": c["compiled"],
                     "stall_s": c["stall_s"]}
                    for c in self.prefill_chunks]
            phases.append(work)
            if done > ft + _EPS:
                phases.append({"phase": "decode", "t0_s": round(ft, 6),
                               "t1_s": round(done, 6),
                               "s": round(done - ft, 6)})
        wf: dict = {"v": SCHEMA_VERSION, "engine": self.engine,
                    "phases": phases}
        if decomp:
            wf["ttft_s"] = round(ft, 6)
            wf["ttft_decomp_s"] = decomp
        if self.itl_count:
            wf["itl"] = {"count": self.itl_count,
                         "mean_s": round(self.itl_sum / self.itl_count, 6),
                         "max_s": round(self.itl_max, 6),
                         "baseline_s": round(self.itl_ewma or 0.0, 6)}
            wf["gaps"] = [[round(g, 6), n] for g, n
                          in zip(self.gap_s, self.gap_tokens)]
        if self.stalls:
            rebased = []
            for s in self.stalls:
                s = dict(s)
                s["t_s"] = round(s.pop("t") - t0, 6)
                rebased.append(s)
            wf["stalls"] = rebased
        if self.stall_totals:
            wf["stall_s"] = {c: round(v, 6) for c, v
                             in sorted(self.stall_totals.items())}
        self.overhead_s += time.perf_counter() - t_in
        wf["overhead_s"] = round(self.overhead_s, 6)
        return wf


# -- analysis (slt waterfall) ------------------------------------------------


def read_records(paths: Sequence[str]) -> List[dict]:
    """JSONL records from files/directories (plus ``*.jsonl.1`` rotation
    siblings and flight-dump ``.json`` files), bad lines skipped —
    doctor's tolerance rules, locally."""
    files: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            for name in sorted(os.listdir(p)):
                if name.endswith((".jsonl", ".jsonl.1", ".json")):
                    files.append(os.path.join(p, name))
        elif os.path.exists(p):
            files.append(p)
    records: List[dict] = []
    for path in files:
        try:
            with open(path) as f:
                if path.endswith(".json"):
                    obj = json.load(f)
                    recs = obj.get("records", []) \
                        if isinstance(obj, dict) else obj
                    records.extend(r for r in recs if isinstance(r, dict))
                    continue
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        continue
                    if isinstance(rec, dict):
                        records.append(rec)
        except (IOError, OSError, ValueError):
            continue
    return records


def merge_requests(records: Sequence[dict]) -> List[dict]:
    """Engine request-span records (carrying ``waterfall``) merged with
    router ``waterfall_hop`` records by trace_id. Router-only entries
    (shed, or the engine log wasn't collected) are kept — a waterfall
    that silently dropped shed requests would under-report brownouts."""
    hops: Dict[str, dict] = {}
    orphans: List[dict] = []
    for rec in records:
        if rec.get("event") == "waterfall_hop":
            tid = rec.get("trace_id")
            if tid:
                hops[tid] = rec
            else:
                orphans.append(rec)
    out: List[dict] = []
    seen: set = set()
    for rec in records:
        if rec.get("event") != "span" or rec.get("span") != "request" \
                or not isinstance(rec.get("waterfall"), dict):
            continue
        tid = rec.get("trace_id")
        req = {"trace_id": tid, "node": rec.get("node"),
               "t0_unix_s": rec.get("t0_unix_s"),
               "duration_s": rec.get("duration_s"),
               "marks_s": rec.get("marks_s") or {},
               "waterfall": rec["waterfall"],
               "router": hops.get(tid)}
        if tid:
            seen.add(tid)
        out.append(req)
    for tid, hop in sorted(hops.items()):
        if tid not in seen:
            out.append({"trace_id": tid, "node": hop.get("node"),
                        "t0_unix_s": None, "duration_s": None,
                        "marks_s": {}, "waterfall": None, "router": hop})
    for hop in orphans:
        out.append({"trace_id": None, "node": hop.get("node"),
                    "t0_unix_s": None, "duration_s": None,
                    "marks_s": {}, "waterfall": None, "router": hop})
    return out


def _percentile(sorted_vals: Sequence[float], q: float) -> Optional[float]:
    if not sorted_vals:
        return None
    idx = min(len(sorted_vals) - 1,
              max(0, int(round(q * (len(sorted_vals) - 1)))))
    return sorted_vals[idx]


def _weighted_percentile(pairs: List[Tuple[float, int]], q: float,
                         ) -> Optional[float]:
    """q-quantile of a sample where each (value, weight) contributes
    ``weight`` observations — ITL gaps carrying several tokens."""
    if not pairs:
        return None
    pairs = sorted(pairs)
    total = sum(w for _, w in pairs)
    rank = q * total
    cum = 0
    for v, w in pairs:
        cum += w
        if cum >= rank:
            return v
    return pairs[-1][0]


def summarize(requests: Sequence[dict]) -> dict:
    """Fleet-wide percentile decompositions + stall-cause totals +
    router provenance rollup, with the two invariant checks the schema
    promises (TTFT decomposition sums to TTFT; per-stall cause breakdown
    sums to the gap)."""
    ttfts: List[Tuple[float, dict]] = []
    itl_pairs: List[Tuple[float, int]] = []
    stall_s: Dict[str, float] = {}
    decode_s = 0.0
    prefill_hit = prefill_tok = 0
    overhead_s = 0.0
    decomp_bad = stall_bad = 0
    engines: Dict[str, int] = {}
    hedged = hedge_wins = retries = sheds = 0
    hedge_wasted_s = 0.0
    for req in requests:
        hop = req.get("router")
        if hop:
            if hop.get("shed"):
                sheds += 1
            retries += int(hop.get("retries") or 0)
            if hop.get("hedged"):
                hedged += 1
                if hop.get("hedge_winner") \
                        and hop.get("hedge_winner") != hop.get("primary"):
                    hedge_wins += 1
                hedge_wasted_s += float(hop.get("hedge_wasted_s") or 0.0)
        wf = req.get("waterfall")
        if not wf:
            continue
        engines[wf.get("engine", "?")] = engines.get(
            wf.get("engine", "?"), 0) + 1
        overhead_s += float(wf.get("overhead_s") or 0.0)
        ttft = wf.get("ttft_s")
        decomp = wf.get("ttft_decomp_s") or {}
        if isinstance(ttft, (int, float)) and decomp:
            ttfts.append((float(ttft), decomp))
            # Invariant 1: the decomposition sums to measured TTFT.
            if abs(sum(decomp.values()) - ttft) > 0.05 * max(ttft, 1e-6):
                decomp_bad += 1
        for g, n in wf.get("gaps") or []:
            itl_pairs.append((float(g) / max(1, int(n)), int(n)))
        for phase in wf.get("phases") or []:
            if phase.get("phase") == "decode":
                decode_s += float(phase.get("s") or 0.0)
            for c in phase.get("chunks") or []:
                prefill_tok += int(c.get("tokens") or 0)
                prefill_hit += int(c.get("prefix_hit_tokens") or 0)
        for c, v in (wf.get("stall_s") or {}).items():
            stall_s[c] = stall_s.get(c, 0.0) + float(v)
        for s in wf.get("stalls") or []:
            # Invariant 2: base + causes == gap, per stall entry.
            total = float(s.get("base_s") or 0.0) \
                + sum((s.get("causes") or {}).values())
            if abs(total - float(s.get("gap_s") or 0.0)) \
                    > 0.02 * max(float(s.get("gap_s") or 0.0), 1e-6):
                stall_bad += 1
    ttfts.sort(key=lambda x: x[0])
    ttft_sorted = [t for t, _ in ttfts]
    out: dict = {
        "requests": len(requests),
        "with_waterfall": sum(bool(r.get("waterfall")) for r in requests),
        "engines": engines,
        "invariants": {"ttft_decomp_bad": decomp_bad,
                       "stall_sum_bad": stall_bad},
        "router": {"hedged": hedged, "hedge_wins": hedge_wins,
                   "hedge_wasted_s": round(hedge_wasted_s, 6),
                   "retries": retries, "sheds": sheds},
    }
    if ttft_sorted:
        ttft_block: dict = {}
        for q, key in ((0.5, "p50"), (0.95, "p95"), (0.99, "p99")):
            p = _percentile(ttft_sorted, q)
            ttft_block[key + "_s"] = round(p, 6)
            # The decomposition OF the percentile request — "p99 TTFT is
            # 80% compile" is the actionable sentence.
            idx = min(len(ttfts) - 1,
                      max(0, int(round(q * (len(ttfts) - 1)))))
            ttft_block[key + "_decomp_s"] = ttfts[idx][1]
        out["ttft"] = ttft_block
    if itl_pairs:
        out["itl"] = {
            "count": sum(n for _, n in itl_pairs),
            "p50_s": round(_weighted_percentile(itl_pairs, 0.5), 6),
            "p95_s": round(_weighted_percentile(itl_pairs, 0.95), 6),
            "p99_s": round(_weighted_percentile(itl_pairs, 0.99), 6)}
    if stall_s:
        total = sum(stall_s.values())
        out["stall_s"] = {c: round(v, 6) for c, v in sorted(
            stall_s.items(), key=lambda kv: -kv[1])}
        out["dominant_stall_cause"] = max(stall_s, key=stall_s.get) \
            if total > 0 else None
    if decode_s > 0:
        out["decode_s"] = round(decode_s, 6)
        out["prefill_interference_frac"] = round(
            stall_s.get("prefill_steal", 0.0) / decode_s, 6)
        out["ledger_overhead_frac"] = round(overhead_s / decode_s, 6)
    if prefill_tok:
        out["prefix_hit_frac"] = round(prefill_hit / prefill_tok, 6)
    return out


def report(paths: Sequence[str], top: int = 10) -> dict:
    """The `slt waterfall` body: read -> merge -> summarize, plus the
    ``top`` slowest requests with their full waterfalls."""
    records = read_records(paths)
    requests = merge_requests(records)
    slow = sorted(
        (r for r in requests if r.get("waterfall")),
        key=lambda r: -(r.get("duration_s") or 0.0))[:max(0, int(top))]
    return {"records": len(records), "summary": summarize(requests),
            "slowest": slow}


def bench_rows(summary: dict, device_kind: str = "cpu") -> List[dict]:
    """Bench-history rows for `utils/benchlog.record` / `slt bench
    --gate`: the ITL headline gates automatically (``*_ms`` -> better=
    min) and carries ``prefill_interference_frac`` + the TTFT
    decomposition as attribution columns."""
    rows: List[dict] = []
    itl = summary.get("itl") or {}
    ttft = summary.get("ttft") or {}
    if itl.get("p99_s") is not None:
        row = {"metric": "serve_itl_p99_ms",
               "value": round(itl["p99_s"] * 1e3, 3),
               "unit": "ms", "device_kind": device_kind,
               "count": itl.get("count")}
        if summary.get("prefill_interference_frac") is not None:
            row["prefill_interference_frac"] = \
                summary["prefill_interference_frac"]
        rows.append(row)
    if ttft.get("p99_s") is not None:
        row = {"metric": "serve_ttft_p99_ms",
               "value": round(ttft["p99_s"] * 1e3, 3),
               "unit": "ms", "device_kind": device_kind}
        for k, v in (ttft.get("p99_decomp_s") or {}).items():
            row[f"ttft_decomp_{k}_ms"] = round(float(v) * 1e3, 3)
        rows.append(row)
    return rows


def render(rep: dict, width: int = 64) -> str:
    """Human rendering: summary lines + per-request phase bars for the
    slowest requests."""
    s = rep.get("summary", {})
    lines = [f"waterfall: {rep.get('records', 0)} records, "
             f"{s.get('requests', 0)} requests "
             f"({s.get('with_waterfall', 0)} with ledger)"]
    ttft = s.get("ttft") or {}
    if ttft:
        d = ttft.get("p99_decomp_s") or {}
        parts = " + ".join(f"{k} {v * 1e3:.1f}ms" for k, v in d.items())
        lines.append(f"  TTFT p50/p95/p99: "
                     f"{ttft.get('p50_s', 0) * 1e3:.1f}/"
                     f"{ttft.get('p95_s', 0) * 1e3:.1f}/"
                     f"{ttft.get('p99_s', 0) * 1e3:.1f} ms"
                     + (f"   (p99 = {parts})" if parts else ""))
    itl = s.get("itl") or {}
    if itl:
        lines.append(f"  ITL p50/p95/p99: "
                     f"{itl.get('p50_s', 0) * 1e3:.2f}/"
                     f"{itl.get('p95_s', 0) * 1e3:.2f}/"
                     f"{itl.get('p99_s', 0) * 1e3:.2f} ms "
                     f"over {itl.get('count', 0)} tokens")
    if s.get("stall_s"):
        total = sum(s["stall_s"].values())
        bits = ", ".join(f"{c} {v:.3f}s ({v / total:.0%})"
                         for c, v in s["stall_s"].items())
        lines.append(f"  decode stalls: {bits}")
    if s.get("prefill_interference_frac") is not None:
        lines.append(f"  prefill interference: "
                     f"{s['prefill_interference_frac']:.1%} of decode; "
                     f"ledger overhead "
                     f"{s.get('ledger_overhead_frac', 0):.2%}")
    r = s.get("router") or {}
    if any(r.values()):
        lines.append(f"  router: {r.get('hedged', 0)} hedged "
                     f"({r.get('hedge_wins', 0)} won by hedge, "
                     f"{r.get('hedge_wasted_s', 0):.3f}s wasted), "
                     f"{r.get('retries', 0)} retries, "
                     f"{r.get('sheds', 0)} shed")
    inv = s.get("invariants") or {}
    if inv.get("ttft_decomp_bad") or inv.get("stall_sum_bad"):
        lines.append(f"  WARNING: invariant violations — "
                     f"{inv.get('ttft_decomp_bad', 0)} TTFT decomps, "
                     f"{inv.get('stall_sum_bad', 0)} stall sums")
    for req in rep.get("slowest", []):
        wf = req["waterfall"]
        tid = (req.get("trace_id") or "?")[:8]
        seg = []
        total = max(req.get("duration_s") or 0.0, 1e-9)
        for ph in wf.get("phases", []):
            w = int(round(width * float(ph.get("s") or 0.0) / total))
            if w > 0:
                seg.append((ph["phase"][:1].upper()) * w)
        hop = req.get("router") or {}
        extra = ""
        if hop.get("pick_reason"):
            # Round-22 join: WHY this hop chose its replica, by name —
            # the decision_id keys into `slt fleetscope`'s event stream.
            extra += f" via:{hop['pick_reason']}"
            if hop.get("decision_id"):
                extra += f"[{hop['decision_id']}]"
        if hop.get("hedged"):
            extra += " hedged"
            if hop.get("hedge_loser"):
                extra += f"(lost:{hop['hedge_loser']})"
        if wf.get("stall_s"):
            worst = max(wf["stall_s"], key=wf["stall_s"].get)
            extra += f" stall:{worst}"
        lines.append(f"  {tid} {total * 1e3:8.1f}ms "
                     f"|{''.join(seg):<{width}}|{extra}")
    if rep.get("slowest"):
        lines.append("  legend: Q queue  A admit  C compile  P prefill  "
                     "D decode  S spec_verify")
    return "\n".join(lines)


# -- self-check --------------------------------------------------------------


def synthetic_records() -> List[dict]:
    """Deterministic mini-fleet of records exercising every schema
    feature (compile stall, preempt stall, hedged hop, shed hop).
    Doubles as the committed-fixture generator —
    the fixture under tests/fixtures/waterfall/ is this, dumped."""
    def span(tid, node, marks, wf):
        return {"event": "span", "span": "request", "trace_id": tid,
                "span_id": tid[:16], "t0_unix_s": 1754000000.0,
                "duration_s": marks["done"], "marks_s": marks,
                "node": node, "waterfall": wf}

    def hop(tid, **kw):
        rec = {"event": "waterfall_hop", "trace_id": tid,
               "node": "router0", "shed": False, "retries": 0,
               "hedged": False}
        rec.update(kw)
        return rec

    recs = []
    # Request A: new-bucket compile stalls decode mid-stream; hedged,
    # won by the hedge replica.
    wf_a = {
        "v": SCHEMA_VERSION, "engine": "continuous",
        "phases": [
            {"phase": "queue", "t0_s": 0.0, "t1_s": 0.004, "s": 0.004},
            {"phase": "admit", "s": 0.001},
            {"phase": "compile", "s": 0.020},
            {"phase": "prefill", "t1_s": 0.045, "s": 0.020,
             "chunks": [{"t0_s": 0.025, "t1_s": 0.045, "tokens": 32,
                         "prefix_hit_tokens": 16, "compiled": True,
                         "stall_s": 0.0}]},
            {"phase": "decode", "t0_s": 0.045, "t1_s": 0.145, "s": 0.1}],
        "ttft_s": 0.045,
        "ttft_decomp_s": {"queue": 0.004, "admit": 0.001,
                          "compile": 0.020, "prefill": 0.020},
        "itl": {"count": 20, "mean_s": 0.005, "max_s": 0.030,
                "baseline_s": 0.003},
        "gaps": [[0.003, 1]] * 16 + [[0.030, 1]] + [[0.003, 1]] * 3,
        "stalls": [{"t_s": 0.1, "gap_s": 0.030, "tokens": 1,
                    "base_s": 0.003, "causes": {"compile": 0.027}}],
        "stall_s": {"compile": 0.027}, "overhead_s": 0.0004}
    recs.append(span("aa" * 16, "node0",
                     {"admit": 0.004, "first_token": 0.045,
                      "done": 0.145}, wf_a))
    recs.append(hop("aa" * 16, hedged=True, primary="n0:9000",
                    replica="n1:9000", hedge_winner="n1:9000",
                    hedge_loser="n0:9000", hedge_wasted_s=0.041,
                    hedge_cancel_s=0.012, queue_wait_s=0.001,
                    total_s=0.19, decision_id="aaaaaaaaaaaaaaaa-1",
                    pick_reason="least_loaded"))
    # Request B: preempted mid-decode; plain hop.
    wf_b = {
        "v": SCHEMA_VERSION, "engine": "continuous",
        "phases": [
            {"phase": "queue", "t0_s": 0.0, "t1_s": 0.002, "s": 0.002},
            {"phase": "admit", "s": 0.001},
            {"phase": "compile", "s": 0.0},
            {"phase": "prefill", "t1_s": 0.012, "s": 0.009,
             "chunks": [{"t0_s": 0.003, "t1_s": 0.012, "tokens": 24,
                         "prefix_hit_tokens": 0, "compiled": False,
                         "stall_s": 0.001}]},
            {"phase": "decode", "t0_s": 0.012, "t1_s": 0.212, "s": 0.2}],
        "ttft_s": 0.012,
        "ttft_decomp_s": {"queue": 0.002, "admit": 0.001,
                          "compile": 0.0, "prefill": 0.009},
        "itl": {"count": 40, "mean_s": 0.005, "max_s": 0.080,
                "baseline_s": 0.0035},
        "gaps": [[0.0035, 1]] * 30 + [[0.080, 1]] + [[0.004, 1]] * 9,
        "stalls": [{"t_s": 0.15, "gap_s": 0.080, "tokens": 1,
                    "base_s": 0.0035,
                    "causes": {"preempt": 0.0645,
                               "prefill_steal": 0.012}}],
        "stall_s": {"preempt": 0.0645, "prefill_steal": 0.012},
        "overhead_s": 0.0007}
    recs.append(span("bb" * 16, "node0",
                     {"admit": 0.002, "first_token": 0.012,
                      "done": 0.212, "preempt": 0.1}, wf_b))
    recs.append(hop("bb" * 16, primary="n0:9000", replica="n0:9000",
                    queue_wait_s=0.0004, total_s=0.22,
                    decision_id="bbbbbbbbbbbbbbbb-2",
                    pick_reason="session_affinity"))
    # Request D: shed at the router — no engine record at all.
    recs.append(hop("dd" * 16, shed=True, queue_wait_s=0.0,
                    total_s=0.0002, decision_id="dddddddddddddddd-3",
                    pick_reason="shed_queue_full"))
    return recs


def self_check(fixture_path: Optional[str] = None) -> dict:
    """`slt waterfall --self-check`: parse/merge/summarize a fixture
    (the committed one in CI; the embedded synthetic copy when no path
    is given) and verify every schema promise."""
    checks: List[dict] = []

    def check(name: str, ok: bool, detail: str = ""):
        checks.append({"check": name, "ok": bool(ok), "detail": detail})

    if fixture_path:
        records = read_records([fixture_path])
        check("fixture_read", len(records) > 0,
              f"{len(records)} records from {fixture_path}")
    else:
        records = synthetic_records()
        check("fixture_read", True,
              f"{len(records)} embedded synthetic records")
    requests = merge_requests(records)
    with_wf = [r for r in requests if r.get("waterfall")]
    check("merge", len(with_wf) >= 2 and len(requests) > len(with_wf),
          f"{len(requests)} requests, {len(with_wf)} with ledger "
          f"(router-only entries preserved)")
    merged_hop = any(r.get("router") and r.get("waterfall")
                     for r in requests)
    check("traceparent_merge", merged_hop,
          "router hop joined to an engine record by trace_id")
    hedge = [r for r in requests
             if (r.get("router") or {}).get("hedged")]
    check("hedge_provenance",
          any((r["router"].get("hedge_winner")
               and r["router"].get("hedge_loser")
               and r["router"].get("hedge_wasted_s") is not None)
              for r in hedge),
          f"{len(hedge)} hedged hop(s) carry winner/loser/wasted")
    check("decision_join",
          any((r.get("router") or {}).get("decision_id")
              and (r.get("router") or {}).get("pick_reason")
              for r in requests),
          "hop records carry route-decision id + pick reason (round 22)")
    bad_phase = [p.get("phase") for r in with_wf
                 for p in r["waterfall"].get("phases", [])
                 if p.get("phase") not in PHASES]
    check("phase_taxonomy", not bad_phase, f"unknown: {bad_phase}")
    known = set(STALL_CAUSES) | {OTHER_CAUSE}
    bad_cause = [c for r in with_wf
                 for c in (r["waterfall"].get("stall_s") or {})
                 if c not in known]
    check("stall_taxonomy", not bad_cause, f"unknown: {bad_cause}")
    summary = summarize(requests)
    inv = summary.get("invariants", {})
    check("ttft_decomposition", inv.get("ttft_decomp_bad") == 0,
          "queue+admit+compile+prefill == TTFT within 5% for all")
    check("stall_sums", inv.get("stall_sum_bad") == 0,
          "base_s + sum(causes) == gap_s for every stall entry")
    check("spec_verify_reserved", "spec_verify" in PHASES,
          "schema reserves the speculative-decode verify phase")
    rows = bench_rows(summary)
    names = {r["metric"] for r in rows}
    check("bench_rows",
          "serve_itl_p99_ms" in names and any(
              "prefill_interference_frac" in r for r in rows),
          f"rows: {sorted(names)}")
    return {"ok": all(c["ok"] for c in checks), "checks": checks}
