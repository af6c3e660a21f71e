"""Request waterfalls (round 21): the per-request lifecycle ledger, its
attribution contract, the `slt waterfall` merge/decomposition pipeline
and router hop provenance.

The attribution contract under test: interval causes (compile,
harvest_drain) claim their measured overlap with a stalled gap (scaled
down when they over-explain); marker causes (preempt, prefill_steal,
kv_exhausted, compaction — any 0-width event) split the leftover excess;
a bare residual lands in "other". Per stall, base_s + sum(causes) must
equal the measured gap — `summarize` re-checks that invariant over every
record it merges, and the smoke acceptance at the bottom proves the
whole thing end to end on a live engine with constructed faults.
"""

import json
import os
import threading
import time

import pytest

from serverless_learn_tpu.telemetry import waterfall
from serverless_learn_tpu.telemetry.registry import MetricsRegistry, Span
from serverless_learn_tpu.telemetry.waterfall import (
    BoundaryEvents, RequestWaterfall)

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "waterfall",
                       "waterfall_fixture.jsonl")
BENCH_FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                             "waterfall", "bench_history_waterfall.json")


# -- builder units -----------------------------------------------------------


def test_boundary_overlap_intervals_clip_and_markers_flag():
    ev = BoundaryEvents()
    ev.note("compile", 10.0, 11.0)        # interval
    ev.note("preempt", 10.5)              # marker (0-width)
    ev.note("compile", 20.0, 21.0)        # outside the probed window
    ov = ev.overlap(10.4, 10.8)
    # The interval's overlap is clipped to the window; the marker shows
    # up as a 0.0 presence flag (it claims residual, not overlap).
    assert ov["compile"] == pytest.approx(0.4, abs=1e-9)
    assert ov["preempt"] == 0.0
    ov2 = ev.overlap(30.0, 31.0)
    assert ov2 == {}


def test_note_decode_stall_invariant_and_baseline_isolation():
    """A gap stalled behind a compile interval: causes sum to the
    above-baseline excess (base_s + sum == gap), and the EWMA baseline
    is NOT polluted by the stalled gap (the next stall still trips)."""
    wf = RequestWaterfall(min_stall_s=0.001)
    ev = BoundaryEvents()
    t = 100.0
    wf.first_token(t)
    for _ in range(8):                    # steady 10ms baseline
        t += 0.010
        assert wf.note_decode(t, 1, ev) is not None
    base_before = wf.itl_ewma
    ev.note("compile", t + 0.002, t + 0.055)
    t += 0.060                            # 60ms gap, ~50ms excess
    itl, causes = wf.note_decode(t, 1, ev)
    assert causes is not None and "compile" in causes
    stall = wf.stalls[-1]
    assert stall["base_s"] + sum(stall["causes"].values()) == \
        pytest.approx(stall["gap_s"], abs=2e-6)
    assert wf.itl_ewma == base_before     # stalled gap excluded from EWMA
    t += 0.060                            # same stall again, still trips
    _, causes2 = wf.note_decode(t, 1, ev)
    assert causes2 is not None


def test_markers_split_residual_and_bare_residual_is_other():
    wf = RequestWaterfall(min_stall_s=0.001)
    ev = BoundaryEvents()
    t = 0.0
    wf.first_token(t)
    for _ in range(6):
        t += 0.010
        wf.note_decode(t, 1, ev)
    # Two markers inside the stalled gap: the excess splits evenly.
    ev.note("preempt", t + 0.01)
    ev.note("prefill_steal", t + 0.02)
    t += 0.050
    _, causes = wf.note_decode(t, 1, ev)
    assert set(causes) == {"preempt", "prefill_steal"}
    assert causes["preempt"] == pytest.approx(causes["prefill_steal"])
    # No event at all inside the next stalled gap -> "other".
    t += 0.050
    _, causes = wf.note_decode(t, 1, ev)
    assert set(causes) == {"other"}


def test_interval_overclaim_is_scaled_to_excess():
    """An interval longer than the gap's excess must not over-explain:
    its claim is scaled down so the breakdown still sums to excess."""
    wf = RequestWaterfall(min_stall_s=0.001)
    ev = BoundaryEvents()
    t = 0.0
    wf.first_token(t)
    for _ in range(6):
        t += 0.010
        wf.note_decode(t, 1, ev)
    ev.note("harvest_drain", t - 0.5, t + 0.5)  # covers the whole gap
    t += 0.040
    _, causes = wf.note_decode(t, 1, ev)
    stall = wf.stalls[-1]
    assert set(causes) == {"harvest_drain"}
    assert sum(causes.values()) == pytest.approx(
        stall["gap_s"] - stall["base_s"], abs=1e-9)


def test_finalize_ttft_decomposition_is_exact():
    span = Span("request")
    wf = RequestWaterfall()
    span.marks["admit"] = 0.010
    span.marks["first_token"] = 0.120
    span.marks["done"] = 0.200
    wf.note_admit(0.0, 0.004)             # durations, absolute ts irrelevant
    wf.note_compile(0.0, 0.050)
    rec = wf.finalize(span)
    d = rec["ttft_decomp_s"]
    assert d["queue"] == pytest.approx(0.010, abs=1e-6)
    assert d["compile"] == pytest.approx(0.050, abs=1e-6)
    assert d["admit"] == pytest.approx(0.004, abs=1e-6)
    # Exact by construction: prefill is the remainder.
    assert d["queue"] + d["admit"] + d["compile"] + d["prefill"] == \
        pytest.approx(rec["ttft_s"], abs=5e-6)
    assert [p["phase"] for p in rec["phases"]] == \
        ["queue", "admit", "compile", "prefill", "decode"]


# -- fixture pipeline (merge / decompose / self-check) -----------------------


def test_fixture_merges_engine_and_router_records():
    rep = waterfall.report([FIXTURE])
    reqs = waterfall.merge_requests(waterfall.read_records([FIXTURE]))
    merged = [r for r in reqs if r.get("waterfall") and r.get("router")]
    assert merged, "no trace carried both engine + router records"
    s = rep["summary"]
    inv = s["invariants"]
    assert inv["ttft_decomp_bad"] == 0 and inv["stall_sum_bad"] == 0
    assert s["dominant_stall_cause"]
    assert s["itl"]["p99_s"] >= s["itl"]["p50_s"]
    # Router rollup saw the fixture's hedge and shed entries.
    assert s["router"]["hedged"] >= 1
    assert s["router"]["sheds"] >= 1


def test_self_check_passes_on_synthetic_and_committed_fixture():
    for rep in (waterfall.self_check(),
                waterfall.self_check(fixture_path=FIXTURE)):
        bad = [c for c in rep["checks"] if not c["ok"]]
        assert rep["ok"] and not bad, bad


def test_bench_rows_carry_attribution_columns():
    rep = waterfall.report([FIXTURE])
    rows = {r["metric"]: r for r in
            waterfall.bench_rows(rep["summary"])}
    itl = rows["serve_itl_p99_ms"]
    ttft = rows["serve_ttft_p99_ms"]
    assert itl["value"] > 0 and "prefill_interference_frac" in itl
    for k in ("ttft_decomp_queue_ms", "ttft_decomp_admit_ms",
              "ttft_decomp_compile_ms", "ttft_decomp_prefill_ms"):
        assert k in ttft, k
    # The committed history built from these rows passes its own gate.
    from serverless_learn_tpu.telemetry import benchgate

    gate = benchgate.run_gate(BENCH_FIXTURE, metric="serve_")
    assert gate["ok"], gate


def test_render_shows_phases_and_stall_causes():
    out = waterfall.render(waterfall.report([FIXTURE]))
    for needle in ("TTFT", "ITL", "stall", "queue", "prefill"):
        assert needle in out, needle


# -- router hop provenance ---------------------------------------------------


def _make_router(replicas, registry=None, events=None, **cfg_kw):
    from serverless_learn_tpu.config import FleetConfig
    from serverless_learn_tpu.fleet.router import FleetRouter

    defaults = dict(health_interval_s=0.15, dead_after_probes=2,
                    discover_interval_s=0.3, hedge_min_delay_s=0.05,
                    eject_s=0.4, upstream_timeout_s=5.0,
                    queue_timeout_s=1.0)
    defaults.update(cfg_kw)
    return FleetRouter(config=FleetConfig(**defaults), host="127.0.0.1",
                       port=0, replicas=tuple(replicas),
                       registry=registry or MetricsRegistry(),
                       emit=(events.append if events is not None
                             else lambda rec: None))


def _hops(events):
    return [e for e in events if e.get("event") == "waterfall_hop"]


def test_router_stamps_hop_record(tmp_path):
    from serverless_learn_tpu.fleet.testing import stub_server
    from serverless_learn_tpu.inference.server import request

    r1 = stub_server()
    events = []
    router = _make_router([r1.addr], events=events, hedge=False).start()
    try:
        time.sleep(0.3)
        rep = request(router.addr, {"prompt": [1, 2], "max_new_tokens": 2})
        assert "tokens" in rep
        deadline = time.monotonic() + 3.0
        while not _hops(events) and time.monotonic() < deadline:
            time.sleep(0.02)
        (hop,) = _hops(events)
        assert hop["trace_id"] and len(hop["trace_id"]) == 32
        assert hop["shed"] is False and hop["hedged"] is False
        assert hop["retries"] == 0
        assert hop["primary"] == hop["replica"] == r1.addr
        assert hop["total_s"] > 0 and hop["queue_wait_s"] >= 0
    finally:
        router.stop(), r1.stop()


def test_router_hedge_winner_loser_and_wasted_seconds():
    """A hedged request's hop names winner and loser; once the losing
    attempt drains, its burned seconds land in the hop and in
    slt_router_hedge_wasted_seconds_total."""
    import hashlib

    from serverless_learn_tpu.fleet.testing import StubEngine, stub_server
    from serverless_learn_tpu.inference.server import request

    slow = StubEngine(latency_s=0.6)
    r1, r2 = stub_server(engine=slow), stub_server()
    reg = MetricsRegistry()
    events = []
    router = _make_router([r1.addr, r2.addr], registry=reg,
                          events=events).start()
    try:
        time.sleep(0.3)
        session = next(       # pin the primary pick to the SLOW replica
            s for s in (f"s{i}" for i in range(64))
            if max((r1.addr, r2.addr), key=lambda a: hashlib.md5(
                f"{s}|{a}".encode()).hexdigest()) == r1.addr)
        rep = request(router.addr, {"prompt": [4], "max_new_tokens": 2,
                                    "session": session}, timeout=10)
        assert "tokens" in rep
        # The hop is emitted only after the losing attempt drains.
        deadline = time.monotonic() + 5.0
        while not _hops(events) and time.monotonic() < deadline:
            time.sleep(0.05)
        (hop,) = _hops(events)
        assert hop["hedged"] is True
        assert hop["primary"] == r1.addr
        assert hop["hedge_winner"] == r2.addr       # the hedge won
        assert hop["hedge_loser"] == r1.addr
        assert hop["hedge_wasted_s"] >= 0.3         # the slow reply burned
        assert hop["hedge_cancel_s"] >= 0.0
        fam = reg.snapshot()["slt_router_hedge_wasted_seconds_total"]
        assert sum(s["value"] for s in fam["series"]) >= 0.3
    finally:
        router.stop(), r1.stop(), r2.stop()


def test_top_renders_itl_stalls_pane():
    """The ITL/STALLS pane appears when an endpoint serves the decode
    trace metrics — stringly-typed names pinned here (SLT002 checks the
    catalog; this checks the render path end to end)."""
    from serverless_learn_tpu.telemetry import top as top_mod
    from serverless_learn_tpu.telemetry.exporter import MetricsExporter

    reg = MetricsRegistry()
    h = reg.histogram("slt_decode_itl_seconds", "itl")
    for v in (0.004, 0.005, 0.006, 0.030):
        h.observe(v)
    reg.counter("slt_decode_stall_seconds_total", "s",
                cause="compile").inc(0.9)
    reg.gauge("slt_prefill_interference_frac", "f").set(0.07)
    exp = MetricsExporter(registry=reg).start()
    try:
        st = top_mod.EndpointState(exp.addr)
        st.poll()
        out = top_mod.render([st])
        # /stalls serves the same rollup for non-screen consumers.
        stalls = json.loads(top_mod.fetch_text(exp.addr, path="/stalls"))
    finally:
        exp.stop()
    assert "ITL/STALLS" in out
    assert "compile=0.90s" in out
    assert stalls["enabled"] and stalls["itl"]["count"] == 4
    assert stalls["stall_s"] == {"compile": 0.9}
    assert stalls["prefill_interference_frac"] == pytest.approx(0.07)


# -- acceptance: live engine with constructed faults -------------------------


@pytest.mark.slow
def test_waterfall_smoke_names_injected_causes(tmp_path):
    """The round-21 acceptance, measured on a live continuous engine:
    pool overflow forces preemption, outgrown warm shapes force a
    mid-decode compile — both BY CONSTRUCTION — and the waterfalls must
    name each cause on the correct requests, with decompositions that
    sum, <2% ledger overhead, doctor naming the dominant cause from the
    JSONL alone, and gate-passing bench rows."""
    from serverless_learn_tpu.fleet.loadgen import run_waterfall_smoke

    history = tmp_path / "bench_history.json"
    rep = run_waterfall_smoke(seed=0, history_path=str(history))
    failed = [c for c in rep["checks"] if not c["ok"]]
    assert rep["ok"], failed
    rows = json.loads(history.read_text())
    assert {r["metric"] for r in rows} == \
        {"serve_itl_p99_ms", "serve_ttft_p99_ms"}
