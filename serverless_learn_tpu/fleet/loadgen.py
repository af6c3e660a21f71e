"""`slt loadgen`: closed- and open-loop load with realistic arrivals.

"Handles heavy traffic" is a claim until there is a latency-vs-offered-
load curve; this module produces it. Two loop disciplines (the
difference matters — closed-loop load generators hide overload by
slowing down with the server; open-loop keeps sending at the offered
rate, which is what a flash crowd does), three arrival processes:

* ``poisson`` — memoryless arrivals at a constant offered rate;
* ``diurnal`` — a sinusoidal rate profile (daily peak/trough compressed
  into the run), sampled by thinning;
* ``flash`` — Poisson base load with a ``spike_mult`` x burst window,
  the DrJAX-style skewed scenario that melts routers without shedding.

All schedules are derived from a seeded RNG, so the same (process,
seed, rate, duration) drives byte-identical request sequences. Results
separate *shed* (the router's typed ``overloaded`` rejection — policy,
counted separately) from *hard failures* (transport errors, missing
replies — never acceptable) and write ``fleet_*_p99_ms`` rows into
``bench_history.json`` through ``utils/benchlog.record`` so
``slt bench --gate`` can hold the line on them.

``run_smoke()`` is the self-contained CI proof: a 2-replica stub fleet
behind a router, open-loop load, one replica killed mid-run and
restarted — zero hard failures allowed (hedges + retries absorb the
kill).
"""

from __future__ import annotations

import json
import math
import random
import socket
import threading
import time
from typing import Callable, Dict, List, Optional

MAX_LINE = 4 * 1024 * 1024


# -- arrival processes -------------------------------------------------------


def poisson_arrivals(rate_rps: float, duration_s: float,
                     rng: random.Random) -> List[float]:
    """Arrival offsets in [0, duration): exponential inter-arrivals."""
    out, t = [], 0.0
    if rate_rps <= 0:
        return out
    while True:
        t += rng.expovariate(rate_rps)
        if t >= duration_s:
            return out
        out.append(t)


def diurnal_arrivals(base_rps: float, duration_s: float, rng: random.Random,
                     amplitude: float = 0.6,
                     period_s: Optional[float] = None) -> List[float]:
    """Sinusoidal rate profile via thinning: peak = base*(1+amplitude),
    trough = base*(1-amplitude), one full period over the run by
    default."""
    period_s = period_s or duration_s
    peak = base_rps * (1.0 + amplitude)
    cand = poisson_arrivals(peak, duration_s, rng)
    out = []
    for t in cand:
        rate = base_rps * (1.0 + amplitude
                           * math.sin(2.0 * math.pi * t / period_s))
        if rng.random() < rate / peak:
            out.append(t)
    return out


def flash_crowd_arrivals(base_rps: float, duration_s: float,
                         rng: random.Random, spike_mult: float = 5.0,
                         spike_at_frac: float = 0.4,
                         spike_dur_frac: float = 0.2) -> List[float]:
    """Poisson base with a spike_mult x burst window mid-run."""
    t0 = duration_s * spike_at_frac
    t1 = t0 + duration_s * spike_dur_frac
    base = poisson_arrivals(base_rps, duration_s, rng)
    spike = [t0 + t for t in poisson_arrivals(
        base_rps * (spike_mult - 1.0), t1 - t0, rng)]
    return sorted(base + spike)


ARRIVALS: Dict[str, Callable] = {
    "poisson": lambda rate, dur, rng: poisson_arrivals(rate, dur, rng),
    "diurnal": lambda rate, dur, rng: diurnal_arrivals(rate, dur, rng),
    "flash": lambda rate, dur, rng: flash_crowd_arrivals(rate, dur, rng),
}


# -- the client --------------------------------------------------------------


def _one_request(addr: str, req: dict, timeout_s: float) -> dict:
    host, _, port = addr.rpartition(":")
    with socket.create_connection((host, int(port)), timeout=timeout_s) as s:
        s.settimeout(timeout_s)
        with s.makefile("rwb") as f:
            f.write(json.dumps(req).encode() + b"\n")
            f.flush()
            line = f.readline(MAX_LINE + 2)
    if not line:
        raise ConnectionError("no reply")
    return json.loads(line)


def percentile(sorted_vals: List[float], q: float) -> Optional[float]:
    if not sorted_vals:
        return None
    i = min(len(sorted_vals) - 1, int(q * len(sorted_vals)))
    return sorted_vals[i]


class LoadReport:
    """Mutable tally shared by the worker threads; summarize() freezes
    it into the report dict the CLI prints and tests assert on."""

    def __init__(self):
        self.lock = threading.Lock()
        self.sent = 0
        self.ok = 0
        self.shed = 0
        self.errors = 0           # server-side error replies (typed, alive)
        self.hard_failures = 0    # transport errors / missing replies
        self.latencies_s: List[float] = []
        self.failure_examples: List[str] = []

    def note(self, outcome: str, latency_s: Optional[float] = None,
             detail: str = ""):
        with self.lock:
            self.sent += 1
            if outcome == "ok":
                self.ok += 1
                if latency_s is not None:
                    self.latencies_s.append(latency_s)
            elif outcome == "shed":
                self.shed += 1
            elif outcome == "error":
                self.errors += 1
            else:
                self.hard_failures += 1
                if len(self.failure_examples) < 5:
                    self.failure_examples.append(detail)

    def summarize(self, offered_rps: Optional[float] = None,
                  duration_s: Optional[float] = None) -> dict:
        with self.lock:
            lats = sorted(self.latencies_s)
            out = {
                "sent": self.sent, "ok": self.ok, "shed": self.shed,
                "errors": self.errors,
                "hard_failures": self.hard_failures,
                "p50_ms": _ms(percentile(lats, 0.50)),
                "p95_ms": _ms(percentile(lats, 0.95)),
                "p99_ms": _ms(percentile(lats, 0.99)),
                "mean_ms": _ms(sum(lats) / len(lats)) if lats else None,
            }
            if self.failure_examples:
                out["failure_examples"] = list(self.failure_examples)
        if offered_rps is not None:
            out["offered_rps"] = offered_rps
        if duration_s:
            out["achieved_rps"] = round(self.ok / duration_s, 2)
        return out


def _ms(x: Optional[float]) -> Optional[float]:
    return None if x is None else round(x * 1e3, 2)


def _classify(rep: dict) -> str:
    if "error" not in rep:
        return "ok"
    if rep.get("code") == "overloaded" or rep.get("shed"):
        return "shed"
    return "error"


def default_request_factory(rng: random.Random, prompt_len: int = 4,
                            max_new_tokens: int = 8,
                            vocab: int = 100) -> Callable[[int], dict]:
    """Per-request payloads: varied prompts/seeds (deterministic from the
    run seed), a session key on ~half so affinity paths get traffic, and
    ~10% priority-0 background traffic so brownout shedding has
    something legitimate to reject first."""
    def make(i: int) -> dict:
        req = {"prompt": [rng.randrange(1, vocab)
                          for _ in range(prompt_len)],
               "max_new_tokens": max_new_tokens, "seed": rng.randrange(997)}
        if rng.random() < 0.5:
            req["session"] = f"sess-{rng.randrange(16)}"
        if rng.random() < 0.1:
            req["priority"] = 0
        return req
    return make


def run_open_loop(addr: str, rate_rps: float, duration_s: float,
                  seed: int = 0, arrival: str = "poisson",
                  make_request: Optional[Callable[[int], dict]] = None,
                  timeout_s: float = 30.0,
                  report: Optional[LoadReport] = None) -> dict:
    """Open loop: requests fire AT the scheduled offsets regardless of
    how slow replies are — each on its own thread, so a melting server
    faces the true offered load."""
    rng = random.Random(f"loadgen-{seed}")
    make_request = make_request or default_request_factory(rng)
    offsets = ARRIVALS[arrival](rate_rps, duration_s, rng)
    reqs = [make_request(i) for i in range(len(offsets))]
    rep = report or LoadReport()
    threads = []
    t0 = time.monotonic()

    def fire(req: dict):
        ts = time.monotonic()
        try:
            out = _one_request(addr, req, timeout_s)
        except (OSError, ValueError) as e:
            rep.note("fail", detail=f"{type(e).__name__}: {e}")
            return
        rep.note(_classify(out), time.monotonic() - ts)

    for off, req in zip(offsets, reqs):
        delay = t0 + off - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        t = threading.Thread(target=fire, args=(req,), daemon=True)
        t.start()
        threads.append(t)
    for t in threads:
        t.join(timeout=timeout_s + 5.0)
    return rep.summarize(offered_rps=rate_rps, duration_s=duration_s)


def run_closed_loop(addr: str, concurrency: int, n_requests: int,
                    seed: int = 0,
                    make_request: Optional[Callable[[int], dict]] = None,
                    timeout_s: float = 30.0) -> dict:
    """Closed loop: ``concurrency`` workers, each sending its next
    request only after the previous reply — the steady-state throughput
    probe."""
    rng = random.Random(f"loadgen-{seed}")
    make_request = make_request or default_request_factory(rng)
    reqs = [make_request(i) for i in range(n_requests)]
    rep = LoadReport()
    idx_lock = threading.Lock()
    idx = [0]
    t0 = time.monotonic()

    def worker():
        while True:
            with idx_lock:
                i = idx[0]
                if i >= len(reqs):
                    return
                idx[0] += 1
            ts = time.monotonic()
            try:
                out = _one_request(addr, reqs[i], timeout_s)
            except (OSError, ValueError) as e:
                rep.note("fail", detail=f"{type(e).__name__}: {e}")
                continue
            rep.note(_classify(out), time.monotonic() - ts)

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(max(1, concurrency))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    out = rep.summarize(duration_s=time.monotonic() - t0)
    out["concurrency"] = concurrency
    return out


# -- the curve + bench history ----------------------------------------------


def run_curve(addr: str, rates: List[float], duration_s: float,
              seed: int = 0, arrival: str = "poisson",
              make_request: Optional[Callable[[int], dict]] = None,
              timeout_s: float = 30.0) -> List[dict]:
    """One open-loop run per offered rate — the latency-vs-load curve."""
    points = []
    for i, rate in enumerate(rates):
        points.append(run_open_loop(
            addr, rate, duration_s, seed=seed + i, arrival=arrival,
            make_request=make_request, timeout_s=timeout_s))
    return points


def bench_rows(points: List[dict], label: str = "fleet",
               device_kind: str = "fleet") -> List[dict]:
    """bench_history-shaped rows, one per curve point. The offered rate
    is part of the METRIC NAME — the gate's comparability key is
    (metric, device_kind, batch_per_chip), and a 5 rps p99 must never
    gate against a 50 rps p99."""
    rows = []
    for p in points:
        if p.get("p99_ms") is None:
            continue
        rate = p.get("offered_rps")
        tag = f"{rate:g}rps" if rate is not None else "closed"
        rows.append({
            "metric": f"{label}_loadgen_{tag}_p99_ms",
            "value": p["p99_ms"], "unit": "ms",
            "device_kind": device_kind,
            "offered_rps": rate, "achieved_rps": p.get("achieved_rps"),
            "p50_ms": p.get("p50_ms"), "p95_ms": p.get("p95_ms"),
            "shed": p.get("shed"), "hard_failures": p.get("hard_failures"),
        })
    return rows


def stamp_bundle(rows: List[dict], history_path: str,
                 role: str = "loadgen",
                 events_path: Optional[str] = None) -> Optional[str]:
    """Round 24: stamp a RunBundle next to the history file and point
    every row at it (``row["bundle"]`` is history-relative), so two
    gated loadgen rows are joinable by `slt regress`. ``events_path``
    rides along only when the caller's event log outlives the smoke
    (own-tmp logs are deleted on return — a pointer to them would be
    noise; bundle loaders tolerate missing artifacts anyway).
    Best-effort: failure leaves the rows un-pointered, never fails the
    smoke."""
    import os

    try:
        from serverless_learn_tpu.telemetry import regress as _regress

        run_id = (time.strftime(f"{role}-%Y%m%dT%H%M%S")
                  + f"-{os.getpid()}")
        hist_dir = os.path.dirname(os.path.abspath(history_path))
        ptr = os.path.join("bundles", run_id)
        sha = _regress.git_sha()
        for row in rows:
            row["bundle"] = ptr
            if sha:
                row.setdefault("git_sha", sha)
        _regress.write_bundle(
            os.path.join(hist_dir, "bundles", run_id),
            run_id=run_id, role=role, bench_rows=rows,
            events=[p for p in [events_path] if p],
            git_sha_value=sha)
        return ptr
    except Exception:
        for row in rows:
            row.pop("bundle", None)
        return None


def record_rows(rows: List[dict], history_path: str,
                events_path: Optional[str] = None) -> List[dict]:
    from serverless_learn_tpu.utils.benchlog import record

    stamp_bundle(rows, history_path, events_path=events_path)
    for row in rows:
        record(row, history_path, better="min",
               key_fields=("metric", "device_kind"))
    return rows


def run_waterfall_smoke(seed: int = 0, events_path: Optional[str] = None,
                        history_path: Optional[str] = None) -> dict:
    """The waterfall acceptance proof (round 21), measured not asserted:
    a real paged continuous engine under a seeded 3-request workload with
    two faults INJECTED by construction — a forced new-bucket XLA compile
    (one request's prompt bucket is deliberately left unwarmed) and a
    KV-exhaustion preemption (the block pool is sized so the late arrival
    cannot prefill until a decoding request is evicted). The engine's
    JSONL event log alone must then tell the whole story:

    * the per-token decode traces attribute ITL stalls to BOTH injected
      causes, on the CORRECT requests (compile/preempt charged to the
      requests that were decoding, never to the late arrival that caused
      them);
    * every TTFT decomposition sums to its measured TTFT within 5% and
      every stall's cause breakdown sums to its gap;
    * ``slt doctor`` names the dominant stall cause from the JSONL alone;
    * the ledger's self-accounted overhead stays under 2% of decode
      wall-clock.

    Rows (``serve_itl_p99_ms`` with ``prefill_interference_frac``,
    ``serve_ttft_p99_ms`` with the decomposition columns) land in bench
    history via ``history_path``, gated by ``slt bench --gate --metric
    serve_``."""
    import os
    import tempfile

    import jax
    import jax.numpy as jnp

    from serverless_learn_tpu.config import KVCacheConfig, WaterfallConfig
    from serverless_learn_tpu.inference.continuous import (
        ContinuousBatchingEngine)
    from serverless_learn_tpu.models.registry import get_model
    from serverless_learn_tpu.telemetry import doctor as doctor_mod
    from serverless_learn_tpu.telemetry import waterfall as wf_mod
    from serverless_learn_tpu.telemetry.registry import (JsonlEventLog,
                                                         MetricsRegistry)
    from serverless_learn_tpu.telemetry.tracing import new_context

    bundle = get_model("llama_tiny", dtype=jnp.float32,
                       param_dtype=jnp.float32, max_seq_len=256)
    module = bundle.module
    params = module.init(jax.random.PRNGKey(seed),
                         jnp.zeros((1, 8), jnp.int32))["params"]

    own_tmp = events_path is None
    if own_tmp:
        fd, events_path = tempfile.mkstemp(suffix=".jsonl",
                                           prefix="slt-waterfall-")
        os.close(fd)
    log = JsonlEventLog(events_path)
    registry = MetricsRegistry()
    # Both faults are injected BY CONSTRUCTION, not by timing:
    # * Pool sizing forces preemption: each decoder grows to 212 tokens
    #   = 14 blocks, so two of them need 28 against the 18-block pool —
    #   decode-time growth MUST evict the youngest residency mid-stream
    #   (kv_exhausted -> preempt -> re-admission, all on the ledger).
    # * Warm-shape scope forces a mid-decode compile: only the
    #   (32, 48)-workload buckets are compiled up front, so the decoders
    #   hit an unwarmed (nb, W) decode bucket the moment their page
    #   count outgrows the warmed width — while their token gaps are
    #   being traced.
    kv = KVCacheConfig(block_size=16, num_blocks=18,
                       prefix_cache=False, prefill_chunk=32)
    eng = ContinuousBatchingEngine(module, params, max_slots=4,
                                   chunk_size=8, registry=registry,
                                   event_log=log, kv=kv,
                                   waterfall=WaterfallConfig())
    rng = random.Random(f"waterfall-{seed}")
    decoder_prompt = [rng.randrange(1, 100) for _ in range(32)]
    intruder_prompt = [rng.randrange(1, 100) for _ in range(72)]
    eng.warm_shapes([(32, 48)], batch_sizes=(1, 2))
    traces = {name: new_context() for name in ("dec0", "dec1", "intr")}
    results: Dict[str, dict] = {}

    def fire(name, prompt, max_new, delay_s):
        if delay_s > 0:
            time.sleep(delay_s)
        results[name] = eng.submit(prompt, max_new=max_new,
                                   temperature=0.0, top_k=1, eos_id=None,
                                   seed=seed, timeout_s=300.0,
                                   trace=traces[name])

    threads = [
        threading.Thread(target=fire, args=("dec0", decoder_prompt,
                                            180, 0.0)),
        threading.Thread(target=fire, args=("dec1", decoder_prompt,
                                            180, 0.0)),
        # A short interactive request arriving mid-stream: its 72-token
        # prompt prefills through chunked-prefill while the decoders
        # decode (prefill_steal markers on their gaps) and its own
        # unwarmed buckets charge a compile phase to ITS TTFT.
        threading.Thread(target=fire, args=("intr", intruder_prompt,
                                            8, 0.05)),
    ]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300.0)
    finally:
        eng.stop()
        log.close()

    rep = wf_mod.report([events_path], top=10)
    summary = rep["summary"]
    by_trace = {traces[n].trace_id: n for n in traces}
    stalls_by_req: Dict[str, Dict[str, float]] = {}
    victims: List[str] = []
    for r in rep["slowest"]:
        name = by_trace.get(r.get("trace_id"))
        if name and r.get("waterfall"):
            stalls_by_req[name] = r["waterfall"].get("stall_s") or {}
            if "preempt" in (r.get("marks_s") or {}):
                victims.append(name)
    checks = []

    def check(name, ok, detail):
        checks.append({"check": name, "ok": bool(ok), "detail": detail})

    check("requests_complete",
          all("error" not in (results.get(n) or {"error": "missing"})
              for n in traces) and len(stalls_by_req) == 3,
          {n: sorted(stalls_by_req.get(n, {})) for n in traces})
    # The intruder's compile must be charged to the requests that were
    # DECODING through it (their inter-token gaps), while for the
    # intruder itself compile is a TTFT phase, not an ITL stall.
    decoder_stalls = set(stalls_by_req.get("dec0", {})) \
        | set(stalls_by_req.get("dec1", {}))
    check("compile_attributed_to_decoders",
          "compile" in decoder_stalls,
          f"decoder stall causes: {sorted(decoder_stalls)}, intruder: "
          f"{sorted(stalls_by_req.get('intr', {}))}")
    # Every victim that was mid-DECODE when evicted must carry the
    # preempt cause on a gap; a victim evicted before its first decode
    # token shows the cost in its (re-prefilled) TTFT instead, so it is
    # excluded — but at least one victim must name the cause.
    traced_victims = [v for v in victims if stalls_by_req.get(v)]
    check("preempt_attributed_to_victim",
          eng.preemptions > 0 and len(traced_victims) > 0
          and all("preempt" in stalls_by_req[v] for v in traced_victims),
          f"preemptions={eng.preemptions}, victim(s)={victims}, "
          f"victim causes: "
          f"{[sorted(stalls_by_req.get(v, {})) for v in victims]}")
    inv = summary.get("invariants") or {}
    check("ttft_decomposition",
          not inv.get("ttft_decomp_bad"),
          f"{inv.get('ttft_decomp_bad', 0)} request(s) whose "
          f"queue+admit+compile+prefill missed TTFT by >5%")
    check("stall_sums", not inv.get("stall_sum_bad"),
          f"{inv.get('stall_sum_bad', 0)} stall(s) whose cause "
          f"breakdown missed the gap by >2%")
    overhead = summary.get("ledger_overhead_frac")
    check("ledger_overhead",
          overhead is not None and overhead < 0.02,
          f"ledger overhead {overhead} of decode wall-clock "
          f"(bound 0.02)")
    verdict = doctor_mod.diagnose(paths=[events_path])[
        "summary"]["verdict"]
    dom = summary.get("dominant_stall_cause")
    check("doctor_names_dominant_cause",
          "decode stalls on" in verdict and dom is not None
          and f"dominant cause {dom}" in verdict,
          verdict[:200])
    rows = wf_mod.bench_rows(summary, device_kind="serve-cpu")
    check("bench_rows",
          {r["metric"] for r in rows}
          >= {"serve_itl_p99_ms", "serve_ttft_p99_ms"}
          and any("prefill_interference_frac" in r for r in rows),
          [r["metric"] for r in rows])
    if history_path:
        from serverless_learn_tpu.utils.benchlog import record

        stamp_bundle(rows, history_path, role="loadgen-serve",
                     events_path=None if own_tmp else events_path)
        for row in rows:
            record(row, history_path, better="min", rel_threshold=0.25,
                   key_fields=("metric", "device_kind"))
    out = {"ok": all(c["ok"] for c in checks), "checks": checks,
           "summary": summary, "bench_rows": rows,
           "events_path": None if own_tmp else events_path}
    if own_tmp:
        os.unlink(events_path)
    return out


def run_fleetscope_smoke(seed: int = 0, n_requests: int = 48,
                         concurrency: int = 6, prefix_len: int = 128,
                         events_path: Optional[str] = None,
                         history_path: Optional[str] = None) -> dict:
    """The fleetscope acceptance proof (round 22), measured not
    asserted: a 3-replica stub fleet whose engines own REAL paged prefix
    caches (:class:`KVStubEngine`), a prefix-heavy seeded workload, and
    the redundancy injected BY CONSTRUCTION — one replica is pre-warmed
    with the shared system prefix directly (bypassing the router), so
    when least-loaded routing then spreads the measured phase across the
    fleet, every pick that lands elsewhere re-prefills tokens that are
    provably resident one hop away. The router's JSONL event log alone
    must then tell the whole story:

    * live accounting: ``slt_fleet_redundant_prefill_tokens_total`` > 0
      and the route_decision stream carries candidate provenance;
    * ``fleet_digest`` snapshots appear as ping digests change;
    * counterfactual replay: prefix-aware picks report STRICTLY fewer
      redundant tokens than the recorded least-loaded stream;
    * determinism: two reports over the same log are byte-identical.

    The client p99 row lands in bench history carrying
    ``fleet_redundant_prefill_frac`` + ``fleet_prefix_dup_factor`` as
    attribution columns, gated by ``slt bench --gate``."""
    import os
    import tempfile

    from serverless_learn_tpu.config import FleetConfig
    from serverless_learn_tpu.fleet.router import FleetRouter
    from serverless_learn_tpu.fleet.testing import KVStubEngine, stub_server
    from serverless_learn_tpu.telemetry import fleetscope as fs_mod
    from serverless_learn_tpu.telemetry.registry import (JsonlEventLog,
                                                         MetricsRegistry)

    own_tmp = events_path is None
    if own_tmp:
        fd, events_path = tempfile.mkstemp(suffix=".jsonl",
                                           prefix="slt-fleetscope-")
        os.close(fd)
    log = JsonlEventLog(events_path)
    registry = MetricsRegistry()
    servers = [stub_server(engine=KVStubEngine(
        num_blocks=256, block_size=16, latency_s=0.01))
        for _ in range(3)]
    probe_s = 0.05
    cfg = FleetConfig(max_inflight=256, health_interval_s=probe_s,
                      dead_after_probes=5, hedge_min_delay_s=5.0)
    router = FleetRouter(config=cfg, host="127.0.0.1", port=0,
                         replicas=tuple(s.addr for s in servers),
                         registry=registry, emit=log.emit).start()
    rng = random.Random(f"fleetscope-{seed}")
    prefix = [rng.randrange(1, 100) for _ in range(prefix_len)]

    def make(i: int) -> dict:
        req = {"prompt": list(prefix)
               + [rng.randrange(1, 100) for _ in range(16)],
               "max_new_tokens": 4, "seed": rng.randrange(997)}
        if i % 3 == 0:
            req["session"] = f"sess-{i % 4}"
        return req

    try:
        # Injected redundancy: ONE replica (and only one) holds the
        # shared prefix before any routed traffic — sent direct, so the
        # router's decision stream stays purely the measured phase.
        _one_request(servers[0].addr,
                     {"prompt": list(prefix), "max_new_tokens": 1},
                     timeout_s=10.0)
        time.sleep(probe_s * 4)  # let pings carry the digest in
        out = run_closed_loop(router.addr, concurrency, n_requests,
                              seed=seed, make_request=make,
                              timeout_s=20.0)
        time.sleep(probe_s * 4)  # final digests -> dup-factor gauge
    finally:
        router.stop()
        for s in servers:
            try:
                s.stop()
            except Exception:
                pass
        log.close()

    snap = registry.snapshot()

    def _val(name):
        fam = snap.get(name) or {}
        return sum(s.get("value", 0) for s in fam.get("series", []))

    rep = fs_mod.report([events_path])
    rep2 = fs_mod.report([events_path])
    summary = rep["summary"]
    base = rep["replay"]["recorded"]
    pa = rep["replay"]["prefix_aware"]
    checks = []

    def check(name, ok, detail):
        checks.append({"check": name, "ok": bool(ok), "detail": detail})

    check("no_hard_failures", out["hard_failures"] == 0
          and out["ok"] == out["sent"] and out["sent"] == n_requests,
          {k: out[k] for k in ("sent", "ok", "shed", "hard_failures")})
    check("decision_stream",
          summary["primary_decisions"] == n_requests,
          f"{summary['primary_decisions']} primary decisions for "
          f"{n_requests} requests")
    check("live_redundancy_counter",
          _val("slt_fleet_redundant_prefill_tokens_total") > 0,
          f"slt_fleet_redundant_prefill_tokens_total="
          f"{_val('slt_fleet_redundant_prefill_tokens_total')}")
    check("recorded_redundancy_nonzero",
          summary["redundant_prefill_frac"] > 0.0,
          f"redundant frac {summary['redundant_prefill_frac']} "
          f"({summary['redundant_prefill_tokens']} of "
          f"{summary['routed_prompt_tokens']} tokens)")
    check("digest_snapshots",
          bool(summary.get("digests")),
          f"fleet_digest replicas: {sorted(summary.get('digests') or ())}")
    check("picks_spread", len(base["picks"]) >= 2,
          f"recorded picks across {len(base['picks'])} replicas")
    check("prefix_aware_strictly_lower",
          pa["redundant_prefill_tokens"]
          < base["redundant_prefill_tokens"],
          f"prefix_aware {pa['redundant_prefill_tokens']} < recorded "
          f"{base['redundant_prefill_tokens']} redundant tokens")
    check("byte_identical_reports",
          json.dumps(rep, sort_keys=True)
          == json.dumps(rep2, sort_keys=True),
          "same-log reports byte-identical")
    rows = []
    if out.get("p99_ms") is not None:
        rows.append({
            "metric": "fleetscope_smoke_p99_ms", "value": out["p99_ms"],
            "unit": "ms", "device_kind": "fleet-stub",
            "concurrency": concurrency,
            "fleet_redundant_prefill_frac":
                summary["redundant_prefill_frac"],
            "fleet_prefix_dup_factor": summary["prefix_dup_factor"],
            "prefix_aware_redundant_tokens":
                pa["redundant_prefill_tokens"]})
    if history_path:
        from serverless_learn_tpu.utils.benchlog import record

        stamp_bundle(rows, history_path, role="loadgen-fleetscope",
                     events_path=None if own_tmp else events_path)
        for row in rows:
            record(row, history_path, better="min", rel_threshold=0.5,
                   key_fields=("metric", "device_kind"))
    result = {"ok": all(c["ok"] for c in checks), "checks": checks,
              "client": out, "summary": summary,
              "replay": rep["replay"], "bench_rows": rows,
              "router": {
                  "redundant_prefill_tokens_total":
                      _val("slt_fleet_redundant_prefill_tokens_total"),
                  "routed_prompt_tokens_total":
                      _val("slt_fleet_routed_prompt_tokens_total"),
                  "prefix_dup_factor":
                      _val("slt_fleet_prefix_dup_factor")},
              "events_path": None if own_tmp else events_path}
    if own_tmp:
        os.unlink(events_path)
    return result


def _await_versions(router, n: int, deadline_s: float = 5.0) -> dict:
    """Poll until ``n`` replicas have reported a weight fingerprint
    (ping-ingested) or the deadline passes; returns the addr->version
    map either way."""
    deadline = time.monotonic() + deadline_s
    while True:
        with router._lock:
            vers = {r.addr: r.version
                    for r in router._replicas.values() if r.version}
        if len(vers) >= n or time.monotonic() > deadline:
            return vers
        time.sleep(0.02)


def run_canary_smoke(seed: int = 0, n_requests: int = 64,
                     concurrency: int = 8,
                     events_path: Optional[str] = None,
                     history_path: Optional[str] = None) -> dict:
    """The canary acceptance proof (round 23), measured not asserted:
    two legs over a 3-replica stub fleet serving TWO weight versions
    (2x baseline, 1x candidate — the fingerprints ride the admin ping),
    a 50% session-sticky split, golden probes pinned per version, and
    the verdict computed offline from the JSONL event log alone.

    * healthy leg: identical candidate behavior -> verdict PROMOTE,
      probe match 100%, probe traffic absent from the router's
      user-latency histogram, probe overhead share exported + bounded;
    * regression leg: the candidate replica's generation is shifted by
      one token (``reply_offset=1`` — same latency, different content)
      -> the golden probes alone flip the verdict to ROLLBACK naming
      the fingerprint evidence. No latency series could see this.
    * shed exemption: against a saturated 1-replica router in brownout,
      a priority-0 user request sheds instantly while a probe —
      identical except for the tag — is admitted and answered.

    The candidate p99 row lands in bench history carrying the
    ``canary_probe_match_frac`` / ``canary_ttft_p99_delta_frac`` /
    ``canary_verdict_ok`` attribution columns, gated by
    ``slt bench --gate``."""
    import os
    import tempfile

    from serverless_learn_tpu.config import FleetConfig
    from serverless_learn_tpu.fleet.router import FleetRouter
    from serverless_learn_tpu.fleet.testing import StubEngine, stub_server
    from serverless_learn_tpu.telemetry import canary as canary_mod
    from serverless_learn_tpu.telemetry.registry import (JsonlEventLog,
                                                         MetricsRegistry)

    v_base, v_cand = "basefp000001", "candfp000002"
    checks: List[dict] = []

    def check(name, ok, detail):
        checks.append({"check": name, "ok": bool(ok), "detail": detail})

    def leg(name: str, reply_offset: int, leg_events: str) -> dict:
        log = JsonlEventLog(leg_events)
        registry = MetricsRegistry()
        servers = [
            stub_server(engine=StubEngine(latency_s=0.02,
                                          weight_version=v_base)),
            stub_server(engine=StubEngine(latency_s=0.02,
                                          weight_version=v_base)),
            stub_server(engine=StubEngine(latency_s=0.02,
                                          weight_version=v_cand,
                                          reply_offset=reply_offset)),
        ]
        cfg = FleetConfig(max_inflight=256, health_interval_s=0.05,
                          dead_after_probes=5, hedge_min_delay_s=5.0)
        router = FleetRouter(config=cfg, host="127.0.0.1", port=0,
                             replicas=tuple(s.addr for s in servers),
                             registry=registry, emit=log.emit).start()
        try:
            vers = _await_versions(router, 3)
            router.set_canary(v_cand, 0.5)
            prober = canary_mod.CanaryProber(
                send=lambda req: _one_request(router.addr, req, 10.0),
                candidate_version=v_cand, baseline_version=v_base,
                registry=registry, emit=log.emit)
            prober.record_baseline()
            prober.run_round()

            def make(i: int) -> dict:
                return {"prompt": [1 + (i % 7), 2, 3], "max_new_tokens": 4,
                        "session": f"sess-{i}"}

            out = run_closed_loop(router.addr, concurrency, n_requests,
                                  seed=seed, make_request=make,
                                  timeout_s=20.0)
            prober.run_round()
        finally:
            router.stop()
            for s in servers:
                try:
                    s.stop()
                except Exception:
                    pass
            log.close()
        snap = registry.snapshot()

        def _val(metric):
            fam = snap.get(metric) or {}
            return sum(s.get("value", 0) for s in fam.get("series", []))

        def _hist_count(metric):
            fam = snap.get(metric) or {}
            return sum(s.get("count", 0) for s in fam.get("series", []))

        rep = canary_mod.report([leg_events])
        return {"name": name, "client": out, "replica_versions": vers,
                "report": rep, "prober": {"sent": prober.sent,
                                          "matched": prober.matched,
                                          "mismatched": prober.mismatched},
                "router": {
                    "user_latency_samples": _hist_count(
                        "slt_router_request_seconds"),
                    "probe_requests": _val(
                        "slt_canary_probe_requests_total"),
                    "probe_overhead_frac": _val(
                        "slt_canary_probe_overhead_frac"),
                    "weight_versions": _val("slt_fleet_weight_versions")}}

    own_tmp = events_path is None
    if own_tmp:
        fd, events_path = tempfile.mkstemp(suffix=".jsonl",
                                           prefix="slt-canary-")
        os.close(fd)
    reg_events = events_path + ".regression"
    try:
        healthy = leg("healthy", 0, events_path)
        regress = leg("regression", 1, reg_events)
    finally:
        if own_tmp and os.path.exists(events_path):
            os.unlink(events_path)
        if os.path.exists(reg_events):
            os.unlink(reg_events)

    h_rep, r_rep = healthy["report"], regress["report"]
    h_vd, r_vd = h_rep["verdict"], r_rep["verdict"]
    probes_routed = healthy["router"]["probe_requests"]
    check("no_hard_failures",
          healthy["client"]["hard_failures"] == 0
          and healthy["client"]["ok"] == n_requests
          and regress["client"]["hard_failures"] == 0,
          {k: healthy["client"][k] for k in ("sent", "ok", "shed")})
    check("two_versions_in_service",
          healthy["router"]["weight_versions"] == 2
          and len(set(healthy["replica_versions"].values())) == 2,
          f"versions gauge {healthy['router']['weight_versions']}, "
          f"pings {sorted(set(healthy['replica_versions'].values()))}")
    check("split_served_both_sides",
          (h_rep["summary"]["versions"].get(v_cand, {}).get("requests", 0)
           >= 8)
          and (h_rep["summary"]["versions"].get(v_base, {})
               .get("requests", 0) >= 8),
          {v: h_rep["summary"]["versions"][v].get("requests")
           for v in sorted(h_rep["summary"]["versions"])})
    check("verdict_promote_when_healthy",
          h_vd["decision"] == "promote"
          and h_vd["probe_match_frac"] == 1.0,
          f"{h_vd['decision']}: {h_vd['evidence']}")
    check("verdict_rollback_on_probe_regression",
          r_vd["decision"] == "rollback"
          and any("golden-probe" in e for e in r_vd["evidence"]),
          f"{r_vd['decision']}: {r_vd['evidence']}")
    check("probes_excluded_from_user_slis",
          healthy["router"]["user_latency_samples"] == n_requests
          and probes_routed > 0,
          f"latency histogram {healthy['router']['user_latency_samples']} "
          f"samples for {n_requests} user requests "
          f"({probes_routed:.0f} probes routed besides)")
    check("probe_overhead_exported_and_bounded",
          0.0 < healthy["router"]["probe_overhead_frac"] <= 0.30
          and 0.0 < h_rep["summary"]["probe_overhead_frac"] <= 0.30,
          f"gauge {healthy['router']['probe_overhead_frac']}, "
          f"ledger {h_rep['summary']['probe_overhead_frac']}")

    # Shed exemption, caught in the act: a 1-replica router saturated
    # into brownout sheds a priority-0 user request instantly but admits
    # the probe — the identical request, tagged.
    slow = stub_server(engine=StubEngine(latency_s=0.5))
    cfg = FleetConfig(max_inflight=2, shed_start_frac=0.5,
                      queue_timeout_s=3.0, health_interval_s=0.05,
                      hedge=False)
    router = FleetRouter(config=cfg, host="127.0.0.1", port=0,
                         replicas=(slow.addr,),
                         registry=MetricsRegistry(),
                         emit=lambda rec: None).start()
    try:
        _await_versions(router, 0, deadline_s=0.5)
        occupant = threading.Thread(
            target=lambda: _one_request(
                router.addr, {"prompt": [1, 2], "max_new_tokens": 1},
                10.0), daemon=True)
        occupant.start()
        time.sleep(0.1)  # occupant holds 1 of 2 slots; shed_at == 1
        user = _one_request(router.addr,
                            {"prompt": [1, 2], "max_new_tokens": 1,
                             "priority": 0}, 10.0)
        probe = _one_request(router.addr,
                             {"prompt": [1, 2], "max_new_tokens": 1,
                              "priority": 0, "probe": True}, 10.0)
        occupant.join(timeout=10)
        check("probe_shed_exempt",
              user.get("code") == "overloaded"
              and "error" not in probe,
              f"priority-0 user: {user.get('error')!r}; "
              f"probe: {'ok' if 'error' not in probe else probe['error']}")
    finally:
        router.stop()
        try:
            slow.stop()
        except Exception:
            pass

    rows = canary_mod.bench_rows(h_rep, device_kind="fleet-stub")
    if history_path:
        from serverless_learn_tpu.utils.benchlog import record

        stamp_bundle(rows, history_path, role="loadgen-canary",
                     events_path=None if own_tmp else events_path)
        for row in rows:
            record(row, history_path, better="min", rel_threshold=0.5,
                   key_fields=("metric", "device_kind"))
    return {"ok": all(c["ok"] for c in checks), "checks": checks,
            "healthy": {"client": healthy["client"],
                        "verdict": h_vd,
                        "router": healthy["router"]},
            "regression": {"verdict": r_vd,
                           "prober": regress["prober"]},
            "bench_rows": rows,
            "events_path": None if own_tmp else events_path}


# -- the CI smoke ------------------------------------------------------------


def run_smoke(seed: int = 0, rate_rps: float = 40.0,
              duration_s: float = 6.0,
              kill_at_frac: float = 0.3, restart_at_frac: float = 0.6,
              history_path: Optional[str] = None) -> dict:
    """Self-contained fleet proof: 2 stub replicas + router, open-loop
    load, one replica killed mid-run and restarted on the same port.
    ok iff ZERO hard failures and ZERO shed (capacity is sized above the
    offered load — every request must complete, the kill absorbed by
    hedges/retries/probing)."""
    from serverless_learn_tpu.config import FleetConfig
    from serverless_learn_tpu.fleet.router import FleetRouter
    from serverless_learn_tpu.fleet.testing import stub_server
    from serverless_learn_tpu.telemetry.registry import MetricsRegistry

    registry = MetricsRegistry()
    events: List[dict] = []
    r1 = stub_server(latency_s=0.005)
    r2 = stub_server(latency_s=0.005)
    cfg = FleetConfig(max_inflight=256, health_interval_s=0.2,
                      dead_after_probes=2, hedge_min_delay_s=0.05,
                      eject_s=0.5)
    router = FleetRouter(config=cfg, host="127.0.0.1", port=0,
                         replicas=(r1.addr, r2.addr), registry=registry,
                         emit=events.append).start()
    report = LoadReport()
    victim_addr = r1.addr
    restarted = []

    def chaos():
        time.sleep(duration_s * kill_at_frac)
        r1.stop()  # hard kill: in-flight requests on r1 get re-routed
        time.sleep(duration_s * (restart_at_frac - kill_at_frac))
        host, _, port = victim_addr.rpartition(":")
        restarted.append(stub_server(latency_s=0.005, host=host,
                                     port=int(port)))

    chaos_t = threading.Thread(target=chaos, daemon=True)
    chaos_t.start()
    try:
        rng = random.Random(f"loadgen-{seed}")
        out = run_open_loop(
            router.addr, rate_rps, duration_s, seed=seed,
            make_request=default_request_factory(rng), timeout_s=20.0,
            report=report)
    finally:
        chaos_t.join(timeout=duration_s + 5)
        router.stop()
        for srv in [r2] + restarted:
            try:
                srv.stop()
            except Exception:
                pass
    snap = registry.snapshot()

    def _val(name):
        fam = snap.get(name) or {}
        return sum(s.get("value", 0) for s in fam.get("series", []))

    rep = {
        "ok": (out["hard_failures"] == 0 and out["shed"] == 0
               and out["ok"] == out["sent"] and out["sent"] > 0),
        "client": out,
        "router": {"hedges": _val("slt_router_hedges_total"),
                   "retries": _val("slt_router_retries_total"),
                   "deaths": _val("slt_router_replica_deaths_total"),
                   "ejections": _val("slt_router_ejections_total")},
        "alerts": [e for e in events if e.get("event") == "alert"],
        "killed": victim_addr, "restarted": bool(restarted),
    }
    if history_path:
        rep["bench_rows"] = record_rows(
            bench_rows([out], label="fleet_smoke",
                       device_kind="fleet-stub"), history_path)
    return rep
