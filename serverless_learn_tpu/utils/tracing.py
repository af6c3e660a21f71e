"""Tracing & profiling.

The reference had no tracing at all — its observability was unconditional
``std::cout`` narration on every RPC and one in-source perf TODO
(reference ``src/master.cc:257``; SURVEY.md §5 "Tracing / profiling").
This module is the rebuild's tracing story, in three parts:

* **Host spans** — ``Tracer.span(name)`` times named host-side sections
  (data fetch, shard decode, step dispatch) into per-name aggregates that
  mirror the native daemons' ``RpcStat`` (count/total/max).
* **Device traces** — ``capture(logdir)`` wraps ``jax.profiler.trace`` so a
  training window can be captured for TensorBoard/Perfetto;
  ``annotate(name)`` / ``step_annotation(step)`` wrap
  ``jax.profiler.TraceAnnotation`` / ``StepTraceAnnotation`` so host spans
  show up aligned with device ops inside the captured trace. All wrappers
  degrade to no-ops when the profiler is unavailable.
* **Daemon scrape** — ``rpc_stats(client)`` turns a Coordinator/Shard
  ``StatsReply`` into the same dict shape as ``Tracer.summary()``, so one
  report covers Python hosts and C++ daemons.

Cluster-wide, scrapeable telemetry (counters/gauges/histograms, the
``/metrics`` endpoint, ``slt top``) lives in ``telemetry/``;
``telemetry.publish_rpc_stats`` lifts this module's scrape shape into
that registry.
"""

from __future__ import annotations

import contextlib
import os
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, Optional

# Per-span narration — the descendant of the reference's unconditional
# ``std::cout`` line on every RPC (its in-source perf TODO,
# ``src/master.cc:257``). Narrating a tight loop costs real wall-clock:
# a flushed stdout write is tens of microseconds, which SKEWS the
# goodput ledger's phase timings and the step-time anomaly baseline for
# sub-millisecond spans. It is therefore OFF by default and gated twice:
# the ``SLT_TRACE_NARRATE`` env var (or ``Tracer(narrate=True)``) must
# opt in, and output goes to stderr, unbuffered by line — never stdout,
# which the CLI reserves for machine-readable JSON.
NARRATE_ENV = "SLT_TRACE_NARRATE"


def _narrate_enabled() -> bool:
    return os.environ.get(NARRATE_ENV, "").strip().lower() \
        not in ("", "0", "false", "no")


def narrate(message: str, force: bool = False):
    """Verbosity-gated debug narration; a no-op unless SLT_TRACE_NARRATE
    is set (or ``force``). Never raises (a closed stderr must not kill a
    span)."""
    if not (force or _narrate_enabled()):
        return
    try:
        sys.stderr.write(message + "\n")
    except (IOError, OSError, ValueError):
        pass

# framing.h MsgType tag -> human name, for daemon-scraped reports.
# Mirrors native/rpc_stats.h: kMaxMsgType (32) is the overflow slot where
# the daemons aggregate tags they don't know (a newer peer's message
# types) instead of dropping their count/max silently.
K_MAX_MSG_TYPE = 32
MSG_TYPE_NAMES = {
    1: "register", 3: "heartbeat", 5: "deregister", 6: "membership",
    20: "manifest", 22: "fetch", 24: "put", 25: "stats", 27: "delete",
    K_MAX_MSG_TYPE: "other",
}


@dataclass
class SpanStat:
    count: int = 0
    total_s: float = 0.0
    max_s: float = 0.0

    def add(self, dt: float):
        self.count += 1
        self.total_s += dt
        if dt > self.max_s:
            self.max_s = dt

    @property
    def mean_s(self) -> float:
        return self.total_s / self.count if self.count else 0.0


@dataclass
class Tracer:
    """Accumulates named host-side span timings; thread-safe.

    ``narrate=True`` (or the SLT_TRACE_NARRATE env var) prints one
    stderr line per finished span — debugging only; silent by default so
    per-RPC spans in tight loops cost aggregation, not I/O flushes."""

    stats: Dict[str, SpanStat] = field(default_factory=dict)
    _lock: threading.Lock = field(default_factory=threading.Lock)
    narrate: bool = False

    @contextlib.contextmanager
    def span(self, name: str, annotate_device: bool = True):
        """Time a section; optionally mirror it into the device trace."""
        ctx = annotate(name) if annotate_device else contextlib.nullcontext()
        t0 = time.perf_counter()
        with ctx:
            yield
        dt = time.perf_counter() - t0
        with self._lock:
            self.stats.setdefault(name, SpanStat()).add(dt)
        if self.narrate or _narrate_enabled():
            narrate(f"[span] {name} {dt * 1e3:.3f} ms", force=self.narrate)

    def record(self, name: str, dt: float):
        with self._lock:
            self.stats.setdefault(name, SpanStat()).add(dt)

    def summary(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            return {
                name: {"count": s.count, "total_s": s.total_s,
                       "mean_s": s.mean_s, "max_s": s.max_s}
                for name, s in sorted(self.stats.items())
            }

    def reset(self):
        with self._lock:
            self.stats.clear()


_global_tracer: Optional[Tracer] = None


def get_tracer() -> Tracer:
    global _global_tracer
    if _global_tracer is None:
        _global_tracer = Tracer()
    return _global_tracer


def _resolve_annotation():
    try:
        import jax.profiler

        return jax.profiler.TraceAnnotation
    except Exception:
        return lambda name: contextlib.nullcontext()


_annotation = None  # resolved on first use: importing this module stays jax-free


def annotate(name: str):
    """Named device-trace annotation; no-op if the profiler is unavailable.
    The serving scheduler opens five per iteration whether or not a trace
    is running, so the import is resolved once, not per call."""
    global _annotation
    if _annotation is None:
        _annotation = _resolve_annotation()
    return _annotation(name)


def step_annotation(step: int):
    """Step marker for TensorBoard's step-time view."""
    try:
        import jax.profiler

        return jax.profiler.StepTraceAnnotation("train", step_num=step)
    except Exception:
        return contextlib.nullcontext()


@contextlib.contextmanager
def capture(logdir: str):
    """Capture a jax.profiler trace (TensorBoard/Perfetto) over the block."""
    import jax.profiler

    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def rpc_stats(client_or_reply) -> Dict[str, Dict[str, float]]:
    """Scrape a daemon's per-RPC latency table into summary() shape.

    Accepts a CoordinatorClient/ShardClient (issues the stats RPC) or an
    already-fetched StatsReply (no extra round trip).
    """
    rep = (client_or_reply if hasattr(client_or_reply, "rpc")
           else client_or_reply.stats())
    out: Dict[str, Dict[str, float]] = {}
    for s in rep.rpc:
        # Tag bounds: gaps inside [0, kMaxMsgType) (e.g. the reserved 9-19
        # range) render as msg_<N>; kMaxMsgType is the daemons' overflow
        # slot ("other"); anything past it (a reply from a daemon built
        # with a LARGER table) still lands as msg_<N> instead of being
        # dropped — per-type max latency must survive unknown tags.
        name = MSG_TYPE_NAMES.get(s.msg_type, f"msg_{s.msg_type}")
        out[f"rpc/{name}"] = {
            "count": s.count,
            "total_s": s.total_us / 1e6,
            "mean_s": (s.total_us / s.count / 1e6) if s.count else 0.0,
            "max_s": s.max_us / 1e6,
        }
    return out
