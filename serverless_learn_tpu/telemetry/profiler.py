"""Shared on-device profiler service (all roles).

PR 2 grew an on-demand ``/debug/profile`` endpoint, but its capture
logic lived with the serving stack and only ``serve --profile-dir``
armed it — a stalling *trainer* was exactly the process you couldn't
profile without a restart. This module is the one profiler owner per
process, shared by every role (train / serve / worker / diloco):

* :func:`arm` fixes the output directory (CLI ``--profile-dir`` on any
  long-running command); :func:`capture` runs one ``jax.profiler``
  device-trace window under a process-global lock (the profiler is
  process-global state — concurrent captures are a 409, not a crash).
* Every capture is stamped with a ``capture-meta.json``: the trigger
  reason, device-memory watermarks at start/stop, and the goodput
  ledger's phase snapshot at trigger time — so a trace opened next week
  still says *why* it was taken and what the run was doing.
* :func:`capture_session` brackets a whole block (``train
  --profile-dir`` without a metrics endpoint) while holding the same
  lock, so an on-demand request during a bracketed run gets a clean
  "busy" instead of a nested ``start_trace`` crash.
* :func:`on_alert` hooks the PR 3 health engine: a **critical** alert
  fires a rate-limited background capture — the profile of the incident
  exists before anyone is paged. ``slt profile <host:port> --seconds N``
  triggers the same capture remotely through ``/debug/profile``.
"""

from __future__ import annotations

import json
import os
import threading
import time
from contextlib import contextmanager
from typing import Callable, Optional

from serverless_learn_tpu.telemetry import flight

MAX_PROFILE_SECONDS = 60.0
DEFAULT_ALERT_CAPTURE_S = 3.0

_lock = threading.Lock()          # one capture at a time, process-global
_state_lock = threading.Lock()
_profile_dir: Optional[str] = None


class ProfilerBusy(RuntimeError):
    """A capture (on-demand or session-bracketed) is already running."""


def arm(profile_dir: Optional[str]):
    """Fix the default output directory; arming is what enables the
    /debug/profile endpoint and alert-triggered captures."""
    global _profile_dir
    with _state_lock:
        if profile_dir:
            _profile_dir = profile_dir


def profile_dir() -> Optional[str]:
    with _state_lock:
        return _profile_dir


def armed() -> bool:
    return profile_dir() is not None


def _device_kind() -> Optional[str]:
    """device_kind of the first local device, only if this process already
    holds its devices (``flight.live_jax``)."""
    jax = flight.live_jax()
    return jax.local_devices()[0].device_kind if jax is not None else None


def _write_meta(out_dir: str, meta: dict):
    try:
        with open(os.path.join(out_dir, "capture-meta.json"), "w") as f:
            json.dump(meta, f, indent=1)
    except (IOError, OSError, TypeError, ValueError):
        pass  # the trace itself is the payload; the stamp is best-effort


def capture(seconds: float, out_dir: Optional[str] = None,
            reason: str = "on-demand", base_dir: Optional[str] = None,
            sleep: Callable[[float], None] = time.sleep) -> dict:
    """One profiler window: start_trace, hold ``seconds``, stop_trace,
    stamp ``capture-meta.json``. Raises :class:`ProfilerBusy` when a
    capture/session already holds the profiler, ``ValueError`` on a bad
    duration, ``RuntimeError`` when nothing is armed."""
    if not (0 < seconds <= MAX_PROFILE_SECONDS):
        raise ValueError(f"seconds must be in (0, {MAX_PROFILE_SECONDS:g}]")
    base = base_dir or profile_dir()
    if out_dir is None:
        if base is None:
            raise RuntimeError(
                "profiling disabled; start this process with "
                "--profile-dir DIR to enable")
        out_dir = os.path.join(base, f"profile-{int(time.time())}")
    if not _lock.acquire(blocking=False):
        raise ProfilerBusy("a profile capture is already running")
    try:
        from serverless_learn_tpu.telemetry import goodput, xray

        meta = {"event": "profile_capture", "reason": reason,
                "seconds": seconds,
                "started_unix_s": round(time.time(), 6),
                "ledger_at_trigger": goodput.get_ledger().report(),
                "device_memory_start": flight.device_memory(),
                "device_kind": _device_kind(),
                "mesh_axes": xray.mesh_axes()}
        import jax.profiler

        jax.profiler.start_trace(out_dir)
        try:
            sleep(seconds)
        finally:
            jax.profiler.stop_trace()
        meta["device_memory_stop"] = flight.device_memory()
        _write_meta(out_dir, meta)
        # Round 16: every capture gets an xray summary stamped into its
        # meta — the trace explains itself ("step is 31% exposed
        # all-reduce on the dp axis") without re-running the analyzer —
        # and becomes the process's last summary, served at /goodput and
        # rendered by `slt top`'s HW pane. Best-effort: a capture whose
        # trace the analyzer can't read still returns the trace.
        try:
            summary = xray.analyze_dir(out_dir)
            meta["xray"] = xray.compact_summary(summary)
            _write_meta(out_dir, meta)
            xray.set_last_summary(summary)
        except Exception:
            pass
        return {"ok": True, "dir": out_dir, "seconds": seconds,
                "reason": reason, "xray": meta.get("xray")}
    finally:
        _lock.release()


@contextmanager
def capture_session(logdir: str):
    """Bracket a whole block with one capture (``train --profile-dir``'s
    classic mode), holding the shared lock so on-demand requests during
    the bracket answer busy instead of crashing the live trace."""
    if not _lock.acquire(blocking=False):
        raise ProfilerBusy("a profile capture is already running")
    try:
        import jax.profiler

        jax.profiler.start_trace(logdir)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
    finally:
        _lock.release()


def on_alert(engine, seconds: float = DEFAULT_ALERT_CAPTURE_S,
             cooldown_s: float = 600.0,
             capture_fn: Optional[Callable[..., dict]] = None,
             in_thread: bool = True) -> Callable:
    """Register an alert hook on a HealthEngine: each **critical** fire
    triggers one capture, rate-limited by ``cooldown_s`` (a flapping
    detector must not fill the disk with traces). Returns the hook (for
    tests); ``capture_fn``/``in_thread`` are injectable for the same
    reason. The capture runs off-thread so a tick never blocks on the
    profiler window."""
    state = {"last_t": None}
    state_lock = threading.Lock()
    fn = capture_fn or capture

    def hook(alert):
        if getattr(alert, "severity", None) != "critical":
            return
        if capture_fn is None and not armed():
            return
        now = time.time()
        with state_lock:
            if (state["last_t"] is not None
                    and now - state["last_t"] < cooldown_s):
                return
            state["last_t"] = now

        def run():
            try:
                fn(seconds, reason=f"alert:{alert.name}")
            except Exception:
                pass  # forensics must never hurt the watched process

        if in_thread:
            threading.Thread(target=run, daemon=True,
                             name="slt-alert-profile").start()
        else:
            run()

    engine.add_alert_hook(hook)
    return hook
