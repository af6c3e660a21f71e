"""Minimal generation server: JSON-lines over TCP.

Completes the framework's serving surface with zero dependencies beyond the
stdlib: one process owns the model on device; clients send one JSON object
per line and get one JSON object per line back.

    request:  {"prompt": [5, 9, 11], "max_new_tokens": 32,
               "temperature": 0.8, "top_k": 40, "eos_id": 2, "seed": 1}
    reply:    {"tokens": [...], "new_tokens": [...], "latency_ms": 12.3}
    errors:   {"error": "..."}

Connections are handled on per-connection threads; generation goes through
the ``ContinuousBatchingEngine`` (``inference/continuous.py``), whose
dispatcher is the sole user of the device: N clients share the decode batch
instead of time-slicing the chip, and batched greedy results are
byte-identical to solo calls. Request lines are capped at MAX_LINE bytes — a
newline-free stream gets an error reply and a dropped connection instead of
unbounded buffering. Bucketed shapes reuse the jit cache; new buckets pay
one compile. The reference has no inference path at all — its model was a
gossiped double vector (`src/protos/serverless_learn.proto:81-83`).
"""

from __future__ import annotations

import json
import socket
import threading
import time
from typing import Optional

# Longest accepted request line. A 128k-token prompt of 7-digit ids is
# ~1 MB; 4 MB leaves headroom while bounding per-connection memory.
MAX_LINE = 4 * 1024 * 1024


class GenerationServer:
    """Owns (module, params) and serves generation requests."""

    def __init__(self, module, params, host: str = "127.0.0.1",
                 port: int = 0, conn_timeout_s: float = 60.0,
                 max_batch: int = 8, engine="continuous",
                 chunk_size: int = 32,
                 registry=None, metrics_port: Optional[int] = None,
                 event_log_path: Optional[str] = None,
                 profile_dir: Optional[str] = None, kv=None,
                 waterfall=None, event_sink=None):
        from serverless_learn_tpu.telemetry import (JsonlEventLog,
                                                    get_registry)

        self.module = module
        self.params = params
        self.conn_timeout_s = conn_timeout_s
        self.registry = registry or get_registry()
        # Where the engine's request spans, scheduler records and
        # lifecycle events go: ``event_sink`` is any object with
        # ``emit(dict)`` (an embedding process's in-memory list, a
        # benchmark's); else the JSONL file at ``event_log_path``.
        if event_sink is not None and event_log_path:
            raise ValueError("give event_sink or event_log_path, not both")
        self.event_log = event_sink if event_sink is not None else (
            JsonlEventLog(event_log_path) if event_log_path else None)
        if not isinstance(engine, str):
            # A pre-built engine object (anything with submit()/stop()):
            # the fleet layer's stub replicas and embedding tests inject
            # their own compute here and reuse the REAL wire server.
            self.engine = engine
        elif engine == "continuous":
            from serverless_learn_tpu.inference.continuous import (
                ContinuousBatchingEngine)

            self.engine = ContinuousBatchingEngine(
                module, params, max_slots=max_batch, chunk_size=chunk_size,
                registry=self.registry, event_log=self.event_log, kv=kv,
                waterfall=waterfall)
        else:
            raise ValueError(f"unknown engine {engine!r}: expected "
                             "'continuous' or an engine object")
        # Scrapeable telemetry endpoint (slt top / Prometheus). None = off;
        # 0 = auto-assign (the addr rides in self.metrics_addr).
        self._exporter = None
        self.metrics_addr: Optional[str] = None
        if profile_dir:
            # Arm the SHARED profiler service (telemetry/profiler.py):
            # /debug/profile on the exporter below, `slt profile`, and
            # alert-triggered captures all go through the same owner.
            from serverless_learn_tpu.telemetry import profiler

            profiler.arm(profile_dir)
        if metrics_port is not None:
            from serverless_learn_tpu.telemetry import MetricsExporter

            # profile_dir arms /debug/profile: an on-demand jax.profiler
            # capture from a live serving node, no restart required.
            self._exporter = MetricsExporter(self.registry, host=host,
                                             port=metrics_port,
                                             profile_dir=profile_dir).start()
            self.metrics_addr = self._exporter.addr
        self._m_requests = self.registry.counter(
            "slt_server_requests_total", "requests answered over the wire")
        self._m_errors = self.registry.counter(
            "slt_server_errors_total", "error replies (validation + engine)")
        self._m_latency = self.registry.histogram(
            "slt_server_request_seconds", "handle() wall time")
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind((host, port))
        self._sock.listen(16)
        self.addr = f"{host}:{self._sock.getsockname()[1]}"
        self.draining = False
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._conns = {}  # live connection thread -> socket, for stop()
        self._conns_lock = threading.Lock()
        # Bounds threads and total line buffers. Twice the engine's
        # slots, and at least 64: a client per slot and one queued
        # behind it keep every slot busy while replies turn round.
        self.max_connections = max(64, 2 * max_batch)
        self.requests_served = 0
        # handle() now runs concurrently (the engine queue serializes the
        # device, not the handlers), so the counter needs its own lock.
        self._stats_lock = threading.Lock()

    # -- request handling --------------------------------------------------

    def handle(self, req: dict) -> dict:
        try:
            rep = self._handle(req)
        except Exception:
            # The caller turns this into an error reply; count it as one.
            self._m_requests.inc()
            self._m_errors.inc()
            raise
        self._m_requests.inc()
        if "error" in rep:
            self._m_errors.inc()
        elif "latency_ms" in rep:
            self._m_latency.observe(rep["latency_ms"] / 1e3)
        return rep

    def _admin(self, req: dict) -> dict:
        """Fleet admin surface on the same wire (never counted as model
        requests): "ping" lets the router probe liveness + drain state
        without touching the device; "drain" starts graceful retirement
        (stop accepting, finish in-flight) — the router's retirement path
        and `serve --fleet`'s SIGTERM handler share it."""
        op = req.get("op")
        if op == "ping":
            rep = {"ok": True, "draining": self.draining,
                   "requests_served": self.requests_served}
            # The engine reports KV pool pressure, the windowed prefix
            # hit rate AND the resident-prefix digest so the fleet
            # router's picking/shedding can weigh MEMORY (not just queue
            # depth) and its fleetscope accounting can intersect each
            # routed prompt against what is already resident fleet-wide
            # (fleet/router.py, telemetry/fleetscope.py).
            kv_stats = getattr(self.engine, "kv_stats", None)
            if callable(kv_stats):
                kv = kv_stats()
                if kv:
                    rep["kv"] = kv
            # Weight-version identity (round 23): rides the ping (not
            # the kv dict — an injected engine may have no kv_stats) so
            # the router can version-tag route decisions and detect a
            # version-skewed fleet.
            ver = getattr(self.engine, "weight_version", None)
            if ver:
                rep["version"] = ver
            return rep
        if op == "drain":
            threading.Thread(target=self.drain, daemon=True).start()
            return {"ok": True, "draining": True}
        return {"error": f"unknown op {op!r}"}

    def _handle(self, req: dict) -> dict:
        t0 = time.perf_counter()
        # Optional W3C-style trace context on the wire request: the engine
        # span chains under the CLIENT's span, so `slt trace` over the
        # client's and this server's span logs shows one causal chain.
        # Malformed values parse to None — tracing never fails a request.
        from serverless_learn_tpu.telemetry import parse_traceparent

        trace = parse_traceparent(req.get("traceparent"))
        prompt = req.get("prompt")
        if (not isinstance(prompt, list) or not prompt
                or not all(isinstance(t, int) for t in prompt)):
            return {"error": "prompt must be a non-empty list of token ids"}
        vocab = self.module.cfg.vocab_size
        if any(t < 0 or t >= vocab for t in prompt):
            return {"error": f"prompt token out of range [0, {vocab})"}
        max_new = int(req.get("max_new_tokens", 32))
        if max_new < 0 or len(prompt) + max_new > self.module.cfg.max_seq_len:
            return {"error": f"prompt+max_new_tokens exceeds max_seq_len "
                             f"{self.module.cfg.max_seq_len}"}
        eos = req.get("eos_id")
        rep = self.engine.submit(
            prompt, max_new, temperature=float(req.get("temperature", 0.0)),
            top_k=int(req.get("top_k", 0)),
            eos_id=None if eos is None else int(eos),
            seed=int(req.get("seed", 0)), trace=trace)
        if "error" in rep:
            return rep
        with self._stats_lock:
            self.requests_served += 1
        out = {"tokens": prompt + rep["new_tokens"],
               "new_tokens": rep["new_tokens"],
               "batch_size": rep.get("batch_size", 1),
               "latency_ms": round((time.perf_counter() - t0) * 1e3, 2)}
        if trace is not None:
            out["trace_id"] = trace.trace_id  # echo for client correlation
        return out

    # -- socket loop -------------------------------------------------------

    def _serve_conn(self, conn: socket.socket):
        # The read timeout bounds each connection thread's lifetime; an
        # idle or half-open client gets dropped, not held forever.
        conn.settimeout(self.conn_timeout_s)
        with conn, conn.makefile("rwb") as f:
            while True:
                try:
                    line = f.readline(MAX_LINE + 2)
                except socket.timeout:
                    return
                if not line:
                    return
                if len(line.rstrip(b"\r\n")) > MAX_LINE:
                    # Oversized or newline-free stream: reply once, hang up —
                    # never buffer without bound.
                    f.write(json.dumps(
                        {"error": f"request line exceeds {MAX_LINE} bytes"}
                    ).encode() + b"\n")
                    f.flush()
                    return
                line = line.strip()
                if not line:
                    continue
                try:
                    req = json.loads(line)
                    if not isinstance(req, dict):
                        raise ValueError("request must be a JSON object")
                    # No device lock: the engine's dispatcher is the
                    # sole device user; concurrent handlers just queue
                    # their requests.
                    rep = (self._admin(req) if "op" in req
                           else self.handle(req))
                except Exception as e:  # any bad request -> error reply,
                    rep = {"error": f"{type(e).__name__}: {e}"}  # server lives
                f.write(json.dumps(rep).encode() + b"\n")
                f.flush()

    def serve_forever(self):
        self._sock.settimeout(0.2)
        while not self._stop.is_set():
            try:
                conn, _ = self._sock.accept()
            except socket.timeout:
                continue
            except OSError:
                break
            # Per-connection thread: a slow or idle keepalive client blocks
            # only its own thread; concurrent generation requests meet
            # in the engine's admission queue.
            t = None
            with self._conns_lock:
                if len(self._conns) < self.max_connections:
                    t = threading.Thread(
                        target=self._serve_conn_safe, args=(conn,),
                        daemon=True)
                    self._conns[t] = conn
            if t is None:
                # At the cap the total buffer memory bound
                # (max_connections * MAX_LINE) would break; refuse rather
                # than queue without bound. The refusal write happens with
                # NO lock held — a client with a full receive buffer must
                # not stall every other accept (SLT001).
                try:
                    conn.sendall(json.dumps(
                        {"error": "server at connection capacity"}
                    ).encode() + b"\n")
                    conn.close()
                except OSError:
                    pass
                continue
            t.start()

    def _serve_conn_safe(self, conn: socket.socket):
        try:
            self._serve_conn(conn)
        except OSError:
            # Client vanished, reset the pipe, or stalled past the write
            # timeout (send-buffer full on an unread reply) — drop that
            # connection, keep the daemon serving.
            pass
        finally:
            with self._conns_lock:
                self._conns.pop(threading.current_thread(), None)

    def drain(self, grace_s: float = 10.0):
        """Graceful retirement: stop accepting NEW connections, let every
        in-flight request finish (bounded by ``grace_s``), leave the
        engine running until stop(). A fleet replica drains when it is
        retired (autoscaler scale-in, SIGTERM under ``serve --fleet``) so
        the router's re-route happens with zero dropped completions."""
        self.draining = True
        try:
            self._sock.close()  # accept() raises OSError -> loop exits
        except OSError:
            pass
        deadline = time.monotonic() + grace_s
        while time.monotonic() < deadline:
            with self._conns_lock:
                if not self._conns:
                    return
            time.sleep(0.02)

    def start(self):
        """Serve on a background thread (tests, embedding)."""
        self._thread = threading.Thread(target=self.serve_forever, daemon=True)
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        try:
            self._sock.close()
        except OSError:
            pass
        if self._thread is not None:
            self._thread.join(timeout=5)
        # Unblock idle readers, then wait for in-flight requests: tearing
        # down device state while a connection thread is inside generate()
        # can crash the runtime.
        with self._conns_lock:
            live = list(self._conns.items())
        for _, c in live:
            try:
                c.shutdown(socket.SHUT_RD)
            except OSError:
                pass
        for t, _ in live:
            t.join(timeout=30.0)
        self.engine.stop()
        if self._exporter is not None:
            self._exporter.stop()


def request(addr: str, req: dict, timeout: float = 120.0) -> dict:
    """One-shot client helper."""
    host, _, port = addr.rpartition(":")
    with socket.create_connection((host, int(port)), timeout=timeout) as s:
        with s.makefile("rwb") as f:
            f.write(json.dumps(req).encode() + b"\n")
            f.flush()
            line = f.readline()
    if not line:
        raise ConnectionError("server closed connection without replying")
    return json.loads(line)
