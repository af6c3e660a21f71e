"""Python clients of the control/data plane.

The transport is a plain socket speaking the daemons' length-prefixed
framing (``native/framing.h``), with per-RPC deadlines, connection
poisoning and a per-peer circuit breaker; messages are the generated
``slt_pb2`` protobufs. The constructors still accept ``prefer_native``
because ``control/gossip.py`` passes it through; it selects nothing (the
ctypes binding to ``native/bin/libslt.so`` had no deadline inside
``slt_call``, so every RPC takes the socket path — ROADMAP D9 owns the
rest of the native plane).

Successor of the reference's per-binary stub classes (SURVEY.md §2.5):
``CoordinatorClient`` ≈ MasterStub (``src/worker.cc:107-133``),
``ShardClient`` ≈ WorkerStub::ReceiveFile seen from the receiving side
(``src/worker.cc:49-61``) but pull-based, ``WorkerAgent`` ≈ the worker's
heartbeat/membership behavior (``src/worker.cc:64-77``) inverted into
lease renewals.
"""

from __future__ import annotations

import os
import random
import socket
import struct
import subprocess
import threading
import time
import zlib
from typing import Callable, Dict, List, Optional

import numpy as np

from serverless_learn_tpu.telemetry import tracing as ttrace

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_NATIVE = os.path.join(_REPO_ROOT, "native")
_GEN = os.path.join(_NATIVE, "gen")
_BIN = os.path.join(_NATIVE, "bin")

_build_lock = threading.Lock()


def ensure_native_built(timeout: float = 300.0) -> bool:
    """Build native/bin/* if missing; returns True if all artifacts exist."""
    targets = [os.path.join(_BIN, n)
               for n in ("coordinator", "shard_server", "libslt.so")]
    if all(os.path.exists(t) for t in targets):
        return True
    with _build_lock:
        if all(os.path.exists(t) for t in targets):
            return True
        try:
            subprocess.run(["make", "-C", _NATIVE], check=True,
                           capture_output=True, timeout=timeout)
        except (subprocess.CalledProcessError, subprocess.TimeoutExpired):
            return False
    return all(os.path.exists(t) for t in targets)


def _pb2():
    import sys

    if _GEN not in sys.path:
        sys.path.insert(0, _GEN)
    import slt_pb2  # generated by native/Makefile

    return slt_pb2


# Must match native/framing.h MsgType.
MSG_REGISTER_REQ = 1
MSG_REGISTER_REP = 2
MSG_HEARTBEAT_REQ = 3
MSG_HEARTBEAT_REP = 4
MSG_DEREGISTER_REQ = 5
MSG_MEMBERSHIP_REQ = 6
MSG_MEMBERSHIP_REP = 7
MSG_ACK = 8
MSG_MANIFEST_REQ = 20
MSG_MANIFEST_REP = 21
MSG_FETCH_REQ = 22
MSG_CHUNK = 23
MSG_PUT_REQ = 24
MSG_STATS_REQ = 25
MSG_STATS_REP = 26
MSG_DELETE_REQ = 27

_CHUNK = 1024 * 1024


class KeyNotFound(IOError):
    """The store ANSWERED and said no-such-key/dataset — distinct from a
    transport failure (ConnectionError/timeout), which means the store is
    unreachable and the key's existence is UNKNOWN. Callers probing
    existence (``ShardServerStore.exists``) map only this to False;
    conflating the two made a partitioned store look empty and sent
    restores cold-starting over perfectly good checkpoints."""


# -- transport hardening (round 11) -----------------------------------------
#
# Three layers between an RPC and a sick network:
#   * per-RPC deadlines: every socket op times out (rpc_timeout_s); a
#     timeout mid-stream POISONS the connection — the socket is closed on
#     the spot so the next call re-dials instead of reading a stale frame
#     from the previous exchange (an un-consumed chunk stream parsed as a
#     fresh reply was the round-11 satellite bug);
#   * capped exponential backoff with FULL jitter between idempotent
#     retries (AWS-style: sleep ~ U(0, min(cap, base * 2^attempt))) — a
#     fleet of workers retrying a recovered coordinator must not stampede
#     it in sync;
#   * a per-peer circuit breaker: after `fail_threshold` consecutive
#     transport failures the peer is OPEN for `open_s` (calls fail fast
#     without touching the socket), then HALF-OPEN admits one probe.
#     State is process-global per peer address, so every Transport to a
#     dead peer shares one verdict.


def full_jitter_backoff(attempt: int, rng: random.Random,
                        base_s: float = 0.05, cap_s: float = 2.0) -> float:
    """Sleep before retry `attempt` (0-based): U(0, min(cap, base*2^n))."""
    return rng.uniform(0.0, min(cap_s, base_s * (2.0 ** attempt)))


class CircuitBreaker:
    """Per-peer failure gate. States: 0 closed, 1 half-open, 2 open."""

    CLOSED, HALF_OPEN, OPEN = 0, 1, 2

    def __init__(self, peer: str, fail_threshold: int = 5,
                 open_s: float = 2.0):
        self.peer = peer
        self.fail_threshold = fail_threshold
        self.open_s = open_s
        self._lock = threading.Lock()
        self._state = self.CLOSED
        self._failures = 0
        self._open_until = 0.0
        from serverless_learn_tpu.telemetry import get_registry

        reg = get_registry()
        self._g_state = reg.gauge(
            "slt_rpc_breaker_state",
            "per-peer circuit breaker: 0 closed, 1 half-open, 2 open",
            peer=peer)
        self._m_opens = reg.counter(
            "slt_rpc_breaker_opens_total",
            "times a peer's breaker tripped open", peer=peer)
        self._g_state.set(self.CLOSED)

    def allow(self) -> bool:
        with self._lock:
            if self._state == self.CLOSED:
                return True
            now = time.monotonic()
            if self._state == self.OPEN and now >= self._open_until:
                self._state = self.HALF_OPEN
                self._g_state.set(self._state)
                return True  # exactly one probe; others keep failing fast
            return False

    def record_success(self):
        with self._lock:
            self._failures = 0
            if self._state != self.CLOSED:
                self._state = self.CLOSED
                self._g_state.set(self._state)

    def record_failure(self):
        with self._lock:
            self._failures += 1
            if (self._state == self.HALF_OPEN
                    or self._failures >= self.fail_threshold):
                if self._state != self.OPEN:
                    self._m_opens.inc()
                self._state = self.OPEN
                self._open_until = time.monotonic() + self.open_s
                self._g_state.set(self._state)

    @property
    def state(self) -> int:
        with self._lock:
            return self._state


_breakers: Dict[str, CircuitBreaker] = {}
_breakers_lock = threading.Lock()


def breaker_for(peer: str, fail_threshold: int = 5,
                open_s: float = 2.0) -> CircuitBreaker:
    with _breakers_lock:
        b = _breakers.get(peer)
        if b is None:
            b = _breakers[peer] = CircuitBreaker(peer, fail_threshold,
                                                 open_s)
        return b


class Transport:
    """One persistent socket connection speaking the daemons' framing."""

    def __init__(self, addr: str, prefer_native: bool = True,
                 rpc_timeout_s: float = 30.0, max_attempts: int = 3,
                 backoff_base_s: float = 0.05, backoff_cap_s: float = 2.0,
                 breaker: Optional[CircuitBreaker] = None):
        self.addr = addr
        self.rpc_timeout_s = rpc_timeout_s
        self.max_attempts = max(1, max_attempts)
        self.backoff_base_s = backoff_base_s
        self.backoff_cap_s = backoff_cap_s
        self.breaker = breaker if breaker is not None else breaker_for(addr)
        self._rng = random.Random()
        self._lock = threading.Lock()
        self._sock: Optional[socket.socket] = None
        self._flow: Optional[int] = None  # sticky backpressure report
        from serverless_learn_tpu.telemetry import get_registry

        reg = get_registry()
        self._m_retries = reg.counter(
            "slt_rpc_retries_total",
            "idempotent RPC resends after a transport failure")
        self._m_timeouts = reg.counter(
            "slt_rpc_timeouts_total",
            "RPCs that hit their deadline (connection poisoned + redialed)")
        self._dial()

    def set_flow(self, flow: Optional[int]):
        """Sticky input-backpressure for this connection's future fetches:
        the consumer's prefetch-queue depth (0 = actively starving). The
        shard server paces well-fed streams while starved ones are in
        flight. None clears the report."""
        with self._lock:
            self._flow = flow

    def _dial(self):
        host, port = self.addr.rsplit(":", 1)
        self._sock = socket.create_connection(
            (host, int(port)), timeout=min(30.0, self.rpc_timeout_s))
        self._sock.settimeout(self.rpc_timeout_s)
        self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)

    def _ensure_sock(self):
        if self._sock is None:
            self._dial()

    def _poison_locked(self):
        """Drop a connection whose stream state is undefined (deadline hit
        mid-exchange): un-consumed reply bytes would be parsed as the NEXT
        call's reply. Closing forces a clean re-dial on the next use."""
        if self._sock is not None:
            try:
                self._sock.close()
            except OSError:
                pass
            self._sock = None

    def _send_frame(self, mtype: int, payload: bytes):
        self._sock.sendall(struct.pack(">IB", len(payload), mtype) + payload)

    def _recv_exact(self, n: int) -> bytes:
        buf = b""
        while len(buf) < n:
            part = self._sock.recv(n - len(buf))
            if not part:
                raise ConnectionError("peer closed")
            buf += part
        return buf

    def _recv_frame(self):
        hdr = self._recv_exact(5)
        length, mtype = struct.unpack(">IB", hdr)
        return mtype, self._recv_exact(length) if length else b""

    # ---- public API ----
    def call(self, req_type: int, payload: bytes, idempotent: bool = True):
        """Unary call; returns (resp_type, resp_bytes). Reconnect+resend on a
        broken connection happens only for ``idempotent`` requests — resending
        e.g. a Register that was already applied would create a ghost worker.

        Idempotent retries use capped exponential backoff with full jitter;
        every attempt is bounded by ``rpc_timeout_s``; a deadline hit
        poisons the connection (see :meth:`_poison_locked`); consecutive failures
        trip the per-peer circuit breaker, after which calls fail fast with
        ``ConnectionError`` until the breaker's open window lapses."""
        if not self.breaker.allow():
            raise ConnectionError(
                f"circuit open to {self.addr} (failing fast)")
        attempts = self.max_attempts if idempotent else 1
        for attempt in range(attempts):
            try:
                # One complete framed exchange per lock hold; the backoff
                # sleep below happens OUTSIDE it so a backing-off caller
                # never blocks other users of this transport.
                with self._lock:
                    self._ensure_sock()
                    self._send_frame(req_type, payload)
                    out = self._recv_frame()
                self.breaker.record_success()
                return out
            except socket.timeout:
                self._m_timeouts.inc()
                with self._lock:
                    self._poison_locked()
                self.breaker.record_failure()
                if attempt == attempts - 1:
                    raise
            except (ConnectionError, OSError):
                with self._lock:
                    self._poison_locked()
                self.breaker.record_failure()
                if attempt == attempts - 1:
                    raise
            self._m_retries.inc()
            time.sleep(full_jitter_backoff(
                attempt, self._rng, self.backoff_base_s,
                self.backoff_cap_s))

    def fetch_into(self, key: str, dst: np.ndarray, offset: int = 0,
                   length: int = 0) -> int:
        """Fetch bytes of `key` into dst (1-D uint8 view); returns count.

        A deadline hit mid-stream poisons the connection (half a chunk
        stream left in the socket would corrupt the next exchange) and
        surfaces as IOError — the caller's next call re-dials clean."""
        assert dst.dtype == np.uint8 and dst.flags["C_CONTIGUOUS"]
        if not self.breaker.allow():
            raise ConnectionError(
                f"circuit open to {self.addr} (failing fast)")
        with self._lock:
            try:
                out = self._fetch_into_locked(key, dst, offset, length)
            except socket.timeout:
                self._m_timeouts.inc()
                self._poison_locked()
                self.breaker.record_failure()
                raise IOError(
                    f"fetch {key!r}: deadline ({self.rpc_timeout_s:g}s) hit "
                    f"mid-stream; connection reset for the next call")
            except (ConnectionError, OSError):
                self._poison_locked()
                self.breaker.record_failure()
                raise
            self.breaker.record_success()
            return out

    def _fetch_into_locked(self, key: str, dst: np.ndarray, offset: int,
                           length: int) -> int:
        pb = _pb2()
        self._ensure_sock()
        req = pb.FetchRequest(key=key, offset=offset, length=length)
        if self._flow is not None:
            req.flow = int(self._flow)
            req.flow_present = True
        ttrace.attach_context(req)
        self._send_frame(MSG_FETCH_REQ, req.SerializeToString())
        written = 0
        crc = 0
        while True:
            mtype, payload = self._recv_frame()
            if mtype != MSG_CHUNK:
                raise IOError(f"unexpected frame {mtype}")
            chunk = pb.ChunkMsg()
            chunk.ParseFromString(payload)
            if chunk.error:
                raise IOError(f"fetch {key!r}: {chunk.error}")
            if chunk.data:
                # CRC over bytes as served (pre-truncation), mirroring
                # the server's running checksum of the range.
                crc = zlib.crc32(chunk.data, crc)
                rel = chunk.offset - offset
                n = min(len(chunk.data), dst.nbytes - rel)
                if n > 0:
                    dst[rel:rel + n] = np.frombuffer(
                        chunk.data[:n], np.uint8)
                    written = max(written, rel + n)
            if chunk.last:
                if chunk.crc_present and chunk.crc32 != crc:
                    raise IOError(f"fetch {key!r}: crc mismatch "
                                  f"(corrupted in transit)")
                return written

    def put(self, key: str, data: bytes) -> None:
        if not self.breaker.allow():
            raise ConnectionError(
                f"circuit open to {self.addr} (failing fast)")
        with self._lock:
            try:
                self._put_locked(key, data)
            except socket.timeout:
                self._m_timeouts.inc()
                self._poison_locked()
                self.breaker.record_failure()
                raise IOError(
                    f"put {key!r}: deadline ({self.rpc_timeout_s:g}s) hit "
                    f"mid-stream; connection reset for the next call")
            except (ConnectionError, OSError):
                self._poison_locked()
                self.breaker.record_failure()
                raise
            self.breaker.record_success()

    def _put_locked(self, key: str, data: bytes) -> None:
        pb = _pb2()
        self._ensure_sock()
        req = pb.PutRequest(key=key, total_size=len(data),
                            crc32=zlib.crc32(data), crc_present=True)
        ttrace.attach_context(req)
        self._send_frame(MSG_PUT_REQ, req.SerializeToString())
        off = 0
        while True:
            n = min(_CHUNK, len(data) - off)
            chunk = pb.ChunkMsg(data=data[off:off + n], offset=off,
                                last=off + n >= len(data))
            self._send_frame(MSG_CHUNK, chunk.SerializeToString())
            off += n
            if off >= len(data):
                break
        mtype, payload = self._recv_frame()
        ack = pb.Ack()
        ack.ParseFromString(payload)
        if not ack.ok:
            raise IOError(f"put {key!r}: {ack.error}")

    def close(self):
        # Under _lock like every other connection-state transition: a
        # close racing an in-flight call() must not drop a socket the
        # call is mid-exchange on.
        with self._lock:
            if self._sock is not None:
                self._sock.close()
                self._sock = None


class CoordinatorClient:
    """Control-plane RPCs. Every request method opens a client span
    (``telemetry/tracing.client_span``) and stamps the ambient trace
    context onto the outgoing message's optional ``trace`` field — the
    daemon's server-side span (``--events_log``) chains under it, which is
    also what `slt trace` pairs for clock-skew correction."""

    def __init__(self, addr: str, prefer_native: bool = True,
                 rpc_timeout_s: float = 30.0):
        self.pb = _pb2()
        self.t = Transport(addr, prefer_native, rpc_timeout_s=rpc_timeout_s)

    def register(self, addr: str, name: str = "", n_chips: int = 1,
                 exclusive_name: bool = False):
        with ttrace.client_span("rpc/register", peer=self.t.addr):
            req = self.pb.RegisterRequest(addr=addr, name=name,
                                          n_chips=n_chips,
                                          exclusive_name=exclusive_name)
            ttrace.attach_context(req)
            _, raw = self.t.call(MSG_REGISTER_REQ, req.SerializeToString(),
                                 idempotent=False)
        rep = self.pb.RegisterReply()
        rep.ParseFromString(raw)
        return rep

    def heartbeat(self, worker_id: int, step: int = 0, metric: float = 0.0,
                  flow: int = 0):
        with ttrace.client_span("rpc/heartbeat", peer=self.t.addr):
            req = self.pb.HeartbeatRequest(worker_id=worker_id, step=step,
                                           metric=metric, flow=flow)
            ttrace.attach_context(req)
            _, raw = self.t.call(MSG_HEARTBEAT_REQ, req.SerializeToString())
        rep = self.pb.HeartbeatReply()
        rep.ParseFromString(raw)
        return rep

    def deregister(self, worker_id: int):
        with ttrace.client_span("rpc/deregister", peer=self.t.addr):
            req = self.pb.DeregisterRequest(worker_id=worker_id)
            ttrace.attach_context(req)
            _, raw = self.t.call(MSG_DEREGISTER_REQ, req.SerializeToString())
        ack = self.pb.Ack()
        ack.ParseFromString(raw)
        return ack

    def membership(self):
        _, raw = self.t.call(MSG_MEMBERSHIP_REQ, b"")
        rep = self.pb.MembershipReply()
        rep.ParseFromString(raw)
        return rep

    def stats(self):
        """Per-RPC latency accounting scraped from the daemon (RpcStat)."""
        _, raw = self.t.call(MSG_STATS_REQ, b"")
        rep = self.pb.StatsReply()
        rep.ParseFromString(raw)
        return rep

    def close(self):
        self.t.close()


class ShardClient:
    def __init__(self, addr: str, prefer_native: bool = True):
        self.pb = _pb2()
        self.t = Transport(addr, prefer_native)

    def manifest(self, dataset: str = ""):
        with ttrace.client_span("rpc/manifest", peer=self.t.addr):
            req = self.pb.ManifestRequest(dataset=dataset)
            ttrace.attach_context(req)
            _, raw = self.t.call(MSG_MANIFEST_REQ, req.SerializeToString())
        rep = self.pb.ManifestReply()
        rep.ParseFromString(raw)
        if not rep.ok:
            # The server answered with a refusal (unknown dataset, bad
            # key): an absence verdict, not a transport failure.
            raise KeyNotFound(f"manifest {dataset!r}: {rep.error}")
        return list(rep.blobs)

    def delete(self, key: str) -> None:
        with ttrace.client_span("rpc/delete", peer=self.t.addr):
            req = self.pb.DeleteRequest(key=key)
            ttrace.attach_context(req)
            _, raw = self.t.call(MSG_DELETE_REQ, req.SerializeToString())
        ack = self.pb.Ack()
        ack.ParseFromString(raw)
        if not ack.ok:
            raise IOError(f"delete {key!r}: {ack.error}")

    def fetch(self, key: str, offset: int = 0, length: int = 0) -> bytes:
        if length == 0:
            length = max(0, self.size_of(key) - offset)
        if length == 0:
            return b""
        buf = np.empty(length, np.uint8)
        n = self.fetch_into(key, buf, offset, length)
        return buf[:n].tobytes()

    def set_flow(self, flow: Optional[int]):
        """Report this consumer's input backpressure (prefetch queue depth;
        0 = starving) on future fetches — the shard server prioritizes
        starved streams under contention."""
        self.t.set_flow(flow)

    def size_of(self, key: str) -> int:
        # One manifest of the key's parent directory (or of the synthetic key
        # itself) — never a full-root scan.
        dataset = key if key.startswith("synthetic:") else (
            key.rsplit("/", 1)[0] if "/" in key else "")
        for b in self.manifest(dataset):
            if b.key == key:
                return b.size
        raise KeyNotFound(f"unknown key {key!r}")

    def fetch_into(self, key: str, dst: np.ndarray, offset: int = 0,
                   length: int = 0) -> int:
        with ttrace.client_span("rpc/fetch", peer=self.t.addr, key=key):
            return self.t.fetch_into(key, dst, offset, length)

    def put(self, key: str, data: bytes) -> None:
        with ttrace.client_span("rpc/put", peer=self.t.addr, key=key,
                                size=len(data)):
            self.t.put(key, data)

    def stats(self):
        _, raw = self.t.call(MSG_STATS_REQ, b"")
        rep = self.pb.StatsReply()
        rep.ParseFromString(raw)
        return rep

    def close(self):
        self.t.close()


class WorkerAgent:
    """Background membership agent for one worker process.

    Registers at birth (successor of ``MasterStub::RegisterBirth``,
    ``src/worker.cc:117-129``), renews its lease on a timer, tracks the
    membership epoch + peer list from heartbeat replies, re-registers
    transparently if the lease lapsed, and invokes ``on_epoch_change`` —
    the hook the elastic controller uses to trigger checkpoint + re-mesh.
    """

    def __init__(self, coordinator_addr: str, advertise_addr: str,
                 name: str = "", n_chips: int = 1,
                 heartbeat_interval_ms: int = 1000,
                 on_epoch_change: Optional[Callable[[int, list], None]] = None,
                 prefer_native: bool = True, exclusive_name: bool = False):
        # Heartbeats carry a deadline scaled to their own cadence — a
        # control RPC hanging for the default 30s blob-transfer timeout
        # would blind the failure detector for 30 missed beats.
        self.client = CoordinatorClient(
            coordinator_addr, prefer_native,
            rpc_timeout_s=max(1.0, 3.0 * heartbeat_interval_ms / 1000.0))
        self.advertise_addr = advertise_addr
        self.name = name
        self.n_chips = n_chips
        self.exclusive_name = exclusive_name
        # Set when the agent can no longer legitimately participate (e.g.
        # its exclusive name was taken over after a lease lapse); owners
        # must stop work — continuing would write into a namespace that now
        # belongs to a live successor.
        self.fatal: Optional[str] = None
        self.interval = heartbeat_interval_ms / 1000.0
        self.on_epoch_change = on_epoch_change
        self.worker_id: Optional[int] = None
        self.epoch = -1
        self.peers: List = []
        self.step = 0
        self.metric = 0.0
        self.flow = 0  # prefetch-queue depth; 0 mid-run = input-starved
        self.lease_ttl_ms = 0  # granted by the coordinator at registration
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        # Control-plane telemetry: heartbeat RTT is the cluster's DCN
        # health signal; lease expiries are the failure detector firing.
        from serverless_learn_tpu.telemetry import get_registry

        reg = get_registry()
        self._m_hb_rtt = reg.histogram(
            "slt_heartbeat_rtt_seconds", "coordinator heartbeat round trip")
        self._m_lease_exp = reg.counter(
            "slt_lease_expiries_total",
            "heartbeats refused for a lapsed lease (re-registered)")
        self._m_hb_fail = reg.counter(
            "slt_heartbeat_failures_total", "transport errors, kept trying")

    def start(self):
        rep = self.client.register(self.advertise_addr, self.name,
                                   self.n_chips, self.exclusive_name)
        if not rep.ok:
            raise RuntimeError(
                f"registration rejected: {rep.error or 'unknown reason'}")
        self.worker_id = rep.worker_id
        self.lease_ttl_ms = rep.lease_ttl_ms
        self._note_epoch(*self._fresh_membership(rep.epoch))
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()
        return self

    def _fresh_membership(self, min_epoch: int):
        """Peer list consistent with (at least) min_epoch, for registration
        paths where the reply carries an epoch but no peers — firing the
        epoch-change callback with a stale/empty list would make an elastic
        controller form a wrong mesh."""
        m = self.client.membership()
        return max(m.epoch, min_epoch), m.peers

    def _note_epoch(self, epoch: int, peers):
        with self._lock:
            changed = epoch != self.epoch
            self.epoch = epoch
            if peers is not None:
                self.peers = list(peers)
            peers_copy = list(self.peers)
        if changed and self.on_epoch_change is not None:
            self.on_epoch_change(epoch, peers_copy)

    def _run(self):
        while not self._stop.wait(self.interval):
            try:
                t0 = time.perf_counter()
                rep = self.client.heartbeat(self.worker_id, self.step,
                                            self.metric, self.flow)
                self._m_hb_rtt.observe(time.perf_counter() - t0)
                if not rep.ok:
                    # Lease expired: elastic re-join. Dump the flight
                    # recorder first (when armed): the failure detector
                    # fired on THIS process, and the last spans/events
                    # before the lapse are exactly the post-mortem a lease
                    # expiry needs.
                    from serverless_learn_tpu.telemetry import flight

                    flight.maybe_dump("lease-expiry")
                    self._m_lease_exp.inc()
                    reg = self.client.register(self.advertise_addr, self.name,
                                               self.n_chips,
                                               self.exclusive_name)
                    if not reg.ok:
                        # Exclusive name taken over while we were lapsed: a
                        # successor owns the namespace now. Stop — silently
                        # retrying would clobber its checkpoints the moment
                        # the successor's own lease hiccups.
                        self.fatal = (reg.error or "re-registration rejected")
                        return
                    self.worker_id = reg.worker_id
                    self.lease_ttl_ms = reg.lease_ttl_ms
                    self._note_epoch(*self._fresh_membership(reg.epoch))
                else:
                    self._note_epoch(rep.epoch, rep.peers)
            except (ConnectionError, OSError):
                self._m_hb_fail.inc()
                continue  # coordinator temporarily unreachable; keep trying

    def report(self, step: int, metric: float, flow: Optional[int] = None):
        self.step, self.metric = step, metric
        if flow is not None:
            self.flow = flow

    def snapshot(self):
        """(epoch, peers) read atomically — pairing a stale epoch with a new
        peer list would let a re-mesh size itself off inconsistent state."""
        with self._lock:
            return self.epoch, list(self.peers)

    def stop(self, deregister: bool = True):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
        if deregister and self.worker_id is not None:
            try:
                self.client.deregister(self.worker_id)
            except (ConnectionError, OSError):
                pass
        self.client.close()
