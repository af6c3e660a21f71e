"""Front-door fleet router: one address, N engine replicas, zero drama.

``slt route`` speaks the SAME JSON-lines protocol as ``serve`` — a
client that pointed at one replica points at the router unchanged — and
spreads requests across every replica discovered via the coordinator
membership plane (``serve --fleet`` self-registration) or a static list.
The design is robustness-first; each mechanism exists because a specific
failure killed a request somewhere:

* **Health gating** — a background prober hits each replica's ``/healthz``
  (503 while a critical health alert fires) and its wire-level
  ``{"op": "ping"}``; unhealthy or draining replicas take no new traffic
  but keep their in-flight requests.
* **Least-loaded + session-affine picking** — default is min in-flight
  (ties break on recent latency); a request carrying ``"session"`` maps
  to a stable replica via rendezvous hashing over the currently-eligible
  set, so KV/prefix locality survives membership churn with minimal
  reshuffling.
* **Hedged retries** — an idempotent request (greedy, or explicitly
  seeded: the engines are deterministic under a fixed seed) that has not
  answered within ``hedge_after_p95_mult x`` the replica-observed p95
  gets a second attempt on a DIFFERENT replica; first completion wins,
  the loser is discarded (never two replies — the client sees exactly
  one line). Transport errors fail over immediately, up to
  ``max_retries`` — through the shared per-peer circuit breakers of
  ``control/client.py`` (``breaker_for``), not a new ad-hoc retry loop.
* **Brownout shedding** — admission is a bounded queue
  (``max_inflight`` slots, ``queue_timeout_s`` max wait). Above
  ``shed_start_frac`` occupancy, priority<=0 traffic is rejected
  immediately; a full queue rejects everything — always with the TYPED
  overload error ``{"error": "overloaded", "code": "overloaded",
  "shed": true, "retry_after_ms": N}``, so clients can tell "backed off
  by policy" from "broken".
* **Outlier ejection** — ``eject_consecutive_errors`` transport failures
  eject a replica for ``eject_s`` (doubling per repeat); a dead TCP
  endpoint (``dead_after_probes`` failed probes) fires a
  ``fleet.replica_dead`` alert event that `slt doctor` ranks and names.
* **Graceful draining** — retiring a replica (membership deregistration,
  autoscaler scale-in, ``remove_replica``) stops NEW picks instantly and
  sends the wire ``{"op": "drain"}`` so the replica finishes its
  in-flight work before exiting.

Replica state machine (docs/ARCHITECTURE.md has the full table)::

    JOINING -> HEALTHY <-> UNHEALTHY -> DEAD
                  |  \\-> EJECTED (timed, doubling) -> HEALTHY
                  \\--> DRAINING -> removed
"""

from __future__ import annotations

import collections
import hashlib
import json
import queue
import socket
import threading
import time
from typing import Dict, List, Optional

from serverless_learn_tpu.config import FleetConfig

MAX_LINE = 4 * 1024 * 1024

_OVERLOAD_RETRY_MS = 250


def _overload_reply(reason: str) -> dict:
    """The typed brownout error: distinguishable from every other error
    by ``code`` so loadgen/clients count shed separately from failures."""
    return {"error": f"overloaded: {reason}", "code": "overloaded",
            "shed": True, "retry_after_ms": _OVERLOAD_RETRY_MS}


class Replica:
    """Router-side view of one engine replica."""

    JOINING, HEALTHY, UNHEALTHY, EJECTED, DRAINING, DEAD = (
        "joining", "healthy", "unhealthy", "ejected", "draining", "dead")

    def __init__(self, addr: str, metrics_addr: Optional[str] = None,
                 name: str = "", static: bool = False):
        self.addr = addr
        self.metrics_addr = metrics_addr
        self.name = name or addr
        self.static = static          # never pruned by membership polls
        self.state = self.JOINING
        self.inflight = 0
        self.consec_errors = 0
        self.eject_count = 0
        self.ejected_until = 0.0
        self.failed_probes = 0
        self.last_error: Optional[str] = None
        # Recent request latencies (seconds) for the hedge delay's p95.
        self.latencies: List[float] = []
        self.requests = 0
        self.errors = 0
        # Paged-KV pressure from the replica's ping reply (round 13):
        # free-block fraction + prefix hit rate. None until the
        # replica reports them (a stub engine never does).
        self.kv_free_frac: Optional[float] = None
        self.prefix_hit_rate: Optional[float] = None
        # Resident-prefix digest from the ping (round 22): the chain
        # hashes of the replica's PrefixTrie nodes, intersected against
        # each routed prompt for fleet-wide redundancy accounting.
        self.digest_hashes: frozenset = frozenset()
        self.digest_block_size: int = 0
        self.digest_top: List[dict] = []
        # Weight-version fingerprint (round 23): seeded from the ;v=
        # registration suffix when present, refreshed from every ping
        # reply. None until the replica reports one.
        self.version: Optional[str] = None

    def note_latency(self, s: float, keep: int = 128):
        self.latencies.append(s)
        if len(self.latencies) > keep:
            del self.latencies[:len(self.latencies) - keep]

    def p95(self) -> Optional[float]:
        if len(self.latencies) < 8:
            return None
        xs = sorted(self.latencies)
        return xs[min(len(xs) - 1, int(0.95 * len(xs)))]

    def eligible(self, now: float) -> bool:
        if self.state in (self.DRAINING, self.DEAD, self.UNHEALTHY):
            return False
        if self.state == self.EJECTED:
            return now >= self.ejected_until
        return True

    def describe(self) -> dict:
        return {"addr": self.addr, "state": self.state,
                "inflight": self.inflight, "requests": self.requests,
                "errors": self.errors,
                **({"metrics_addr": self.metrics_addr}
                   if self.metrics_addr else {}),
                **({"kv_free_frac": self.kv_free_frac}
                   if self.kv_free_frac is not None else {}),
                **({"prefix_hit_rate": self.prefix_hit_rate}
                   if self.prefix_hit_rate is not None else {}),
                **({"version": self.version}
                   if self.version else {}),
                **({"last_error": self.last_error}
                   if self.last_error else {})}


class FleetRouter:
    """The front-door process. start() binds and serves; stop() tears
    down. Thread model mirrors GenerationServer: one accept loop, one
    thread per client connection, plus a prober and (optionally) a
    membership-discovery loop; forwards run on per-attempt threads so a
    hedge can outlive the attempt it raced."""

    def __init__(self, config: Optional[FleetConfig] = None,
                 host: Optional[str] = None, port: Optional[int] = None,
                 replicas: tuple = (), coordinator_addr: Optional[str] = None,
                 registry=None, emit=None, clock=time.monotonic):
        from serverless_learn_tpu.telemetry import get_registry

        self.cfg = config or FleetConfig()
        self.coordinator_addr = coordinator_addr
        self.registry = registry or get_registry()
        self.clock = clock
        # Alert-shaped event emission (doctor/trace ingest); default rides
        # the ambient tracing sink (--events-log), tests inject a list.
        if emit is None:
            from serverless_learn_tpu.telemetry.tracing import emit_event
            emit = emit_event
        self._emit = emit

        self._replicas: Dict[str, Replica] = {}
        self._lock = threading.Lock()          # replica table + counters
        self._adm_lock = threading.Lock()      # admission queue
        self._adm_cv = threading.Condition(self._adm_lock)
        self._inflight = 0
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self._conns: Dict[threading.Thread, socket.socket] = {}
        self._conns_lock = threading.Lock()
        self.max_connections = 128

        reg = self.registry
        self._m_requests = reg.counter(
            "slt_router_requests_total", "requests accepted by the router")
        self._m_errors = reg.counter(
            "slt_router_errors_total",
            "error replies returned to clients (upstream + validation)")
        self._m_shed = reg.counter(
            "slt_router_shed_total",
            "requests rejected with the typed overload error")
        self._m_hedges = reg.counter(
            "slt_router_hedges_total", "hedge attempts launched")
        self._m_hedge_wins = reg.counter(
            "slt_router_hedge_wins_total",
            "requests whose hedge attempt answered first")
        self._m_retries = reg.counter(
            "slt_router_retries_total",
            "failover resends after an upstream transport error")
        self._m_ejections = reg.counter(
            "slt_router_ejections_total",
            "replicas ejected for consecutive errors")
        self._m_deaths = reg.counter(
            "slt_router_replica_deaths_total",
            "replicas declared dead after failed liveness probes")
        self._g_replicas = reg.gauge(
            "slt_router_replicas", "replicas known to the router")
        self._g_healthy = reg.gauge(
            "slt_router_replicas_healthy", "replicas eligible for traffic")
        self._g_inflight = reg.gauge(
            "slt_router_inflight", "requests currently held by the router")
        self._g_kv_free = reg.gauge(
            "slt_router_kv_free_frac",
            "min free KV-block fraction across eligible paged replicas "
            "(1.0 when none report)")
        self._h_queue_wait = reg.histogram(
            "slt_router_queue_wait_seconds",
            "admission wait below capacity (the autoscaler's SLO signal)")
        self._h_latency = reg.histogram(
            "slt_router_request_seconds",
            "client-observed latency through the router")
        self._h_upstream = reg.histogram(
            "slt_router_upstream_seconds", "one forward attempt's latency")
        self._m_hedge_wasted = reg.counter(
            "slt_router_hedge_wasted_seconds_total",
            "upstream seconds burned by losing hedge attempts (duplicate "
            "work the race discarded)")
        # ---- fleetscope redundancy accounting (round 22) ----
        self._m_redundant_tokens = reg.counter(
            "slt_fleet_redundant_prefill_tokens_total",
            "prompt tokens the picked replica will prefill while already "
            "resident in another eligible replica's prefix cache")
        self._m_prompt_tokens = reg.counter(
            "slt_fleet_routed_prompt_tokens_total",
            "prompt tokens routed (the redundancy fraction's denominator)")
        self._g_redundant_frac = reg.gauge(
            "slt_fleet_redundant_prefill_frac",
            "running fraction of routed prompt tokens re-prefilled while "
            "resident elsewhere in the fleet")
        self._g_dup_factor = reg.gauge(
            "slt_fleet_prefix_dup_factor",
            "mean replicas holding each fleet-resident prefix chunk "
            "(1.0 = no duplication; 0 when no digests reported)")
        self._decision_seq = 0
        self._redundant_tokens_sum = 0
        self._prompt_tokens_sum = 0
        # ---- weight-version identity + canary split (round 23) ----
        self._g_versions = reg.gauge(
            "slt_fleet_weight_versions",
            "distinct weight-version fingerprints reported by known "
            "replicas (a value > 1 with no canary active is skew)")
        self._m_version_swaps = reg.counter(
            "slt_fleet_version_swaps_total",
            "replica weight-version changes observed via ping or "
            "registration")
        self._g_canary_frac = reg.gauge(
            "slt_canary_candidate_frac",
            "configured candidate-version traffic fraction "
            "(0 = no canary split active)")
        self._m_probe_requests = reg.counter(
            "slt_canary_probe_requests_total",
            "golden-probe requests routed (shed-exempt, excluded from "
            "user-facing latency SLIs)")
        self._g_probe_overhead = reg.gauge(
            "slt_canary_probe_overhead_frac",
            "running share of routed requests that were golden probes "
            "(the bounded canary overhead)")
        self._probe_req_sum = 0
        self._total_req_sum = 0
        # Runtime canary split state (FleetConfig is frozen; these seed
        # from it and move via set_canary()).
        self._canary_version: Optional[str] = None
        self._canary_frac = 0.0

        for addr in replicas:
            self.add_replica(addr, static=True)
        if self.cfg.canary_version:
            self.set_canary(self.cfg.canary_version, self.cfg.canary_frac)

        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind((host if host is not None else self.cfg.router_host,
                         port if port is not None else self.cfg.router_port))
        self._sock.listen(64)
        self.addr = "%s:%d" % self._sock.getsockname()[:2]

    # -- fleet membership ---------------------------------------------------

    def add_replica(self, addr: str, metrics_addr: Optional[str] = None,
                    name: str = "", static: bool = False,
                    version: Optional[str] = None) -> Replica:
        with self._lock:
            r = self._replicas.get(addr)
            if r is None:
                r = self._replicas[addr] = Replica(
                    addr, metrics_addr, name, static=static)
            elif r.state in (Replica.DEAD, Replica.DRAINING):
                # Re-registration of a known address = a restarted
                # replica: forget the obituary.
                r.state = Replica.JOINING
                r.failed_probes = 0
                r.consec_errors = 0
                r.eject_count = 0
            if metrics_addr:
                r.metrics_addr = metrics_addr
            self._refresh_gauges_locked()
        if version:
            self._note_version(r, version)
        return r

    def set_canary(self, version: Optional[str], frac: float = 0.0):
        """Configure (or clear) the candidate version-split. Session-
        sticky assignment happens per request in _dispatch; the
        canary_config event gives the offline verdict engine the
        candidate identity and split fraction."""
        with self._lock:
            self._canary_version = version or None
            self._canary_frac = max(0.0, min(1.0, float(frac)))
            eff = self._canary_frac if self._canary_version else 0.0
        self._g_canary_frac.set(eff)
        try:
            self._emit({"event": "canary_config",
                        "t_unix_s": time.time(),
                        "candidate_version": version or None,
                        "frac": eff})
        except Exception:
            pass

    def _note_version(self, r: Replica, version: str):
        """Record a replica's reported weight fingerprint; emit a
        fleet_version event only on CHANGE (mirrors the fleet_digest
        emit-on-change pattern) and refresh the distinct-version gauge."""
        with self._lock:
            prev = r.version
            if version == prev:
                return
            r.version = version
            distinct = len({x.version for x in self._replicas.values()
                            if x.version})
        if prev is not None:
            self._m_version_swaps.inc()
        self._g_versions.set(distinct)
        try:
            self._emit({"event": "fleet_version", "replica": r.addr,
                        "t_unix_s": time.time(), "version": version,
                        "prev": prev})
        except Exception:
            pass

    def remove_replica(self, addr: str, drain: bool = True,
                       reason: str = "retired"):
        """Retirement: no new picks from this instant; optionally tell
        the replica to drain so its in-flight work completes."""
        with self._lock:
            r = self._replicas.get(addr)
            if r is None:
                return
            r.state = Replica.DRAINING
            self._refresh_gauges_locked()
        self._emit_alert("fleet.replica_retired", "info", "firing",
                         f"replica {addr} retiring ({reason})", addr)
        if drain:
            try:
                self._wire_request(addr, {"op": "drain"}, timeout=2.0)
            except OSError:
                pass  # already gone; nothing to drain
        with self._lock:
            self._replicas.pop(addr, None)
            self._refresh_gauges_locked()

    def replicas(self) -> List[dict]:
        with self._lock:
            return [r.describe() for r in self._replicas.values()]

    def _refresh_gauges_locked(self):
        now = self.clock()
        self._g_replicas.set(len(self._replicas))
        self._g_healthy.set(sum(r.eligible(now)
                                for r in self._replicas.values()))

    def _emit_alert(self, name: str, severity: str, state: str,
                    message: str, replica_addr: str):
        """Health-engine-shaped alert record: `slt doctor` aggregates
        these straight from the events log, so a dead replica is NAMED
        from telemetry alone (labels.replica)."""
        now = time.time()
        try:
            self._emit({"event": "alert", "alert": name,
                        "severity": severity, "detector": "fleet",
                        "state": state, "message": message,
                        "labels": {"replica": replica_addr},
                        "value": 1.0, "threshold": 0.0, "count": 1,
                        "first_fired_unix_s": round(now, 3),
                        "last_fired_unix_s": round(now, 3)})
        except Exception:
            pass

    # -- health probing + discovery -----------------------------------------

    def _probe_once(self):
        with self._lock:
            snapshot = list(self._replicas.values())
        for r in snapshot:
            if r.state == Replica.DRAINING:
                continue
            ok, draining, err = self._probe_replica(r)
            died = resurrected = False
            with self._lock:
                if r.addr not in self._replicas:
                    continue
                if ok:
                    r.failed_probes = 0
                    was = r.state
                    if draining:
                        r.state = Replica.DRAINING
                    elif r.state in (Replica.JOINING, Replica.UNHEALTHY,
                                     Replica.DEAD):
                        r.state = Replica.HEALTHY
                    resurrected = (was == Replica.DEAD
                                   and r.state == Replica.HEALTHY)
                else:
                    r.failed_probes += 1
                    r.last_error = err
                    if r.failed_probes >= self.cfg.dead_after_probes:
                        if r.state != Replica.DEAD:
                            r.state = Replica.DEAD
                            self._m_deaths.inc()
                            died = True
                    elif r.state == Replica.HEALTHY:
                        r.state = Replica.UNHEALTHY
                self._refresh_gauges_locked()
            if died:
                self._emit_alert(
                    "fleet.replica_dead", "critical", "firing",
                    f"replica {r.addr} failed {self.cfg.dead_after_probes} "
                    f"liveness probes ({err})", r.addr)
            if resurrected:
                self._emit_alert("fleet.replica_dead", "critical",
                                 "resolved",
                                 f"replica {r.addr} answering again",
                                 r.addr)
        self._g_kv_free.set(self._kv_pressure())
        self._g_dup_factor.set(round(self._prefix_dup_factor(), 4))

    def _prefix_dup_factor(self) -> float:
        """Mean number of replicas holding each prefix chunk resident
        anywhere in the fleet (from the ping digests). 1.0 means every
        cached prefix lives on exactly one replica; 2.0 means the
        average chunk burns double its KV memory fleet-wide."""
        with self._lock:
            sets = [r.digest_hashes for r in self._replicas.values()
                    if r.digest_hashes]
        if not sets:
            return 0.0
        counts: collections.Counter = collections.Counter()
        for s in sets:
            counts.update(s)
        return sum(counts.values()) / len(counts)

    def _kv_pressure(self) -> float:
        """Min free KV-block fraction across the eligible set; 1.0 when
        no replica reports KV stats (such a fleet is never
        memory-shed)."""
        now = self.clock()
        with self._lock:
            fracs = [r.kv_free_frac for r in self._replicas.values()
                     if r.eligible(now) and r.kv_free_frac is not None]
        return min(fracs) if fracs else 1.0

    def _probe_replica(self, r: Replica):
        """(ok, draining, error): wire-level ping (cheap, definitive for
        liveness + drain state), then /healthz when a metrics addr is
        known (503 while a critical alert fires = no new traffic)."""
        try:
            rep = self._wire_request(r.addr, {"op": "ping"}, timeout=2.0)
            draining = bool(rep.get("draining"))
            ver = rep.get("version")
            if isinstance(ver, str) and ver:
                self._note_version(r, ver)
            kv = rep.get("kv")
            if isinstance(kv, dict) and kv.get("blocks_total"):
                # Under _lock like every other Replica-field mutation:
                # _pick/_kv_pressure read these mid-iteration and a torn
                # probe write could shed on a half-updated fraction.
                changed = None
                with self._lock:
                    r.kv_free_frac = (kv.get("blocks_free", 0)
                                      / max(kv["blocks_total"], 1))
                    r.prefix_hit_rate = kv.get("prefix_hit_rate")
                    dg = kv.get("prefix_digest")
                    if isinstance(dg, dict):
                        new = frozenset(
                            h for h in (dg.get("hashes") or ())
                            if isinstance(h, str))
                        if new != r.digest_hashes:
                            changed = dg
                        r.digest_hashes = new
                        r.digest_block_size = int(
                            dg.get("block_size") or 0)
                        r.digest_top = list(dg.get("top") or ())
                if changed is not None:
                    # fleet_digest snapshot for slt fleetscope/doctor —
                    # only when the resident set actually moved, so a
                    # quiet fleet costs zero event volume.
                    try:
                        self._emit({
                            "event": "fleet_digest", "replica": r.addr,
                            "t_unix_s": time.time(),
                            "block_size": int(
                                changed.get("block_size") or 0),
                            "blocks": int(changed.get("blocks") or 0),
                            "hashes": sorted(
                                h for h in (changed.get("hashes") or ())
                                if isinstance(h, str)),
                            "top": list(changed.get("top") or ())})
                    except Exception:
                        pass
        except (OSError, ValueError) as e:
            return False, False, f"{type(e).__name__}: {e}"
        if r.metrics_addr:
            try:
                from serverless_learn_tpu.telemetry.exporter import fetch_text

                hz = json.loads(fetch_text(r.metrics_addr, "/healthz",
                                           timeout=2.0))
                if not hz.get("ok", True):
                    return False, draining, (
                        "healthz not ok: "
                        + ",".join(hz.get("firing_critical") or []))
            except Exception:
                # Unreachable *metrics* endpoint never condemns a replica
                # whose serving socket answers — the gate, not the judge.
                pass
        return True, draining, None

    def _discover_once(self):
        """Poll coordinator membership for replica:<service> peers; new
        peers join, vanished dynamic peers drain out (their deregistration
        or lease expiry IS the retirement signal)."""
        if not self.coordinator_addr:
            return
        from serverless_learn_tpu.control.client import CoordinatorClient
        from serverless_learn_tpu.fleet.registration import parse_replica

        client = getattr(self, "_coordinator", None)
        if client is None:
            try:
                client = CoordinatorClient(self.coordinator_addr,
                                           rpc_timeout_s=5.0)
            except (ConnectionError, OSError):
                return
            self._coordinator = client
        try:
            rep = client.membership()
        except (ConnectionError, OSError, ValueError):
            self._coordinator = None
            try:
                client.close()
            except Exception:
                pass
            return
        seen = set()
        for peer in rep.peers:
            info = parse_replica(peer.name, peer.addr)
            if info is None or info["service"] != self.cfg.service:
                continue
            seen.add(info["serve_addr"])
            self.add_replica(info["serve_addr"],
                             metrics_addr=info["metrics_addr"],
                             name=peer.name,
                             version=info.get("version"))
        with self._lock:
            gone = [a for a, r in self._replicas.items()
                    if not r.static and a not in seen
                    and r.state != Replica.DRAINING]
        for addr in gone:
            self.remove_replica(addr, drain=True, reason="deregistered")

    def _background_loop(self):
        last_discover = 0.0
        while not self._stop.is_set():
            now = self.clock()
            if now - last_discover >= self.cfg.discover_interval_s:
                try:
                    self._discover_once()
                except Exception:
                    pass
                last_discover = now
            try:
                self._probe_once()
            except Exception:
                pass
            self._stop.wait(self.cfg.health_interval_s)

    # -- picking ------------------------------------------------------------

    def _candidates(self) -> List[Replica]:
        now = self.clock()
        with self._lock:
            return [r for r in self._replicas.values() if r.eligible(now)]

    def _pick(self, candidates: List[Replica],
              session: Optional[str], exclude=(),
              want_version: Optional[str] = None,
              avoid_version: Optional[str] = None,
              strict_version: bool = False) -> Optional[Replica]:
        pool = [r for r in candidates if r.addr not in exclude]
        if want_version is not None or avoid_version is not None:
            # Canary split / pin filter. Non-strict (split traffic)
            # falls back to the full pool when the wanted version has
            # no eligible replica — availability beats split fidelity.
            # Strict (pinned probes, hedges under a split) returns None
            # instead: a probe must never measure the wrong version and
            # a hedge must never race two versions (their replies may
            # legitimately differ, breaking hedge idempotency).
            vpool = [r for r in pool
                     if (want_version is None
                         or r.version == want_version)
                     and (avoid_version is None
                          or r.version != avoid_version)]
            if vpool or strict_version:
                pool = vpool
        if not pool:
            return None
        if session:
            # Rendezvous hashing: stable per session, minimal reshuffle
            # on membership change — and still health-gated, because the
            # pool is already the eligible set.
            return max(pool, key=lambda r: hashlib.md5(
                f"{session}|{r.addr}".encode()).hexdigest())
        with self._lock:
            # Memory pressure ranks between load and latency: among
            # equally-loaded replicas, prefer the one with KV headroom
            # (bucketed to 20% steps so probe-to-probe noise doesn't
            # thrash affinity-free traffic between replicas).
            def pressure(r: Replica) -> int:
                if r.kv_free_frac is None:
                    return 0
                return int((1.0 - max(0.0, min(1.0, r.kv_free_frac)))
                           * 5.0)

            return min(pool, key=lambda r: (
                r.inflight, r.consec_errors, pressure(r),
                r.latencies[-1] if r.latencies else 0.0, r.addr))

    # -- forwarding ---------------------------------------------------------

    def _wire_request(self, addr: str, req: dict, timeout: float) -> dict:
        host, _, port = addr.rpartition(":")
        with socket.create_connection((host, int(port)),
                                      timeout=timeout) as s:
            s.settimeout(timeout)
            with s.makefile("rwb") as f:
                f.write(json.dumps(req).encode() + b"\n")
                f.flush()
                line = f.readline(MAX_LINE + 2)
        if not line:
            raise ConnectionError(f"{addr} closed without replying")
        rep = json.loads(line)
        if not isinstance(rep, dict):
            raise ValueError(f"{addr} replied non-object")
        return rep

    def _forward_attempt(self, r: Replica, req: dict, out: "queue.Queue"):
        from serverless_learn_tpu.control.client import breaker_for

        breaker = breaker_for(r.addr)
        t0 = self.clock()
        try:
            if not breaker.allow():
                raise ConnectionError(f"circuit open to {r.addr}")
            rep = self._wire_request(r.addr, req,
                                     timeout=self.cfg.upstream_timeout_s)
        except (OSError, ValueError) as e:
            breaker.record_failure()
            with self._lock:
                r.inflight -= 1
                r.errors += 1
                r.consec_errors += 1
                r.last_error = f"{type(e).__name__}: {e}"
                ejected = (r.state == Replica.HEALTHY
                           and r.consec_errors
                           >= self.cfg.eject_consecutive_errors)
                if ejected:
                    r.state = Replica.EJECTED
                    r.eject_count += 1
                    r.ejected_until = (self.clock() + self.cfg.eject_s
                                       * (2 ** (r.eject_count - 1)))
                    self._m_ejections.inc()
                    self._refresh_gauges_locked()
            if ejected:
                self._emit_alert(
                    "fleet.replica_ejected", "warning", "firing",
                    f"replica {r.addr} ejected after "
                    f"{r.consec_errors} consecutive errors "
                    f"({r.last_error})", r.addr)
            out.put((r, None, f"{type(e).__name__}: {e}",
                     self.clock() - t0))
            return
        dt = self.clock() - t0
        breaker.record_success()
        self._h_upstream.observe(dt)
        with self._lock:
            r.inflight -= 1
            r.requests += 1
            r.note_latency(dt)
            if "error" in rep and rep.get("code") != "overloaded":
                r.errors += 1
            else:
                r.consec_errors = 0
                if r.state == Replica.EJECTED:
                    r.state = Replica.HEALTHY
                    self._refresh_gauges_locked()
        out.put((r, rep, None, dt))

    def _launch(self, r: Replica, req: dict, out: "queue.Queue"):
        with self._lock:
            r.inflight += 1
        t = threading.Thread(target=self._forward_attempt,
                             args=(r, req, out), daemon=True)
        t.start()

    def _hedge_delay(self, r: Replica) -> float:
        p95 = r.p95()
        if p95 is None:
            return max(self.cfg.hedge_min_delay_s, 0.2)
        return max(self.cfg.hedge_min_delay_s,
                   p95 * self.cfg.hedge_after_p95_mult)

    @staticmethod
    def _idempotent(req: dict) -> bool:
        """Greedy decoding is deterministic; seeded sampling is too (the
        engines derive the sampling rng from the request seed, and the
        wire default seed is 0) — so a duplicate execution returns the
        SAME completion and hedging is safe. Only an explicit
        ``"idempotent": false`` opts a request out."""
        if req.get("idempotent") is False:
            return False
        return True

    def handle(self, req: dict) -> dict:
        """One request end-to-end: admission (shed), pick, forward with
        hedging/failover, exactly one reply. Every request also leaves a
        ``waterfall_hop`` record (round 21): the router parses-or-mints
        the W3C traceparent, forwards it so the engine's request span
        shares the trace_id, and stamps hop provenance (queue wait, shed,
        replica picked, hedge winner/loser + wasted seconds, retries) so
        ``slt waterfall`` can merge both sides into one timeline."""
        from serverless_learn_tpu.telemetry.tracing import (
            new_context, node_name, parse_traceparent)

        t_start = self.clock()
        priority = req.pop("priority", 1)
        session = req.pop("session", None)
        probe = bool(req.pop("probe", False))
        pin_version = req.pop("pin_version", None)
        if not isinstance(pin_version, str) or not pin_version:
            pin_version = None
        try:
            priority = int(priority)
        except (TypeError, ValueError):
            priority = 1
        if probe:
            # Golden probes are shed-exempt (round 23): priority >= 1
            # bypasses the brownout and KV-pressure sheds below (the
            # hard queue-full backstop still applies — a probe must not
            # be able to wedge an overloaded fleet either).
            priority = max(priority, 1)
        ctx = parse_traceparent(req.get("traceparent")) or new_context()
        req["traceparent"] = ctx.traceparent()
        hop = {"event": "waterfall_hop", "trace_id": ctx.trace_id,
               "node": node_name(), "t_unix_s": time.time(),
               "shed": False, "hedged": False, "retries": 0,
               "queue_wait_s": 0.0}
        if probe:
            # Tagged in the ledger so offline SLI aggregation (canary,
            # waterfall) can exclude probe traffic like the live
            # histograms below do.
            hop["probe"] = True

        # ---- admission: bounded queue with brownout shedding ----
        cap = max(1, self.cfg.max_inflight)
        shed_at = max(1, int(cap * self.cfg.shed_start_frac))
        deadline = t_start + self.cfg.queue_timeout_s
        with self._adm_cv:
            while True:
                if self._inflight < cap and (
                        self._inflight < shed_at or priority > 0):
                    self._inflight += 1
                    self._g_inflight.set(self._inflight)
                    break
                if priority <= 0:
                    # Brownout: lowest-priority traffic never queues —
                    # rejecting it instantly is what keeps the queue
                    # short for traffic that matters.
                    self._m_shed.inc()
                    self._note_decision(req, [], None, session, hop,
                                        reason="shed_brownout",
                                        account=False, probe=probe)
                    self._emit_hop(hop, t_start, shed=True)
                    return _overload_reply(
                        f"brownout at {self._inflight}/{cap} in flight")
                remaining = deadline - self.clock()
                if remaining <= 0:
                    self._m_shed.inc()
                    self._note_decision(req, [], None, session, hop,
                                        reason="shed_queue_full",
                                        account=False, probe=probe)
                    self._emit_hop(hop, t_start, shed=True)
                    return _overload_reply(
                        f"queue full ({cap} in flight, waited "
                        f"{self.cfg.queue_timeout_s:g}s)")
                self._adm_cv.wait(remaining)
        # KV-pressure brownout: when EVERY eligible replica's paged pool
        # is nearly exhausted, background traffic sheds immediately —
        # queue depth alone cannot see a fleet out of KV memory (its
        # queues drain slowly but its admissions all backpressure).
        if (priority <= 0
                and self._kv_pressure() < self.cfg.kv_shed_free_frac):
            with self._adm_cv:
                self._inflight -= 1
                self._g_inflight.set(self._inflight)
                self._adm_cv.notify()
            self._m_shed.inc()
            self._note_decision(req, [], None, session, hop,
                                reason="shed_kv_pressure",
                                account=False, probe=probe)
            self._emit_hop(hop, t_start, shed=True)
            return _overload_reply(
                f"fleet KV pool pressure (free frac < "
                f"{self.cfg.kv_shed_free_frac:g})")
        hop["queue_wait_s"] = round(self.clock() - t_start, 6)
        if not probe:
            # User-facing SLI histograms exclude probe traffic; probes
            # get their own counter + running overhead-share gauge.
            self._h_queue_wait.observe(self.clock() - t_start)
        self._m_requests.inc()
        with self._lock:
            self._total_req_sum += 1
            if probe:
                self._probe_req_sum += 1
            share = self._probe_req_sum / self._total_req_sum
        if probe:
            self._m_probe_requests.inc()
        self._g_probe_overhead.set(round(share, 4))
        try:
            rep = self._dispatch(req, session, hop, probe=probe,
                                 pin_version=pin_version)
        finally:
            with self._adm_cv:
                self._inflight -= 1
                self._g_inflight.set(self._inflight)
                self._adm_cv.notify()
        if "error" in rep and rep.get("code") != "overloaded":
            self._m_errors.inc()
        elif not probe:
            self._h_latency.observe(self.clock() - t_start)
        self._emit_hop(hop, t_start,
                       shed=bool(rep.get("code") == "overloaded"))
        return rep

    def _emit_hop(self, hop: dict, t_start: float, shed: bool = False):
        """Finish + emit one ``waterfall_hop`` record. When losing hedge
        attempts are still in flight the emission is deferred to the
        drain thread so the record carries their wasted/cancel seconds."""
        hop["total_s"] = round(self.clock() - t_start, 6)
        if shed:
            hop["shed"] = True
        drain = hop.pop("_drain", None)
        if drain is not None:
            t = threading.Thread(target=self._drain_losers,
                                 args=(hop,) + drain, daemon=True)
            t.start()
            return
        self._emit(hop)

    def _drain_losers(self, hop: dict, out: "queue.Queue", pending: int,
                      t_win: float):
        """Wait for the losing hedge attempt(s) to land, charge their
        duplicate upstream seconds, then emit the completed hop record.
        ``hedge_cancel_s`` is how long past the winner the loser kept
        running — the latency cost of not having true cancellation."""
        wasted = 0.0
        cancel = None
        deadline = self.clock() + self.cfg.upstream_timeout_s + 1.0
        for _ in range(pending):
            try:
                r, rep, err, dt = out.get(
                    timeout=max(0.0, deadline - self.clock()))
            except queue.Empty:
                break
            wasted += dt
            lag = max(0.0, self.clock() - t_win)
            cancel = lag if cancel is None else max(cancel, lag)
            hop.setdefault("hedge_loser", r.addr)
        if wasted > 0.0:
            self._m_hedge_wasted.inc(wasted)
        hop["hedge_wasted_s"] = round(wasted, 6)
        if cancel is not None:
            hop["hedge_cancel_s"] = round(cancel, 6)
        self._emit(hop)

    # -- route-decision provenance (round 22) --------------------------------

    # Prompt chunks hashed per decision — bounds the per-request hashing
    # cost and the event size for pathological prompts.
    _PROMPT_HASH_CAP = 128

    def _new_decision_id(self, trace_id: str) -> str:
        with self._lock:
            self._decision_seq += 1
            seq = self._decision_seq
        return f"{trace_id[:16]}-{seq}"

    def _note_decision(self, req: dict, candidates: List[Replica],
                       pick: Optional[Replica], session: Optional[str],
                       hop: Optional[dict], reason: str,
                       account: bool = True, parent: Optional[str] = None,
                       exclude=frozenset(), probe: bool = False,
                       assign: Optional[str] = None) -> Optional[str]:
        """Emit one structured ``route_decision`` record and (for primary
        picks) account fleet-wide redundant prefill.

        The record carries the full candidate set with per-replica scores
        (load, KV pressure bucket, windowed prefix hit rate, resident
        prompt tokens per the ping digests) plus the prompt's chain
        hashes — everything ``slt fleetscope`` needs to re-score the
        decision under a counterfactual policy offline. ``redundant
        prefill`` for a decision is the prompt tokens the PICK must
        prefill that some other eligible replica already holds resident:
        ``max(0, best_other_resident - pick_resident)``. Digests are
        probe-lagged and truncated shallow-first, so the accounting
        UNDER-counts; it never fabricates redundancy."""
        from serverless_learn_tpu.inference.kvcache import chunk_hashes

        trace_id = hop.get("trace_id", "") if hop else ""
        did = parent or self._new_decision_id(trace_id)
        prompt = req.get("prompt")
        n_prompt = len(prompt) if isinstance(prompt, (list, tuple)) else 0
        with self._lock:
            bs = next((r.digest_block_size for r in candidates
                       if r.digest_block_size), 0)
            hxs: List[str] = []
            if bs and n_prompt:
                hxs = chunk_hashes(
                    prompt[:bs * self._PROMPT_HASH_CAP], bs)
            cand_rows = []
            resident: Dict[str, int] = {}
            for r in candidates:
                run = 0
                if hxs and r.digest_hashes:
                    for h in hxs:
                        if h not in r.digest_hashes:
                            break
                        run += 1
                resident[r.addr] = run * bs
                cand_rows.append({
                    "addr": r.addr, "state": r.state,
                    "inflight": r.inflight,
                    "kv_pressure_bucket": (
                        None if r.kv_free_frac is None else
                        int((1.0 - max(0.0, min(1.0, r.kv_free_frac)))
                            * 5.0)),
                    "prefix_hit_rate": r.prefix_hit_rate,
                    "resident_tokens": run * bs,
                    "eligible": r.addr not in exclude,
                    "version": r.version})
        spread = sum(1 for v in resident.values() if v > 0)
        red = 0
        if account and pick is not None and n_prompt:
            best_other = max(
                (v for a, v in resident.items() if a != pick.addr),
                default=0)
            red = max(0, min(best_other, n_prompt)
                      - resident.get(pick.addr, 0))
            with self._lock:
                self._prompt_tokens_sum += n_prompt
                self._redundant_tokens_sum += red
                frac = (self._redundant_tokens_sum
                        / max(1, self._prompt_tokens_sum))
            self._m_prompt_tokens.inc(n_prompt)
            if red:
                self._m_redundant_tokens.inc(red)
            self._g_redundant_frac.set(round(frac, 4))
        rec = {"event": "route_decision", "decision_id": did,
               "trace_id": trace_id, "t_unix_s": time.time(),
               "reason": reason, "session": bool(session),
               "pick": pick.addr if pick is not None else None,
               "version": pick.version if pick is not None else None,
               "probe": probe,
               "prompt_tokens": n_prompt, "block_size": bs,
               "prompt_hashes": hxs,
               "redundant_prefill_tokens": red,
               "resident_replicas": spread,
               "candidates": cand_rows}
        if assign is not None:
            # Version-split provenance: "candidate"/"baseline" (the
            # session-sticky canary bucket) or "pinned" (probe target).
            rec["canary"] = assign
        try:
            self._emit(rec)
        except Exception:
            pass
        if hop is not None and parent is None:
            # Waterfall<->router join: the hop record names the decision
            # that picked its replica, so `slt waterfall` renders WHY.
            hop["decision_id"] = did
            hop["pick_reason"] = reason
        return did

    def _dispatch(self, req: dict, session: Optional[str],
                  hop: Optional[dict] = None, probe: bool = False,
                  pin_version: Optional[str] = None) -> dict:
        hedgeable = self.cfg.hedge and self._idempotent(req)
        req = {k: v for k, v in req.items() if k != "idempotent"}
        candidates = self._candidates()
        if not candidates:
            self._m_shed.inc()
            self._note_decision(req, [], None, session, hop,
                                reason="shed_no_replicas", account=False,
                                probe=probe)
            return _overload_reply("no healthy replicas")
        # ---- version-split assignment (round 23) ----
        # pin_version (probe targeting) filters STRICTLY; a configured
        # canary split buckets by session (one conversation never
        # straddles versions) or by trace for session-free traffic, and
        # falls back to the full pool when the assigned version has no
        # eligible replica — availability beats split fidelity.
        want = avoid = None
        assign = None
        if pin_version is not None:
            want, assign = pin_version, "pinned"
        else:
            with self._lock:
                canary_v = self._canary_version
                canary_f = self._canary_frac
            if canary_v and canary_f > 0.0:
                key = session or (hop or {}).get("trace_id") or ""
                bucket = int(hashlib.md5(
                    f"canary|{key}".encode()).hexdigest()[:8],
                    16) / 4294967296.0
                if bucket < canary_f:
                    want, assign = canary_v, "candidate"
                else:
                    avoid, assign = canary_v, "baseline"
        primary = self._pick(candidates, session, want_version=want,
                             avoid_version=avoid,
                             strict_version=pin_version is not None)
        if primary is None:
            self._m_shed.inc()
            self._note_decision(req, candidates, None, session, hop,
                                reason="shed_no_version", account=False,
                                probe=probe, assign=assign)
            return _overload_reply(
                f"no eligible replica serving version {pin_version}")
        if hop is not None:
            hop["primary"] = primary.addr
        did = self._note_decision(
            req, candidates, primary, session, hop,
            reason="session_affinity" if session else "least_loaded",
            probe=probe, assign=assign)
        out: "queue.Queue" = queue.Queue()
        tried = {primary.addr}
        launched = [primary.addr]
        self._launch(primary, req, out)
        pending = 1
        hedged = False
        retries = 0
        hedge_at = self.clock() + self._hedge_delay(primary)
        last_err = None
        while pending:
            timeout = None
            if hedgeable and not hedged:
                timeout = max(0.0, hedge_at - self.clock())
            try:
                r, rep, err, _dt = out.get(timeout=timeout)
            except queue.Empty:
                # Hedge: the primary is slow, race one more replica —
                # STRICTLY within the assigned/pinned version (two
                # versions racing could return divergent completions,
                # breaking hedge idempotency); no same-version spare
                # means no hedge.
                cands = self._candidates()
                hedge = self._pick(
                    cands, None, exclude=tried, want_version=want,
                    avoid_version=avoid,
                    strict_version=want is not None or avoid is not None)
                hedged = True
                if hop is not None:
                    hop["hedged"] = True
                if hedge is not None:
                    self._note_decision(
                        req, cands, hedge, None, hop, reason="hedge",
                        account=False, parent=f"{did}.h",
                        exclude=frozenset(tried), probe=probe,
                        assign=assign)
                    tried.add(hedge.addr)
                    launched.append(hedge.addr)
                    self._m_hedges.inc()
                    self._launch(hedge, req, out)
                    pending += 1
                continue
            pending -= 1
            launched.remove(r.addr)
            if rep is not None:
                if hedged and r.addr != primary.addr:
                    self._m_hedge_wins.inc()
                if hop is not None:
                    hop["replica"] = r.addr
                    hop["retries"] = retries
                    if hedged:
                        hop["hedge_winner"] = r.addr
                        if launched:
                            hop["hedge_loser"] = launched[0]
                    if pending:
                        # Hand the still-running loser(s) to the drain
                        # thread (started by _emit_hop) so the hop record
                        # ships with their wasted/cancel seconds.
                        hop["_drain"] = (out, pending, self.clock())
                # Losing attempts keep running on their daemon threads;
                # their replies land in `out`, which the drain thread
                # reads for provenance — the client still gets exactly
                # this one completion.
                return rep
            last_err = err
            if pending:
                continue  # the race partner may still answer
            if retries < self.cfg.max_retries:
                # Retry prefers the assigned version but falls back to
                # any replica (non-strict _pick): the client gets one
                # completion either way, and failover availability
                # outranks split fidelity once the pick has failed.
                cands = self._candidates()
                nxt = self._pick(cands, None, exclude=tried,
                                 want_version=want, avoid_version=avoid)
                if nxt is not None:
                    self._note_decision(
                        req, cands, nxt, None, hop, reason="retry",
                        account=False, parent=f"{did}.r{retries + 1}",
                        exclude=frozenset(tried), probe=probe,
                        assign=assign)
                    tried.add(nxt.addr)
                    launched.append(nxt.addr)
                    retries += 1
                    self._m_retries.inc()
                    self._launch(nxt, req, out)
                    pending += 1
                    continue
            if hop is not None:
                hop["retries"] = retries
            return {"error": f"upstream failed after {len(tried)} "
                             f"replica(s): {last_err}",
                    "code": "upstream_unavailable"}

    # -- wire server (same JSON-lines shape as GenerationServer) ------------

    def _serve_conn(self, conn: socket.socket):
        conn.settimeout(60.0)
        with conn, conn.makefile("rwb") as f:
            while True:
                try:
                    line = f.readline(MAX_LINE + 2)
                except socket.timeout:
                    return
                if not line:
                    return
                if len(line.rstrip(b"\r\n")) > MAX_LINE:
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
                    if req.get("op") == "fleet":
                        rep = {"ok": True, "replicas": self.replicas(),
                               "inflight": self._inflight}
                    else:
                        rep = self.handle(req)
                except Exception as e:
                    rep = {"error": f"{type(e).__name__}: {e}"}
                f.write(json.dumps(rep).encode() + b"\n")
                f.flush()

    def _serve_conn_safe(self, conn: socket.socket):
        try:
            self._serve_conn(conn)
        except OSError:
            pass
        finally:
            with self._conns_lock:
                self._conns.pop(threading.current_thread(), None)

    def serve_forever(self):
        self._sock.settimeout(0.2)
        while not self._stop.is_set():
            try:
                conn, _ = self._sock.accept()
            except socket.timeout:
                continue
            except OSError:
                break
            t = None
            with self._conns_lock:
                if len(self._conns) < self.max_connections:
                    t = threading.Thread(target=self._serve_conn_safe,
                                         args=(conn,), daemon=True)
                    self._conns[t] = conn
            if t is None:
                try:
                    conn.sendall(json.dumps(_overload_reply(
                        "router at connection capacity")).encode() + b"\n")
                    conn.close()
                except OSError:
                    pass
                continue
            t.start()

    def start(self) -> "FleetRouter":
        bg = threading.Thread(target=self._background_loop, daemon=True,
                              name="fleet-prober")
        bg.start()
        self._threads.append(bg)
        acc = threading.Thread(target=self.serve_forever, daemon=True,
                               name="fleet-router")
        acc.start()
        self._threads.append(acc)
        return self

    def stop(self):
        self._stop.set()
        try:
            self._sock.close()
        except OSError:
            pass
        with self._conns_lock:
            live = list(self._conns.items())
        for _, c in live:
            try:
                c.shutdown(socket.SHUT_RD)
            except OSError:
                pass
        for t in self._threads:
            t.join(timeout=5)
        client = getattr(self, "_coordinator", None)
        if client is not None:
            try:
                client.close()
            except Exception:
                pass
