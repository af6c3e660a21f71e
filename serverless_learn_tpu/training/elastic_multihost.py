"""Multi-host elastic training: membership changes restart the JAX world.

The reference's headline capability is "any *process* can join anytime"
(``src/master.cc:79-91``, ``src/worker.cc:117-129``) — but its processes only
ever gossiped doubles pairwise. ``training/elastic.py`` realizes elasticity
for the devices of ONE process; this module is the multi-process realization
(VERDICT round 1 item 1): N independent worker processes, each owning its
local TPU chips, form and re-form a single SPMD world as membership changes.

Why checkpoint-restart with a *supervisor per host*, not an in-process
re-initialize: JAX's world is fixed at ``jax.distributed.initialize``
(SURVEY §7 hard part (a)), and — measured here, not assumed — when a member
dies mid-step the survivors either get hard-terminated by the distributed
runtime's error propagation (default) or, with ``jax_enable_recoverability``,
block forever inside the gloo/ICI collective with no catchable error. A
Python thread wedged in a collective cannot be recovered in-process. So each
host runs:

    supervisor (this module, pure Python, no JAX state)
        owns the WorkerAgent: registration under a run-scoped tag, lease
        heartbeats, membership snapshots from the native coordinator
    inner trainer (subprocess, one per *generation* of the world)
        jax.distributed world over the current member set; jitted step;
        sharded checkpoints on the shared data plane

Lifecycle per generation:

    form        supervisors wait for a *stable* view of tagged peers;
                ranks are ascending worker-id order
    rendezvous  rank 0's supervisor spawns its inner first; the inner binds
                a fresh coordination-service port and reports it; the
                supervisor publishes {generation, member ids, address} as
                one JSON value on the data plane (the same store that
                carries shards and checkpoints). Follower supervisors poll
                until the published ids match their own stable view —
                exact agreement, no port arithmetic, no split-brain joins.
    run         inner: initialize → Mesh over all global devices → step
                loop. Every step each inner all-gathers a tiny drain flag,
                so every process leaves the loop at the SAME step (a lone
                early exit would wedge the others' collectives). Periodic
                sharded checkpoints bound crash loss.
    drain       on a membership change that *grows* the set, supervisors
                send "drain" on the inner's stdin; inners agree via the
                flag allgather, finish the step, save a sharded checkpoint
                (process 0 commits), and exit cleanly.
    kill        on a membership change that *loses* a member, the world is
                already broken — no collective (not even the drain
                agreement or the checkpoint barrier) can complete. The
                supervisor grants a short grace, then SIGKILLs the wedged
                inner. Steps since the last committed checkpoint are lost:
                that is the fault-tolerance contract, and the COMMIT marker
                guarantees the loss is to a *consistent* step.
    resume      re-form with the new membership; the next inner restores
                the latest committed checkpoint into the new world's
                shardings (restore-time resharding moves only the byte
                ranges each host needs) and continues.

Joins and crashes are thus symmetric at the membership level — exactly the
reference's birth-registration elasticity — while the gradient path stays
synchronous SPMD with zero bytes on the control plane.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import threading
import time
from dataclasses import dataclass
from typing import List, Optional

from serverless_learn_tpu.config import (ExperimentConfig,
                                          UnsatisfiableMeshError, scale_mesh)
from serverless_learn_tpu.control.gossip import make_membership_agent
from serverless_learn_tpu.training.checkpoint import (
    Checkpointer, LocalStore, ShardServerStore)
from serverless_learn_tpu.utils.metrics import log_json

# Registration-name tag for multi-host elastic participants. Distinct from
# multihost.MH_TAG (fixed-size bootstrap) so the two rendezvous protocols
# never rank each other's processes.
EMH_TAG = "emh!"


def store_spec(store) -> dict:
    """Serializable description of a checkpoint/rendezvous store, for
    handing to the inner subprocess."""
    if isinstance(store, ShardServerStore):
        return {"kind": "shard", "addr": store.addr}
    if isinstance(store, LocalStore):
        return {"kind": "local", "root": store.root}
    raise TypeError(f"unsupported store {type(store).__name__}")


def store_from_spec(spec: dict):
    if spec["kind"] == "shard":
        return ShardServerStore(spec["addr"])
    if spec["kind"] == "local":
        return LocalStore(spec["root"])
    raise ValueError(f"unknown store kind {spec['kind']!r}")


@dataclass
class Generation:
    """One formed world, as observed by this host's supervisor."""

    gen: int
    world: int
    rank: int
    start_step: int = -1
    end_step: int = -1
    status: str = "formed"  # formed | complete | remesh | killed | error
    mesh: Optional[dict] = None  # axis sizes the inner actually formed


# ---------------------------------------------------------------------------
# Supervisor (one per host)
# ---------------------------------------------------------------------------


class ElasticHostSupervisor:
    """Keeps one host participating in an elastic multi-host run."""

    def __init__(
        self,
        config: ExperimentConfig,
        store,
        coordinator_addr: str,
        run_name: str = "run",
        label: Optional[str] = None,
        advertise_host: str = "127.0.0.1",
        n_chips: Optional[int] = None,
        min_hosts: int = 1,
        form_timeout_s: float = 120.0,
        init_timeout_s: float = 30.0,
        drain_timeout_s: float = 120.0,
        kill_grace_s: float = 5.0,
        inner_env: Optional[dict] = None,
        verbose: bool = False,
    ):
        self.config = config
        self.store = store
        self.run_name = run_name
        self.min_hosts = min_hosts
        self.form_timeout_s = form_timeout_s
        self.init_timeout_s = init_timeout_s
        self.drain_timeout_s = drain_timeout_s
        self.kill_grace_s = kill_grace_s
        self.inner_env = inner_env
        self.verbose = verbose
        self.advertise_host = advertise_host
        self.generations: List[Generation] = []
        # step -> loss across all generations; a crash-restart re-records
        # the replayed steps (last write wins), so the series is the run's
        # actual training trajectory.
        self.step_losses: dict = {}
        self._membership_changed = threading.Event()
        label = label or f"{socket.gethostname()}-{os.getpid()}"
        self._tag = f"{EMH_TAG}{run_name}/"
        # Membership plane per config.membership.mode (SWIM gossip or the
        # classic master-heartbeat fallback) — round 11.
        self.agent = make_membership_agent(
            config, coordinator_addr, f"{advertise_host}:0",
            name=self._tag + label,
            n_chips=n_chips if n_chips is not None else 1,
            on_epoch_change=lambda e, p: self._membership_changed.set())
        self._last_gen = 0

    # -- membership --------------------------------------------------------

    def _tagged_ids(self, peers) -> List[int]:
        return sorted(p.worker_id for p in peers
                      if p.name.startswith(self._tag))

    def _current_ids(self) -> List[int]:
        return self._tagged_ids(self.agent.snapshot()[1])

    def _tagged_view(self) -> tuple:
        """(sorted ids, {id: chips}) for tagged peers — from ONE membership
        snapshot, so the pair is always internally consistent."""
        peers = [p for p in self.agent.snapshot()[1]
                 if p.name.startswith(self._tag)]
        return (sorted(p.worker_id for p in peers),
                {p.worker_id: max(1, p.n_chips) for p in peers})

    def _active_ids(self, ids: List[int],
                    chips: dict) -> Optional[List[int]]:
        """The subset of a stable membership that actually forms the world.

        The configured mesh makes some chip totals unusable (model axes
        need a divisible device total, fsdp has a memory floor — config.
        scale_mesh). Satisfiability depends only on the chip TOTAL, so this
        is a small subset-sum: every supervisor deterministically picks the
        member subset with the LARGEST satisfiable chip total (at least
        ``min_hosts`` members), which handles heterogeneous chip counts —
        e.g. hosts with [1, 2, 2] chips under tp=2 form the 4-chip world
        from the two 2-chip hosts, with the 1-chip host standing by (a
        plain id-prefix scan would find every prefix total odd and
        wrongly declare the membership unsatisfiable). Ties prefer
        lower-id members (join order). Spares re-join at the next
        membership change. Returns None when no subset works.

        ``chips`` MUST come from the same snapshot as ``ids`` (use
        ``_tagged_view``): mixing a stale id list with fresh chip counts
        would let two supervisors derive different active sets from "the
        same" view.
        """
        grand = sum(chips[i] for i in ids)
        need = max(self.min_hosts, 1)
        n = len(ids)
        # Layered reachability: reach[i][t] is a bitmask of member COUNTS
        # achievable with chip total t using only the first i members. The
        # layers are kept (not a rolling 1-D array with backpointers: a
        # single take[] table gets overwritten by later members and its
        # chains then mix DP generations — that produced duplicated
        # members / wrong totals) so the backtrack below is exact.
        reach = [[0] * (grand + 1) for _ in range(n + 1)]
        reach[0][0] = 1  # zero members, zero chips
        for i in range(n):
            c = chips[ids[i]]
            prev, cur = reach[i], reach[i + 1]
            for t in range(grand + 1):
                m = prev[t]
                if t >= c:
                    m |= prev[t - c] << 1
                cur[t] = m
        for total in range(grand, 0, -1):
            counts = reach[n][total] >> need
            if not counts:
                continue
            try:
                scale_mesh(self.config.mesh, total)
            except UnsatisfiableMeshError:
                continue
            # Largest achievable member count (use more of the fleet), then
            # backtrack preferring to EXCLUDE high-id members when both
            # choices remain feasible -> lower ids (join order) win ties.
            k = counts.bit_length() - 1 + need
            members, t = [], total
            for i in range(n, 0, -1):
                if (reach[i - 1][t] >> k) & 1:
                    continue  # droppable without losing feasibility
                members.append(ids[i - 1])
                t -= chips[ids[i - 1]]
                k -= 1
            assert t == 0 and k == 0, (ids, chips, total, members)
            return sorted(members)
        return None

    def _stable_view(self, deadline: float) -> tuple:
        """Wait until the set of tagged peers (incl. us) holds still for a
        stability window; returns (ids, {id: chips}) from the final
        snapshot. Untagged workers sharing the coordinator churn the epoch
        but not this view."""
        stability_s = max(2.0 * self.agent.interval, 0.3)
        view: Optional[List[int]] = None
        chips: dict = {}
        since = 0.0
        while True:
            ids, chips = self._tagged_view()
            me = self.agent.worker_id
            now = time.time()
            if me in ids and len(ids) >= self.min_hosts:
                if ids != view:
                    view, since = ids, now
                elif now - since >= stability_s:
                    return ids, chips
            else:
                view = None
            if now > deadline:
                raise TimeoutError(
                    f"no stable membership within {self.form_timeout_s}s "
                    f"(last view {view}, me {me})")
            time.sleep(0.05)

    # -- rendezvous over the data plane -------------------------------------

    def _form_key(self) -> str:
        return f"emh-{self.run_name}/FORM"

    def _read_form(self) -> Optional[dict]:
        try:
            return json.loads(self.store.get(self._form_key()))
        except (IOError, OSError, ValueError):
            return None

    def _committed_step(self) -> int:
        """Latest committed checkpoint step, observed via the data plane —
        how standby hosts (and the completion fast path) track a world they
        are not part of."""
        try:
            meta = json.loads(self.store.get(f"emh-{self.run_name}/LATEST"))
            return int(meta["step"])
        except (IOError, OSError, ValueError, KeyError):
            return -1

    def _standby(self, deadline: Optional[float], why: str) -> str:
        """Wait out a world this host is not part of.

        deadline=None: an active world is running without us (hot spare) —
        wait indefinitely for membership churn or run completion. With a
        deadline: NO satisfiable world exists; if membership still hasn't
        produced one by the deadline, raise (loudly — never fall back to a
        mesh the config doesn't describe).
        """
        if self.verbose:
            log_json({"event": "standby", "why": why,
                      "rank0_world": None if deadline is None else "none"})
        while True:
            if self._committed_step() >= self.config.train.num_steps:
                return "complete"
            # Event-wait gives instant membership wakeups while the LATEST
            # store read (a network RPC on ShardServerStore) stays at 1 Hz —
            # a spare can idle for hours without hammering the data plane.
            if self._membership_changed.wait(timeout=1.0):
                self._membership_changed.clear()
                return "standby"
            if deadline is not None and time.time() > deadline:
                raise UnsatisfiableMeshError(
                    f"no satisfiable world within {self.form_timeout_s}s: "
                    f"{why}")

    # -- inner process ------------------------------------------------------

    def _spawn_inner(self, gen: int, rank: int, world: int,
                     addr: Optional[str]) -> "_InnerHandle":
        args = [
            sys.executable, "-u", "-m",
            "serverless_learn_tpu.training.elastic_multihost",
            "--gen", str(gen), "--rank", str(rank), "--world", str(world),
            "--run-name", self.run_name,
            "--store", json.dumps(store_spec(self.store)),
            "--config", self.config.to_json(),
            "--advertise-host", self.advertise_host,
            "--init-timeout-s", str(self.init_timeout_s),
        ]
        if addr:
            args += ["--addr", addr]
        env = dict(os.environ)
        if self.inner_env:
            env.update(self.inner_env)
        proc = subprocess.Popen(args, stdin=subprocess.PIPE,
                                stdout=subprocess.PIPE, env=env, text=True)
        return _InnerHandle(proc, verbose=self.verbose, rank=rank)

    # -- main loop ----------------------------------------------------------

    def run(self, max_consecutive_failures: int = 8):
        """Participate until the run completes ``config.train.num_steps``
        (as observed via the shared checkpoint) or formation times out.

        ``max_consecutive_failures`` bounds deterministic-failure loops
        (bad config, broken store): generations that neither trained nor
        followed a real membership change count against it; any productive
        generation resets it.
        """
        self.agent.start()
        failures = 0
        try:
            while True:
                status = self._one_generation()
                if status == "complete":
                    return self.generations
                if status in ("remesh", "killed", "standby"):
                    failures = 0  # real membership churn / waiting, not a fault
                else:
                    failures += 1
                    if failures >= max_consecutive_failures:
                        raise RuntimeError(
                            f"{failures} consecutive failed world "
                            f"formations (last status {status!r}); giving "
                            "up — check the inner trainer's stderr")
                    time.sleep(min(0.5 * failures, 5.0))
        finally:
            self.agent.stop()

    def _one_generation(self) -> str:
        deadline = time.time() + self.form_timeout_s
        self._membership_changed.clear()
        if self._committed_step() >= self.config.train.num_steps:
            return "complete"  # run finished while we were between worlds
        ids, chips = self._stable_view(deadline)
        active = self._active_ids(ids, chips)
        if active is None:
            return self._standby(
                deadline, f"membership {ids} (chips {chips}) "
                          f"cannot host mesh {self.config.mesh}")
        if self.agent.worker_id not in active:
            return self._standby(None, f"hot spare behind active {active}")
        rank = active.index(self.agent.worker_id)
        world = len(active)

        inner: Optional[_InnerHandle] = None
        if rank == 0:
            prev = self._read_form()
            gen = max(prev["gen"] if prev else 0, self._last_gen) + 1
            inner = self._spawn_inner(gen, 0, world, addr=None)
            addr = inner.wait_event("service_addr",
                                    timeout=self.init_timeout_s)
            if addr is None:
                inner.kill()
                return "retry"
            self.store.put(self._form_key(), json.dumps(
                {"gen": gen, "ids": active, "addr": addr["addr"]}).encode())
        else:
            # Follower: wait for a FORM that matches our computed active set
            # (every supervisor derives the same one from the same stable
            # view + registered chip counts).
            form = None
            while time.time() < deadline:
                form = self._read_form()
                if (form and form["ids"] == active
                        and form["gen"] > self._last_gen):
                    break
                if self._current_ids() != ids:
                    return "retry"  # view moved; re-form
                time.sleep(0.05)
                form = None
            if form is None:
                return "retry"
            gen = form["gen"]
            inner = self._spawn_inner(gen, rank, world, addr=form["addr"])

        self._last_gen = gen
        g = Generation(gen=gen, world=world, rank=rank)
        self.generations.append(g)
        status = self._monitor(inner, g, ids, active)
        g.status = status
        if self.verbose:
            log_json({"event": "generation_done", "gen": gen, "rank": rank,
                      "world": world, "status": status,
                      "start_step": g.start_step, "end_step": g.end_step})
        return status

    def _monitor(self, inner: "_InnerHandle", g: Generation,
                 ids: List[int], active: List[int]) -> str:
        """Relay inner progress into heartbeats; react to membership
        changes; decide drain-vs-kill. Returns the generation's outcome.

        ``ids`` is the full stable view the world was formed from; ``active``
        is the subset actually IN the world. Only an active member's loss
        breaks collectives (-> kill); spare churn either offers growth
        (join -> drain) or is irrelevant (spare departure -> ignore).
        """
        drain_sent = False
        kill_at: Optional[float] = None
        while True:
            ev = inner.poll_event(timeout=0.1)
            if ev is not None:
                if ev["event"] == "inner_up":
                    g.start_step = ev["step"]
                    g.mesh = ev.get("mesh")
                    if self.verbose:
                        log_json({"event": "world_formed", "gen": g.gen,
                                  "world": g.world, "rank": g.rank,
                                  "step": ev["step"], "mesh": ev.get("mesh"),
                                  "devices": ev.get("devices")})
                elif ev["event"] == "step":
                    self.step_losses[ev["step"]] = ev.get("loss", 0.0)
                    self.agent.report(ev["step"], ev.get("loss", 0.0),
                                      flow=ev.get("flow", 0))
                elif ev["event"] == "inner_done":
                    g.end_step = ev["step"]
            if inner.exited():
                # Join the reader thread and drain the tail of the event
                # queue BEFORE judging the outcome: the process can exit
                # before its final stdout lines are parsed, and dropping
                # them would misread a clean drain as an error (and lose
                # the last step/loss records).
                inner.wait()
                while True:
                    tail = inner.poll_event()
                    if tail is None:
                        break
                    if tail["event"] == "inner_up":
                        g.start_step = tail["step"]
                        g.mesh = tail.get("mesh")
                    elif tail["event"] == "step":
                        self.step_losses[tail["step"]] = tail.get("loss", 0.0)
                rc = inner.returncode()
                done = inner.last_done()
                if done is not None:
                    g.end_step = done["step"]
                if rc == 0 and done is not None:
                    return done["status"]  # "complete" | "remesh"
                return "error"
            if self._membership_changed.is_set():
                self._membership_changed.clear()
                cur, cur_chips = self._tagged_view()
                if cur != ids:
                    lost_active = set(active) - set(cur)
                    would_be = self._active_ids(cur, cur_chips)
                    if lost_active:
                        # World broken: no collective (not even the drain
                        # agreement) can complete; the inner is wedged or
                        # about to be. Short grace, then kill — shortening
                        # any longer drain deadline a prior join set.
                        if not drain_sent:
                            inner.send_drain()
                            drain_sent = True
                        ka = time.time() + self.kill_grace_s
                        kill_at = ka if kill_at is None else min(kill_at, ka)
                    elif would_be is not None and would_be != active:
                        # Growth (or reshuffle) opportunity: the new
                        # membership forms a DIFFERENT active set. Drain
                        # cleanly and re-form to absorb it.
                        if not drain_sent:
                            inner.send_drain()
                            drain_sent = True
                        if kill_at is None:
                            kill_at = time.time() + self.drain_timeout_s
                    # Otherwise (spare-only churn, or a joiner that cannot
                    # change the active set — e.g. an odd chip that keeps
                    # the same satisfiable prefix): don't restart a healthy
                    # world for a membership change that alters nothing.
                    ids = cur
            if kill_at is not None and time.time() > kill_at:
                inner.kill()
                inner.wait()
                done = inner.last_done()
                if done is not None:
                    g.end_step = done["step"]
                return "killed"


class _InnerHandle:
    """Non-blocking line-event reader + control channel for one inner."""

    def __init__(self, proc: subprocess.Popen, verbose: bool, rank: int):
        self.proc = proc
        self.verbose = verbose
        self.rank = rank
        self._events: List[dict] = []
        self._done: Optional[dict] = None
        self._lock = threading.Lock()
        self._cursor = 0
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _read(self):
        try:
            for line in self.proc.stdout:
                line = line.strip()
                if not line:
                    continue
                try:
                    ev = json.loads(line)
                except ValueError:
                    continue  # stray non-JSON output
                if not (isinstance(ev, dict) and "event" in ev):
                    # Native libraries under the inner occasionally write to
                    # fd 1; a bare JSON scalar ("1") parses fine and then
                    # crashed _monitor's ev["event"] (observed: supervisor
                    # death -> partner's formation timeout). Only dicts
                    # carrying an "event" tag are protocol messages.
                    continue
                with self._lock:
                    self._events.append(ev)
                    if ev.get("event") == "inner_done":
                        self._done = ev
        except (IOError, OSError, ValueError):
            pass

    def poll_event(self, timeout: float = 0.0) -> Optional[dict]:
        deadline = time.time() + timeout
        while True:
            with self._lock:
                if self._cursor < len(self._events):
                    ev = self._events[self._cursor]
                    self._cursor += 1
                    return ev
            if time.time() >= deadline:
                return None
            time.sleep(0.02)

    def wait_event(self, name: str, timeout: float) -> Optional[dict]:
        deadline = time.time() + timeout
        seen = 0
        while time.time() < deadline:
            with self._lock:
                while seen < len(self._events):
                    if self._events[seen].get("event") == name:
                        return self._events[seen]
                    seen += 1
            if self.proc.poll() is not None:
                return None
            time.sleep(0.02)
        return None

    def send_drain(self):
        try:
            self.proc.stdin.write("drain\n")
            self.proc.stdin.flush()
        except (IOError, OSError, ValueError):
            pass  # inner already gone

    def exited(self) -> bool:
        return self.proc.poll() is not None

    def returncode(self):
        return self.proc.returncode

    def wait(self, timeout: Optional[float] = None):
        self.proc.wait(timeout=timeout)
        self._reader.join(timeout=2.0)

    def kill(self):
        try:
            self.proc.kill()
        except OSError:
            pass

    def last_done(self) -> Optional[dict]:
        with self._lock:
            return self._done


# ---------------------------------------------------------------------------
# Inner trainer (one process per generation of the world)
# ---------------------------------------------------------------------------


def _emit(ev: dict):
    sys.stdout.write(json.dumps(ev) + "\n")
    sys.stdout.flush()


def inner_main(argv: Optional[List[str]] = None) -> int:
    import argparse

    p = argparse.ArgumentParser()
    p.add_argument("--gen", type=int, required=True)
    p.add_argument("--rank", type=int, required=True)
    p.add_argument("--world", type=int, required=True)
    p.add_argument("--addr", default=None)
    p.add_argument("--run-name", required=True)
    p.add_argument("--store", required=True)
    p.add_argument("--config", required=True)
    p.add_argument("--advertise-host", default="127.0.0.1")
    p.add_argument("--init-timeout-s", type=float, default=30.0)
    args = p.parse_args(argv)

    import jax

    addr = args.addr
    if args.rank == 0 and addr is None:
        with socket.socket() as s:
            s.bind((args.advertise_host, 0))
            port = s.getsockname()[1]
        addr = f"{args.advertise_host}:{port}"
        _emit({"event": "service_addr", "addr": addr})

    jax.distributed.initialize(
        coordinator_address=addr, num_processes=args.world,
        process_id=args.rank,
        initialization_timeout=int(args.init_timeout_s),
        heartbeat_timeout_seconds=10)

    import numpy as np
    from jax.experimental import multihost_utils

    from serverless_learn_tpu.data.datasets import Prefetcher
    from serverless_learn_tpu.parallel.mesh import make_mesh
    from serverless_learn_tpu.training.loop import make_source
    from serverless_learn_tpu.training.train_step import build_trainer

    config = ExperimentConfig.from_json(args.config)
    store = store_from_spec(json.loads(args.store))
    ckpt = Checkpointer(store, name=f"emh-{args.run_name}",
                        async_save=False, sharded=True)

    # Honor the configured mesh at every world size: model axes fixed, fsdp
    # floor respected, dp stretched (config.scale_mesh). The supervisor only
    # forms worlds it believes satisfiable; this raise is the backstop for a
    # supervisor whose chip accounting was wrong (loud, not dp-fallback).
    mesh_cfg = scale_mesh(config.mesh, len(jax.devices()))
    cfg = config.override(mesh=mesh_cfg)
    mesh = make_mesh(mesh_cfg, devices=list(jax.devices()))
    trainer = build_trainer(cfg, mesh=mesh)
    if ckpt.latest_step() is not None:
        state = ckpt.restore(trainer.abstract_state(),
                             shardings=trainer.state_shardings)
    else:
        state = trainer.init()
    step = int(jax.device_get(state.step))
    _emit({"event": "inner_up", "gen": args.gen, "step": step,
           "rank": args.rank, "world": args.world,
           "devices": len(jax.devices()),
           "mesh": mesh_cfg.nontrivial_axes()})

    # Drain requests arrive on stdin from the supervisor.
    drain = threading.Event()

    def watch_stdin():
        for line in sys.stdin:
            if line.strip() == "drain":
                drain.set()

    threading.Thread(target=watch_stdin, daemon=True).start()

    num_steps = cfg.train.num_steps
    ckpt_every = cfg.train.checkpoint_every
    source = make_source(cfg, trainer, dp_rank=args.rank, dp_size=args.world,
                         start_step=step)
    prefetch = Prefetcher(iter(source), trainer.shard_batch,
                          depth=cfg.data.prefetch)
    status = "complete"
    # Test pacing knob: slows the step loop so process-level churn tests
    # can schedule joins/kills at meaningful points. Never set in production.
    step_delay = float(os.environ.get("SLT_STEP_DELAY_S", "0") or 0)
    try:
        while step < num_steps:
            # Every process must leave this loop at the same step: agree on
            # the drain flag with a tiny allgather before each step.
            flags = multihost_utils.process_allgather(
                np.array([1 if drain.is_set() else 0], np.int32))
            if int(np.max(flags)) > 0:
                status = "remesh"
                break
            batch = next(prefetch)
            state, metrics = trainer.step(state, batch)
            loss = float(jax.device_get(metrics["loss"]))
            step += 1
            _emit({"event": "step", "step": step, "loss": loss,
                   "flow": prefetch.depth()})
            if ckpt_every and step % ckpt_every == 0 and step < num_steps:
                ckpt.save_sharded(state)
            if step_delay:
                time.sleep(step_delay)
    finally:
        prefetch.close()
        if hasattr(source, "close"):
            source.close()
    ckpt.save_sharded(state)
    _emit({"event": "inner_done", "step": step, "status": status,
           "gen": args.gen})
    # Skip jax.distributed.shutdown(): with a clean exit the coordination
    # service notices the disconnect, and a wedged shutdown barrier (peer
    # already gone) would turn a clean drain into a supervisor kill.
    return 0


if __name__ == "__main__":
    sys.exit(inner_main())
