"""Multi-host bootstrap: turn elastic membership into a JAX process group.

The reference "scales" by workers registering with a well-known master at
birth (``src/worker.cc:117-129``, ``src/master.cc:79-91``) — but its
processes never coordinate beyond random pairwise gossip. Here the same
birth-registration contract *bootstraps a real SPMD world*: each host
registers with the native coordinator, ranks are derived from the membership
snapshot, and ``jax.distributed.initialize`` forms the process group. After
that, cross-host gradient traffic rides XLA collectives (ICI within a slice,
DCN between hosts) — the control plane only ever carried addresses.

Two entry paths:

* ``initialize(...)`` — explicit rank/world flags, for launchers that
  already know the topology (mirrors ``jax.distributed.initialize``).
* ``bootstrap_via_coordinator(...)`` — "serverless" path: no
  pre-assigned ranks; N hosts register with the coordinator, agree on
  rank order (ascending worker id), and rank 0's advertised endpoint
  becomes the JAX coordination service address.
"""

from __future__ import annotations

import socket
import time
from dataclasses import dataclass
from typing import Optional

from serverless_learn_tpu.control.client import WorkerAgent

# Registration-name tag marking bootstrap participants. Rank derivation only
# considers tagged peers, so ordinary elastic workers sharing the same
# coordinator are never ranked into (or displace hosts from) a forming world.
MH_TAG = "mh!"


def initialize(coordinator_address: str, num_processes: int,
               process_id: int, timeout_s: Optional[float] = None) -> None:
    """Explicit-topology init (thin wrapper, kept for symmetry/logging).

    ``timeout_s`` bounds the coordination-service connect so a host whose
    world view diverged fails with a clear error instead of hanging for
    JAX's multi-minute default.
    """
    import jax

    kw = {} if timeout_s is None else {
        "initialization_timeout": int(timeout_s)}
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id, **kw)


def free_port(host: str = "127.0.0.1") -> int:
    with socket.socket() as s:
        s.bind((host, 0))
        return s.getsockname()[1]


@dataclass
class World:
    """A formed multi-host world; keep it alive for the training run."""

    rank: int
    num_processes: int
    jax_coordinator: str
    worker_id: int
    agent: Optional[WorkerAgent]  # heartbeats keep our lease alive

    def shutdown(self, deregister: bool = True):
        if self.agent is not None:
            self.agent.stop(deregister=deregister)
            self.agent = None


def bootstrap_via_coordinator(
    coordinator_addr: str,
    world_size: int,
    advertise_host: str = "127.0.0.1",
    jax_port: Optional[int] = None,
    name: str = "host",
    n_chips: Optional[int] = None,
    timeout_s: float = 120.0,
    heartbeat_interval_ms: int = 1000,
    _initialize=None,
) -> World:
    """Register with the native coordinator, wait for ``world_size`` hosts,
    derive ranks, and run ``jax.distributed.initialize``.

    Each host advertises ``advertise_host:jax_port`` — a port it owns and
    on which it can serve the JAX coordination service *if* it ends up as
    rank 0 (only rank 0's endpoint is ever used). Ranks are ascending
    worker-id order, so the earliest registrant is rank 0.

    The returned ``World`` keeps a heartbeating ``WorkerAgent`` so the
    host's lease stays live during training; call ``shutdown()`` when done.
    ``world_size`` hosts must arrive within ``timeout_s``; extra hosts
    beyond ``world_size`` are not ranked and must not call this with the
    same coordinator while a group is forming.
    """
    # Hold the advertised port bound for the whole formation wait so another
    # process can't claim it in the window before rank 0's coordination
    # service binds it; released immediately before initialize.
    hold = socket.socket()
    hold.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    if jax_port is None:
        hold.bind((advertise_host, 0))
        jax_port = hold.getsockname()[1]
    else:
        hold.bind((advertise_host, jax_port))
    advertise = f"{advertise_host}:{jax_port}"

    agent = WorkerAgent(coordinator_addr, advertise, name=MH_TAG + name,
                        n_chips=n_chips if n_chips is not None else 1,
                        heartbeat_interval_ms=heartbeat_interval_ms)
    agent.start()
    try:
        deadline = time.time() + timeout_s
        stable_view = None  # (my_id, tuple of ranked worker ids)
        stable_since = 0.0
        stable_polls = 0  # consecutive polls the current view has held
        extended = False  # one-time deadline extension for a fresh view
        # Commit to a rank assignment only after the same view has held for
        # a full stability window (a couple of lease heartbeats). A host
        # whose lease lapses mid-wait re-registers under a new worker id;
        # without the window, peers that already committed and this host
        # would disagree on the rank order / rank-0 endpoint and deadlock
        # in jax.distributed.initialize. The window doesn't close the race
        # completely (a lapse *after* commit can still diverge views), so
        # ``initialize`` additionally gets a bounded timeout below — a
        # divergent world fails fast instead of hanging.
        stability_s = max(2.0 * heartbeat_interval_ms / 1000.0, 0.3)
        while True:
            # Re-read each round: the agent transparently re-registers with
            # a fresh worker id if its lease ever lapses mid-wait.
            my_id = agent.worker_id
            _, peers = agent.snapshot()
            hosts = [p for p in peers if p.name.startswith(MH_TAG)]
            if len(hosts) >= world_size:
                ranked = sorted(hosts, key=lambda p: p.worker_id)[:world_size]
                view = (my_id, tuple(p.worker_id for p in ranked))
                if any(p.worker_id == my_id for p in ranked):
                    now = time.time()
                    if view != stable_view:
                        stable_view, stable_since = view, now
                        stable_polls = 1
                    else:
                        stable_polls += 1
                        if now - stable_since >= stability_s:
                            break
                else:
                    stable_view, stable_polls = None, 0
            else:
                stable_view, stable_polls = None, 0
            if time.time() > deadline:
                if stable_view is not None and stable_polls >= 2:
                    # A complete view exists at the deadline AND held for at
                    # least two consecutive polls — commit to it rather than
                    # failing a world that did form (the full stability
                    # window is best-effort, not part of the formation
                    # budget). A single-poll view is exactly the churn case
                    # the window exists for, so it never short-circuits.
                    break
                if stable_view is not None and not extended:
                    # Fresh view right at the deadline: grant one stability
                    # window to confirm it instead of committing blind.
                    deadline += stability_s
                    extended = True
                else:
                    raise TimeoutError(
                        f"world of {world_size} did not form within "
                        f"{timeout_s}s (have {len(hosts)} bootstrap hosts)")
            time.sleep(0.05)

        rank = next(i for i, p in enumerate(ranked) if p.worker_id == my_id)
        jax_coordinator = ranked[0].addr
        hold.close()
        init = _initialize if _initialize is not None else initialize
        if _initialize is not None:
            # Test hooks may not take the timeout keyword.
            try:
                init(jax_coordinator, world_size, rank,
                     timeout_s=max(deadline - time.time(), 30.0))
            except TypeError:
                init(jax_coordinator, world_size, rank)
        else:
            init(jax_coordinator, world_size, rank,
                 timeout_s=max(deadline - time.time(), 30.0))
        return World(rank=rank, num_processes=world_size,
                     jax_coordinator=jax_coordinator, worker_id=my_id,
                     agent=agent)
    except BaseException:
        hold.close()
        agent.stop(deregister=True)
        raise
