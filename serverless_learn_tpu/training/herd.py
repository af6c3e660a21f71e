"""`slt herd`: a vmapped many-client DiLoCo harness on virtual time.

ROADMAP item "thousand-worker heterogeneous training scenarios via
vmapped clients" (DrJAX, arXiv:2403.07128). The chaos simulator
(``chaos/sim.py``) runs the REAL gossip membership protocol at hundreds
of nodes but modeled training as a scalar progress counter — none of the
straggler/churn/quorum claims had ever been validated with real model
updates in the loop. This module closes that gap:

* **N real clients, one process** — every simulated DiLoCo worker holds
  real (tiny-model) parameters and runs real inner SGD steps. All N
  workers live in ONE stacked pytree with a leading client axis and the
  whole inner phase is a single ``jax.vmap``-of-``lax.scan`` jit — the
  DrJAX trick that makes 256–1000 clients cost a few milliseconds per
  round on CPU instead of N processes.
* **non-IID shards** — worker ``i`` draws inputs from a shard-shifted
  distribution (``x ~ N(shift_i, 1)``, shift scale ``shard_skew``) while
  the label function (a fixed random projection) is SHARED, so the global
  task is learnable but per-worker gradients are genuinely heterogeneous
  (covariate + label skew).
* **speed skew + churn on the event heap** — compute is uniform inside
  the vmap; heterogeneity is temporal: worker ``i``'s delta *arrives* at
  ``round_start + inner_steps * step_time_i`` on the simulator's event
  heap, where ``step_time_i`` is seeded-lognormal. Kills, restarts,
  partitions and pauses come from the existing FaultPlan DSL and act on
  the same hosts that run the REAL SWIM gossip nodes — membership
  agreement is asserted with training in the loop.
* **participation policy** — the leader (min live id, exactly as
  ``diloco_dcn``) closes the round once ``quorum_fraction`` of its OWN
  gossip view has delivered, else at ``round_timeout_s``. Late deltas
  are dropped or staleness-discounted per ``late_policy`` — the same
  policy surface ``LocalSGDConfig`` exposes for real islands.
* **delta quarantine** — per-worker delta stats come from
  ``telemetry/numerics.tree_stats`` vmapped over the client axis:
  non-finite deltas are ALWAYS quarantined, norm outliers
  (median + ``outlier_factor`` × MAD over the round's finite deltas)
  are quarantined too, each emitting a ``diloco.delta_quarantined``
  alert event that ``slt doctor`` names per worker. A poisoned worker
  can therefore never fold NaNs into the anchor.

Everything is seeded and runs on virtual time: two runs with the same
(spec, plan, seed) produce byte-identical reports, which is what turns
"256 workers, kill 20% mid-round, quorum 0.8" into a cheap CI assertion
instead of a cluster rental.
"""

from __future__ import annotations

import json
import math
from dataclasses import dataclass, field, replace
from typing import Dict, List, Optional, Set, Tuple

import numpy as np

from serverless_learn_tpu.chaos.plan import FaultPlan
from serverless_learn_tpu.chaos.sim import SIM_EPOCH, ChaosSim
from serverless_learn_tpu.control.gossip import GossipConfig

# How often an arrival blocked by a partition re-checks reachability.
_RETRY_S = 0.25


@dataclass(frozen=True)
class HerdSpec:
    """One herd scenario. Compute-shaping fields (model/optimizer/sizes)
    key the jit cache; schedule fields (quorum, timeouts, chaos knobs)
    are plain host logic and never recompile."""

    n_workers: int = 256
    rounds: int = 5
    inner_steps: int = 4
    batch_size: int = 8
    features: Tuple[int, ...] = (32,)
    num_classes: int = 10
    input_dim: int = 64
    inner_lr: float = 0.05
    inner_momentum: float = 0.9
    outer_lr: float = 1.0
    outer_momentum: float = 0.0
    # heterogeneity
    shard_skew: float = 1.0      # non-IID shard shift scale (0 = IID)
    speed_skew: float = 0.35     # lognormal sigma of per-worker step time
    base_step_s: float = 0.05    # median virtual seconds per inner step
    # participation policy (mirrors LocalSGDConfig round-19 fields)
    quorum_fraction: float = 1.0
    round_timeout_s: float = 2.0
    late_policy: str = "drop"    # "drop" | "discount"
    staleness_discount: float = 0.25
    # delta quarantine gate
    outlier_factor: float = 12.0
    gate_min_peers: int = 4
    # chaos knobs: scale worker poison_worker's round-poison_round delta
    # by NaN (the quarantine acceptance drill) or by scale_factor (the
    # norm-outlier drill). -1 = off.
    poison_worker: int = -1
    poison_round: int = -1
    scale_worker: int = -1
    scale_round: int = -1
    scale_factor: float = 1000.0
    # Wire codec (round 20, training/wire_codec.py): per-worker deltas
    # and the anchor broadcast ride a simulated blockwise-quantized wire
    # — the quantizer runs UNDER the client vmap, and error_feedback
    # carries each worker's residual into its next round's delta, the
    # property the int8-vs-f32 A/B (run_wire_ab) exists to prove.
    wire_dtype: str = "float32"  # float32 | int8 | fp8
    wire_block: int = 128
    error_feedback: bool = True
    bootstrap_s: float = 2.0     # gossip settle time before round 0
    # Start from an ESTABLISHED membership (every node knows every
    # node, the state of a fleet that has been up for a while) instead
    # of a cold-boot join storm. At 256+ nodes, cold-boot dissemination
    # alone takes ~130 protocol periods — far past the sim's post-fault
    # re-convergence bound — and it is not what herd scenarios test:
    # the interesting churn is kills/partitions DURING training, which
    # SWIM still detects and disseminates live. False = cold boot.
    established: bool = True

    def validate(self):
        if self.n_workers < 2:
            raise ValueError("herd needs >= 2 workers")
        if not 0.0 < self.quorum_fraction <= 1.0:
            raise ValueError("quorum_fraction must be in (0, 1]")
        if self.late_policy not in ("drop", "discount"):
            raise ValueError("late_policy must be 'drop' or 'discount'")
        if self.rounds < 1 or self.inner_steps < 1:
            raise ValueError("rounds and inner_steps must be >= 1")
        from serverless_learn_tpu.training import wire_codec

        wire_codec.normalize_dtype(self.wire_dtype)  # ValueError if bad
        if self.wire_block < 1:
            raise ValueError("wire_block must be >= 1")


# -- compiled kernels ---------------------------------------------------------
#
# Cached by compute shape only (not seed / schedule): a determinism pair
# or a quorum-A/B comparison reuses one compile. Seed-dependent values
# (base PRNG key, shard shifts, label projection) enter as ARGUMENTS.

_KERNEL_CACHE: Dict[tuple, dict] = {}


def _kernel_key(spec: HerdSpec) -> tuple:
    return (spec.n_workers, spec.inner_steps, spec.batch_size,
            tuple(spec.features), spec.num_classes, spec.input_dim,
            spec.inner_lr, spec.inner_momentum,
            spec.outer_lr, spec.outer_momentum,
            spec.wire_dtype, spec.wire_block, spec.error_feedback)


def _kernels(spec: HerdSpec) -> dict:
    key = _kernel_key(spec)
    hit = _KERNEL_CACHE.get(key)
    if hit is not None:
        return hit

    import jax
    import jax.numpy as jnp
    import optax

    from serverless_learn_tpu.models.registry import get_model
    from serverless_learn_tpu.telemetry.numerics import (global_norm,
                                                         tree_stats)
    from serverless_learn_tpu.training import wire_codec

    n, steps, batch = spec.n_workers, spec.inner_steps, spec.batch_size
    dim, classes = spec.input_dim, spec.num_classes
    wire = wire_codec.require_supported(spec.wire_dtype)
    quantized = wire != "float32"
    ef = spec.error_feedback

    def fq(tree):
        return wire_codec.tree_fake_quantize(tree, wire, spec.wire_block)
    bundle = get_model("mlp_mnist", features=tuple(spec.features),
                       num_classes=classes, image_shape=(dim, 1, 1))
    tx = optax.sgd(spec.inner_lr, momentum=spec.inner_momentum)
    olr, omu = spec.outer_lr, spec.outer_momentum
    tmap = jax.tree_util.tree_map

    def _bcast(mask, leaf):
        return mask.reshape(mask.shape + (1,) * (leaf.ndim - 1))

    def init(seed: int):
        kp = jax.random.PRNGKey(seed)
        params = bundle.module.init(kp, jnp.zeros((batch, dim)))["params"]
        params = tmap(lambda p: p.astype(jnp.float32), params)
        trace = tmap(jnp.zeros_like, params)
        opt = jax.vmap(tx.init)(
            tmap(lambda p: jnp.broadcast_to(p[None], (n,) + p.shape),
                 params))
        proj = jax.random.normal(jax.random.fold_in(kp, 7919),
                                 (dim, classes), jnp.float32)
        shifts = spec.shard_skew * jax.random.normal(
            jax.random.fold_in(kp, 104729), (n, dim), jnp.float32)
        return params, trace, opt, proj, shifts, kp

    @jax.jit
    def inner(anchor, opt_states, shifts, proj, base_key, delta_scale,
              alive, reset, round_idx, residual):
        """One round's inner phase for ALL workers: vmap over clients of
        a lax.scan over inner steps. Returns the stacked WIRE deltas —
        what the leader would dequantize, with the quantizer itself run
        under the client vmap — plus the per-worker gate stats (computed
        on the dequantized values, so a bad quantization block trips the
        same quarantine a sick worker would) and the updated per-worker
        error-feedback residual."""

        def per_worker(wid, opt, shift, rst):
            opt = tmap(lambda o: jnp.where(rst, jnp.zeros_like(o), o), opt)

            def body(carry, s):
                params, opt = carry
                kk = jax.random.fold_in(
                    jax.random.fold_in(
                        jax.random.fold_in(base_key, wid), round_idx), s)
                x = jax.random.normal(kk, (batch, dim), jnp.float32) + shift
                y = jnp.argmax(x @ proj, axis=-1).astype(jnp.int32)
                (loss, _), grads = jax.value_and_grad(
                    bundle.loss_fn, has_aux=True)(
                        params, {"image": x, "label": y})
                updates, opt = tx.update(grads, opt, params)
                params = tmap(lambda p, u: (p + u).astype(p.dtype),
                              params, updates)
                return (params, opt), loss

            (params, opt), losses = jax.lax.scan(
                body, (anchor, opt), jnp.arange(steps))
            delta = tmap(lambda a, p: (a - p).astype(jnp.float32),
                         anchor, params)
            return delta, opt, losses.mean()

        deltas, new_opts, mean_loss = jax.vmap(per_worker)(
            jnp.arange(n), opt_states, shifts, reset)
        # Chaos injection AFTER the real compute, BEFORE the wire: a NaN
        # (or huge) scale poisons the delta exactly as a sick worker
        # would, and the gate must catch it downstream.
        deltas = tmap(lambda l: l * _bcast(delta_scale, l), deltas)
        # Dead workers neither trained nor keep this round's opt state.
        new_opts = tmap(lambda nw, old: jnp.where(_bcast(alive, nw),
                                                  nw, old),
                        new_opts, opt_states)
        if quantized:
            # A restarted worker lost its residual carry with the rest
            # of its inner state.
            residual = tmap(lambda r: jnp.where(_bcast(reset, r), 0.0, r),
                            residual)
            send = (tmap(jnp.add, deltas, residual) if ef else deltas)
            wired = jax.vmap(fq)(send)
        else:
            send, wired = deltas, deltas
        stats = jax.vmap(lambda d: tree_stats(d, depth=1))(wired)
        nonfinite = sum(st["nonfinite"] for st in stats.values())
        l2 = jax.vmap(global_norm)(wired)
        if quantized and ef:
            # Absorb this round's quantization error — but never a NaN
            # (a poisoned delta must not poison every later round), and
            # never for a dead worker (it sent nothing).
            ok = alive & (nonfinite == 0)
            residual = tmap(lambda s, w, r: jnp.where(_bcast(ok, s),
                                                      s - w, r),
                            send, wired, residual)
        return wired, new_opts, mean_loss, l2, nonfinite, residual

    @jax.jit
    def outer(anchor, trace, deltas, weights):
        """Weighted-mean delta -> Nesterov outer step (the exact
        formulation diloco_dcn._nesterov_step uses)."""
        wsum = jnp.maximum(weights.sum(), 1e-9)
        # A quarantined NaN delta carries weight 0, but 0 * NaN = NaN —
        # non-finite entries must be zeroed BEFORE the weighted sum or
        # the quarantine is cosmetic.
        grad = tmap(lambda d: jnp.tensordot(
            weights, jnp.where(jnp.isfinite(d), d, 0.0), axes=1) / wsum,
            deltas)
        new_trace = tmap(lambda g, t: g + omu * t, grad, trace)
        new_anchor = tmap(
            lambda a, g, t: (a - olr * (g + omu * t)).astype(a.dtype),
            anchor, grad, new_trace)
        drift = global_norm(tmap(lambda x, y: x - y, new_anchor, anchor))
        return new_anchor, new_trace, drift

    @jax.jit
    def late_apply(anchor, deltas, idx, weight):
        """Stale straggler delta applied as plain discounted SGD on the
        current anchor (momentum deliberately untouched — a stale
        gradient must not steer the trace)."""
        d = tmap(lambda l: l[idx], deltas)
        return tmap(lambda a, x: (a - weight * x).astype(a.dtype),
                    anchor, d)

    @jax.jit
    def wire_anchor(anchor, resid):
        """The leader's anchor broadcast through the same wire: publish
        the quantized anchor (every worker — the leader included — adopts
        the DEQUANTIZED tree, so all islands hold bit-identical anchors),
        with a leader-side error-feedback carry."""
        if not quantized:
            return anchor, resid
        send = tmap(jnp.add, anchor, resid) if ef else anchor
        wired = fq(send)
        new_resid = tmap(jnp.subtract, send, wired) if ef else resid
        return wired, new_resid

    @jax.jit
    def eval_loss(anchor, shifts, proj, base_key):
        """Anchor loss on a fixed mixture batch drawn from EVERY shard —
        the global objective under non-IID data."""
        kk = jax.random.fold_in(base_key, 15485863)
        x = jax.random.normal(kk, (n, 2, dim), jnp.float32) \
            + shifts[:, None, :]
        x = x.reshape(2 * n, dim)
        y = jnp.argmax(x @ proj, axis=-1).astype(jnp.int32)
        loss, _ = bundle.loss_fn(anchor, {"image": x, "label": y})
        return loss

    kit = {"init": init, "inner": inner, "outer": outer,
           "late_apply": late_apply, "eval_loss": eval_loss,
           "wire_anchor": wire_anchor}
    _KERNEL_CACHE[key] = kit
    return kit


# -- the harness --------------------------------------------------------------


@dataclass
class _Round:
    idx: int
    t0: float
    leader: str
    view: Set[str]
    need: int
    closed: bool = False
    delivered: Dict[int, float] = field(default_factory=dict)
    accepted: List[int] = field(default_factory=list)
    quarantined: Dict[int, str] = field(default_factory=dict)
    deltas: object = None          # device [N, ...] tree, freed lazily
    l2: Optional[np.ndarray] = None
    nonfinite: Optional[np.ndarray] = None
    losses: Optional[np.ndarray] = None


class HerdSim(ChaosSim):
    """ChaosSim with the scalar training model replaced by the real
    vmapped DiLoCo herd. Membership, faults, telemetry and invariants
    are inherited — the herd only swaps what "training" means."""

    def __init__(self, spec: HerdSpec, seed: int = 0,
                 plan: Optional[FaultPlan] = None,
                 gossip: Optional[GossipConfig] = None,
                 events_log: Optional[str] = None):
        spec.validate()
        # ping_timeout = period/2 (not the CLI's 0.3x): the simulator
        # ticks every timeout/2, so a lazier direct-ack wait cuts the
        # dominant per-node event rate ~40% at herd scale; detection
        # stays bounded by the same suspicion math.
        super().__init__(
            spec.n_workers, seed=seed, plan=plan,
            gossip=gossip or GossipConfig(protocol_period_s=0.5,
                                          ping_timeout_s=0.25),
            events_log=events_log, round_s=spec.bootstrap_s,
            inner_steps=spec.inner_steps,
            quorum_fraction=spec.quorum_fraction)
        self.spec = spec
        if spec.established:
            from serverless_learn_tpu.control.gossip import ALIVE, Member

            for nid, host in self.hosts.items():
                for other in self.hosts:
                    if other == nid:
                        continue
                    host.node._members[other] = Member(
                        node_id=other, addr=f"sim://{other}",
                        incarnation=0, state=ALIVE, since=0.0,
                        meta={"worker_id": self._widx(other),
                              "n_chips": 1})
        self.k = _kernels(spec)
        (self.anchor, self.trace, self.opt_states, self._proj,
         self._shifts, self._base_key) = self.k["init"](seed)
        # Wire codec state + byte ledger (round 20): per-worker error-
        # feedback residuals ride the same stacked layout as the opt
        # states; the byte ledger prices each round the way the real
        # protocol pays it — one delta PUT per delivery, one anchor PUT
        # plus one GET per live worker.
        import jax
        import jax.numpy as jnp

        from serverless_learn_tpu.training import wire_codec

        self._wire = wire_codec.normalize_dtype(spec.wire_dtype)
        self.residual = jax.tree_util.tree_map(
            lambda p: jnp.zeros((self.n,) + p.shape, jnp.float32),
            self.anchor)
        self.anchor_resid = jax.tree_util.tree_map(
            lambda p: jnp.zeros_like(p, jnp.float32), self.anchor)
        self._delta_logical = wire_codec.logical_nbytes(self.anchor)
        self._delta_wire = wire_codec.wire_nbytes(
            self.anchor, self._wire, spec.wire_block)
        # the anchor publish carries params + outer momentum trace
        self._anchor_logical = 2 * self._delta_logical
        self._anchor_wire = 2 * self._delta_wire
        self.wire_logical_bytes = 0
        self.wire_bytes = 0
        # Per-worker virtual step time: seeded lognormal speed skew.
        rng = np.random.default_rng([seed, 0x4E4D])
        self.step_times = spec.base_step_s * np.exp(
            spec.speed_skew * rng.standard_normal(spec.n_workers))
        self.round_idx = 0
        self._cur: Optional[_Round] = None
        self._prev: Optional[_Round] = None
        self._needs_reset: Set[int] = set()
        self._quarantine_firing: Set[int] = set()
        self._quarantine_log: Dict[int, dict] = {}
        self.participation: List[float] = []
        self.round_losses: List[float] = []
        self.round_waits: List[float] = []
        self.late_dropped = 0
        self.late_discounted = 0
        self.skipped_rounds = 0
        self._delivered_ever: Set[int] = set()
        self._init_eval = float(self.k["eval_loss"](
            self.anchor, self._shifts, self._proj, self._base_key))

    # -- helpers -----------------------------------------------------------

    @staticmethod
    def _widx(nid: str) -> int:
        return int(nid.split("-")[1])

    def _live_unpaused(self) -> Set[str]:
        return {nid for nid, h in self.hosts.items()
                if h.alive and h.paused_until <= self.now}

    def _leader_view(self) -> Tuple[Optional[str], Set[str]]:
        """Leader = min live id (diloco_dcn's rule); its quorum
        denominator is its OWN gossip view restricted to truly-live —
        the real membership protocol in the loop."""
        live = self._live_unpaused()
        if not live:
            return None, set()
        leader = min(live)
        view = set(self.hosts[leader].node.alive_ids()) & live
        view.add(leader)
        return leader, view

    def _join_initial(self, nid: str):
        if self.spec.established:
            return  # no join storm — membership is pre-seeded
        super()._join_initial(nid)

    def _restart(self, nid: str):
        super()._restart(nid)
        # A restarted worker lost its inner optimizer state; it adopts
        # the current anchor at its next round (params do automatically
        # — they start from the anchor every round).
        self._needs_reset.add(self._widx(nid))

    # -- the training model (replaces ChaosSim's scalar counter) -----------

    def _training_round(self):  # first scheduled by ChaosSim.run
        self._start_round()

    def _start_round(self):
        if self.round_idx >= self.spec.rounds:
            return
        leader, view = self._leader_view()
        if leader is None:
            self._push(self.now + self.spec.round_timeout_s,
                       self._start_round)
            return
        spec = self.spec
        r = self.round_idx
        alive = np.array([self.hosts[self._nid(i)].alive
                          for i in range(self.n)], np.bool_)
        reset = np.array([i in self._needs_reset and alive[i]
                          for i in range(self.n)], np.bool_)
        self._needs_reset -= {i for i in range(self.n) if reset[i]}
        scale = np.ones(self.n, np.float32)
        if spec.scale_worker >= 0 and r == spec.scale_round:
            scale[spec.scale_worker] = spec.scale_factor
        if spec.poison_worker >= 0 and r == spec.poison_round:
            scale[spec.poison_worker] = np.nan
        (deltas, self.opt_states, losses, l2, nonfinite,
         self.residual) = self.k["inner"](
            self.anchor, self.opt_states, self._shifts, self._proj,
            self._base_key, scale, alive, reset, r, self.residual)
        import jax

        losses, l2, nonfinite = (np.asarray(jax.device_get(losses)),
                                 np.asarray(jax.device_get(l2)),
                                 np.asarray(jax.device_get(nonfinite)))
        cur = _Round(idx=r, t0=self.now, leader=leader, view=view,
                     need=max(1, math.ceil(spec.quorum_fraction
                                           * len(view) - 1e-9)),
                     deltas=deltas, l2=l2, nonfinite=nonfinite,
                     losses=losses)
        self._cur = cur
        cohort = sorted(nid for nid, h in self.hosts.items() if h.alive)
        for nid in cohort:
            i = self._widx(nid)
            arrival = self.now + spec.inner_steps * float(
                self.step_times[i])
            self._push(arrival, self._delta_arrival, r, i)
        self._push(self.now + spec.round_timeout_s,
                   self._round_timeout, r)

    def _delta_arrival(self, r: int, i: int):
        cur = self._cur
        nid = self._nid(i)
        host = self.hosts[nid]
        if cur is None or cur.idx != r or cur.closed:
            self._late_delta(r, i)
            return
        if not host.alive:
            return  # crashed before posting — the churn case
        if host.paused_until > self.now:
            self._push(host.paused_until, self._delta_arrival, r, i)
            return
        leader, _ = self._leader_view()
        if leader is None or not self._reachable(nid, leader):
            # Partitioned away from the leader: retry until the round
            # closes (the timeout bounds these events).
            self._push(self.now + _RETRY_S, self._delta_arrival, r, i)
            return
        if i not in cur.delivered:
            cur.delivered[i] = round(self.now - cur.t0, 6)
            self._delivered_ever.add(i)
        if len(cur.delivered) >= cur.need:
            self._close_round(cur)

    def _round_timeout(self, r: int):
        cur = self._cur
        if cur is None or cur.idx != r or cur.closed:
            return
        if cur.delivered:
            self._close_round(cur)
            return
        # Nothing arrived at all (e.g. total partition): safe-pause the
        # round — anchor unchanged, no committed progress.
        cur.closed = True
        self.paused_rounds += 1
        self.skipped_rounds += 1
        self._emit({"event": "training_safe_pause", "leader": cur.leader,
                    "participants": 0, "needed": cur.need,
                    "round": cur.idx,
                    "t_unix_s": round(SIM_EPOCH + self.now, 3)})
        self._advance(cur)

    def _quarantine(self, cur: _Round, i: int, reason: str, value: float,
                    threshold: float):
        cur.quarantined[i] = reason
        log = self._quarantine_log.setdefault(
            i, {"rounds": [], "reason": reason})
        log["rounds"].append(cur.idx)
        self._quarantine_firing.add(i)
        self._alert(
            ("delta_quarantine", i), firing=True, severity="critical",
            alert="diloco.delta_quarantined", detector="diloco",
            node=self._nid(i), labels={"worker": str(i)},
            message=f"round {cur.idx}: delta from worker {i} quarantined "
                    f"({reason}) — excluded from the outer average",
            value=round(float(value), 6), threshold=round(threshold, 6))

    def _close_round(self, cur: _Round):
        cur.closed = True
        spec = self.spec
        # ---- delta quarantine gate ----------------------------------
        finite: List[int] = []
        for i in sorted(cur.delivered):
            if int(cur.nonfinite[i]) > 0:
                self._quarantine(cur, i, "nonfinite",
                                 float(cur.nonfinite[i]), 0.0)
            else:
                finite.append(i)
        if len(finite) >= spec.gate_min_peers:
            norms = np.array([cur.l2[i] for i in finite], np.float64)
            med = float(np.median(norms))
            mad = float(np.median(np.abs(norms - med)))
            # Spread floor 10% of the median: non-IID shards produce
            # legitimately unequal delta norms, and a tight MAD must
            # not quarantine a merely-heterogeneous worker.
            cut = med + spec.outlier_factor * max(mad, 0.1 * abs(med),
                                                  1e-9)
            kept = []
            for i, nrm in zip(finite, norms):
                if nrm > cut:
                    self._quarantine(cur, i, "norm_outlier", float(nrm),
                                     cut)
                else:
                    kept.append(i)
            finite = kept
        cur.accepted = finite
        if (spec.poison_worker >= 0 and cur.idx == spec.poison_round
                and spec.poison_worker in cur.delivered
                and spec.poison_worker not in cur.quarantined):
            self.violations.append(
                f"poisoned worker {spec.poison_worker} delivered in round "
                f"{cur.idx} but was never quarantined")
        for i in finite:
            if i in self._quarantine_firing:
                self._quarantine_firing.discard(i)
                self._alert(("delta_quarantine", i), firing=False,
                            severity="critical",
                            alert="diloco.delta_quarantined",
                            node=self._nid(i),
                            message=f"worker {i} posted a clean delta in "
                                    f"round {cur.idx}; readmitted")
        # ---- outer step ---------------------------------------------
        import jax
        import jax.numpy as jnp

        if finite:
            w = np.zeros(self.n, np.float32)
            w[finite] = 1.0
            self.anchor, self.trace, drift = self.k["outer"](
                self.anchor, self.trace, cur.deltas, jnp.asarray(w))
            drift = float(jax.device_get(drift))
            # The broadcast rides the same wire as the deltas: every
            # worker (the next leader included) adopts the DEQUANTIZED
            # anchor, with a leader-side error-feedback carry. A skipped
            # round republishes the previous round's bytes unchanged —
            # no re-quantization (matching diloco_dcn's packed-blob
            # reuse).
            self.anchor, self.anchor_resid = self.k["wire_anchor"](
                self.anchor, self.anchor_resid)
            self.committed_step += spec.inner_steps
            self.completed_rounds += 1
        else:
            drift = 0.0
            self.paused_rounds += 1
            self.skipped_rounds += 1
        # Byte ledger: one delta PUT per delivery, one anchor PUT plus
        # one anchor GET per live worker — the real protocol's shape.
        r_logical = (len(cur.delivered) * self._delta_logical
                     + (1 + len(cur.view)) * self._anchor_logical)
        r_wire = (len(cur.delivered) * self._delta_wire
                  + (1 + len(cur.view)) * self._anchor_wire)
        self.wire_logical_bytes += r_logical
        self.wire_bytes += r_wire
        self._emit({"event": "dcn_wire", "consumer": "diloco",
                    "direction": "tx", "kind": "herd_round",
                    "wire_dtype": self._wire,
                    "logical_bytes": int(r_logical),
                    "wire_bytes": int(r_wire), "round": cur.idx,
                    "t_unix_s": round(SIM_EPOCH + self.now, 3)})
        part = round(len(finite) / max(len(cur.view), 1), 4)
        self.participation.append(part)
        self.round_waits.append(round(self.now - cur.t0, 4))
        loss = float(np.mean([cur.losses[i] for i in sorted(cur.delivered)]
                             )) if cur.delivered else float("nan")
        self.round_losses.append(round(loss, 6))
        rec = {"event": "diloco_round", "run": "herd", "round": cur.idx,
               "leader": self._widx(cur.leader),
               "posted": sorted(cur.delivered),
               "live": sorted(self._widx(nid) for nid in cur.view),
               "arrivals_s": {str(i): cur.delivered[i]
                              for i in sorted(cur.delivered)},
               "participation": part,
               "quarantined": sorted(cur.quarantined),
               "delta_norms": {str(i): round(float(cur.l2[i]), 6)
                               for i in cur.accepted},
               "anchor_drift": round(drift, 6),
               "waited_s": round(self.now - cur.t0, 4),
               "t_unix_s": round(SIM_EPOCH + self.now, 3)}
        self._emit(rec)
        self._advance(cur)

    def _advance(self, cur: _Round):
        self._step_history.append((self.now, self.committed_step))
        if self._prev is not None:
            self._prev.deltas = None  # free the stale round's device tree
        self._prev = cur
        self.round_idx += 1
        self._start_round()

    def _late_delta(self, r: int, i: int):
        """A delta arriving after its round closed — the straggler path
        the participation policy exists for."""
        host = self.hosts[self._nid(i)]
        if not host.alive:
            return
        prev = self._prev
        record = {"event": "diloco_late_delta", "worker": i, "round": r,
                  "t_unix_s": round(SIM_EPOCH + self.now, 3)}
        if (self.spec.late_policy == "discount" and prev is not None
                and prev.idx == r and prev.deltas is not None
                and int(prev.nonfinite[i]) == 0):
            rounds_late = max(1, self.round_idx - r)
            weight = (self.spec.outer_lr
                      * self.spec.staleness_discount ** rounds_late)
            self.anchor = self.k["late_apply"](
                self.anchor, prev.deltas, i, weight)
            self.late_discounted += 1
            record["action"] = "discounted"
            record["weight"] = round(weight, 6)
        else:
            self.late_dropped += 1
            record["action"] = "dropped"
        self._emit(record)

    # -- run/report --------------------------------------------------------

    def run(self, duration_s: Optional[float] = None) -> dict:
        if duration_s is None:
            bound_s = (self.convergence_bound_periods()
                       * self.cfg.protocol_period_s)
            duration_s = (max(self.plan.end_time(),
                              self.round_s + self.spec.rounds
                              * self.spec.round_timeout_s)
                          + 2.0 * bound_s)
        return super().run(duration_s)

    def _report(self, converged_at, duration) -> dict:
        from serverless_learn_tpu.telemetry.numerics import tree_stats

        anchor_bad = int(sum(
            int(np.asarray(st["nonfinite"]))
            for st in tree_stats(self.anchor, depth=1).values()))
        if anchor_bad:
            self.violations.append(
                f"anchor contains {anchor_bad} non-finite value(s) — "
                f"a poisoned delta reached the outer step")
        spec = self.spec
        rep = super()._report(converged_at, duration)
        if not self.plan.faults:
            # The base convergence invariant measures RE-convergence
            # after the last fault; with no faults it degenerates to
            # "cold-boot dissemination finished", which at herd scale
            # (256+ simultaneous joins saturating the piggyback budget)
            # legitimately exceeds the post-fault O(log N) bound. Report
            # it, don't fail on it — quorum reads the leader's live
            # view, not global agreement.
            rep["violations"] = [v for v in rep["violations"]
                                 if "converge" not in v]
            rep["ok"] = not rep["violations"]
            rep["converged"] = True
        if self.round_idx >= spec.rounds:
            # The herd stops training when its schedule completes; the
            # base "no progress after the final fault" invariant only
            # applies while rounds remain.
            rep["violations"] = [v for v in rep["violations"]
                                 if "no progress after the final" not in v]
            rep["ok"] = not rep["violations"]
        final_eval = float(self.k["eval_loss"](
            self.anchor, self._shifts, self._proj, self._base_key))
        rep["herd"] = {
            "workers": self.n,
            "rounds_target": spec.rounds,
            "rounds_completed": self.completed_rounds,
            "rounds_skipped": self.skipped_rounds,
            "committed_step": self.committed_step,
            "quorum_fraction": spec.quorum_fraction,
            "participation": list(self.participation),
            "mean_participation": (round(float(np.mean(
                self.participation)), 4) if self.participation else None),
            "workers_delivered_ever": len(self._delivered_ever),
            "quarantined": {str(i): dict(v) for i, v in
                            sorted(self._quarantine_log.items())},
            "late_deltas": {"dropped": self.late_dropped,
                            "discounted": self.late_discounted},
            "round_losses": list(self.round_losses),
            "round_waits_s": list(self.round_waits),
            "init_eval_loss": round(self._init_eval, 6),
            "final_eval_loss": round(final_eval, 6),
            "anchor_finite": anchor_bad == 0,
            "wire": {
                "dtype": self._wire,
                "block": spec.wire_block,
                "error_feedback": bool(spec.error_feedback),
                "logical_bytes": int(self.wire_logical_bytes),
                "wire_bytes": int(self.wire_bytes),
                "compression_ratio": (
                    round(self.wire_logical_bytes / self.wire_bytes, 4)
                    if self.wire_bytes else None),
                "bytes_per_round": (
                    int(self.wire_bytes / max(len(self.participation), 1))
                    if self.participation else 0),
            },
        }
        return rep


def smoke_plan(spec: HerdSpec, kill_frac: float = 0.2) -> FaultPlan:
    """The CI smoke schedule: kill ``kill_frac`` of the herd mid-round
    (while deltas are in flight) and pause one straggler for a round."""
    mid = spec.bootstrap_s + 0.6 * spec.inner_steps * spec.base_step_s
    return FaultPlan.from_obj({"faults": [
        {"at": round(mid, 3), "op": "kill", "frac": kill_frac},
        {"at": round(mid + spec.round_timeout_s, 3), "op": "pause",
         "count": 1, "for": round(spec.round_timeout_s, 3)},
    ]})


def run_smoke(workers: int = 48, seed: int = 0,
              events_log: Optional[str] = None) -> dict:
    """Self-contained proof for `slt chaos herd --smoke`: small N, short
    virtual duration, a mid-round kill of 20% of the herd, one poisoned
    worker. Asserts (on top of the harness's own invariants) that two
    same-seed runs report byte-identically and that the poisoned worker
    was quarantined. Doctor attribution is asserted by the CLI."""
    spec = HerdSpec(n_workers=workers, rounds=3, inner_steps=2,
                    batch_size=4, features=(16,),
                    quorum_fraction=0.8, round_timeout_s=1.5,
                    poison_worker=workers - 3, poison_round=1)
    plan = smoke_plan(spec)

    def one(log):
        rep = HerdSim(spec, seed=seed, plan=plan, events_log=log).run()
        rep.pop("wall_time_s", None)
        return rep

    rep = one(events_log)
    rep2 = one(None)
    rep["deterministic"] = (json.dumps(rep, sort_keys=True)
                            == json.dumps(rep2, sort_keys=True))
    if not rep["deterministic"]:
        rep["ok"] = False
        rep["violations"].append("same-seed reports differ")
    if str(spec.poison_worker) not in rep["herd"]["quarantined"]:
        rep["ok"] = False
        rep["violations"].append(
            f"poisoned worker {spec.poison_worker} was not quarantined")
    return rep


def parity_specs(workers: int = 256, quorum: float = 0.8
                 ) -> Tuple[HerdSpec, HerdSpec]:
    """The partial-vs-full participation A/B pair (same compute key, so
    the second run reuses the first's compiles)."""
    base = HerdSpec(n_workers=workers, rounds=5, inner_steps=2,
                    batch_size=4, features=(16,), speed_skew=0.5,
                    round_timeout_s=1.0)
    return replace(base, quorum_fraction=quorum), \
        replace(base, quorum_fraction=1.0)


def wire_parity_specs(workers: int = 256, quorum: float = 0.8,
                      wire_dtype: str = "int8"
                      ) -> Tuple[HerdSpec, HerdSpec]:
    """The quantized-vs-f32 A/B pair (round 20): same seed ⇒ same init,
    shards, speed skew and fault schedule; ONLY the wire encoding
    differs, so a final-loss gap is attributable to the codec alone."""
    base = HerdSpec(n_workers=workers, rounds=5, inner_steps=2,
                    batch_size=4, features=(16,), speed_skew=0.5,
                    round_timeout_s=1.0, quorum_fraction=quorum)
    return replace(base, wire_dtype=wire_dtype), base


def run_wire_ab(workers: int = 48, seed: int = 0,
                wire_dtype: str = "int8", kill_frac: float = 0.2,
                events_log: Optional[str] = None) -> dict:
    """Int8(/fp8)-vs-f32 loss-parity proof under churn (quorum 0.8, a
    mid-round kill of ``kill_frac`` of the herd), with a no-error-
    feedback negative control. Checks, on one seed:

    * every leg's harness invariants hold;
    * the quantized-with-feedback leg's final eval loss lands within 5%
      of the f32 leg's, on the init-loss scale (the EQuARX claim);
    * wire bytes shrink >= 3.5x;
    * the negative control: either dropping error feedback measurably
      WORSENS parity (the feedback term matters), or the feedback leg's
      gap sits below the noise floor of a one-seed reading. Either
      quantized leg's final-loss gap is a zero-mean draw, not a bias:
      over 12 seeds at 16 workers and 8 at 48 its RMS is
      ``0.0046 * init / sqrt(workers)`` for both legs (the feedback
      leg's 0.8x the control's), so which of two single draws is the
      smaller says nothing, and the floor is three of those RMS. A
      feedback leg that is both worse than the control AND above the
      floor fails: the carry would be hurting, not helping. What one
      seed's loss cannot show (a wrong-sign carry only doubles the
      noise) is pinned exactly: the carry telescopes in
      tests/test_herd.py::test_error_feedback_carry_telescopes and
      unbiases the stream in tests/test_wire_codec.py.
    """
    quant_spec, f32_spec = wire_parity_specs(workers, 0.8, wire_dtype)
    noef_spec = replace(quant_spec, error_feedback=False)
    plan = smoke_plan(f32_spec, kill_frac)

    def leg(spec, log=None):
        rep = HerdSim(spec, seed=seed, plan=plan, events_log=log).run()
        rep.pop("wall_time_s", None)
        return rep

    rf = leg(f32_spec)
    rq = leg(quant_spec, events_log)
    rn = leg(noef_spec)
    init = rf["herd"]["init_eval_loss"]
    ef_gap = abs(rq["herd"]["final_eval_loss"]
                 - rf["herd"]["final_eval_loss"])
    noef_gap = abs(rn["herd"]["final_eval_loss"]
                   - rf["herd"]["final_eval_loss"])
    ratio = (rf["herd"]["wire"]["wire_bytes"]
             / max(rq["herd"]["wire"]["wire_bytes"], 1))
    violations = []
    for name, rep in (("f32", rf), ("quant", rq), ("quant-noef", rn)):
        if not rep["ok"]:
            violations.append(f"{name} leg: {rep['violations']}")
    if not ef_gap < 0.05 * init:
        violations.append(
            f"quantized leg diverged: |{rq['herd']['final_eval_loss']} "
            f"- {rf['herd']['final_eval_loss']}| = {ef_gap:.6f} >= 5% "
            f"of init {init}")
    if ratio < 3.5:
        violations.append(
            f"wire bytes shrank only {ratio:.2f}x (< 3.5x)")
    # Three RMS of a one-seed gap at this herd size (docstring).
    noise_floor = 3 * 0.0046 * init / math.sqrt(workers)
    if ef_gap <= noef_gap + 1e-9:
        feedback_verdict = "matters" if noef_gap > noise_floor \
            else "equivalent_below_noise_floor"
    elif ef_gap <= noise_floor:
        feedback_verdict = "equivalent_below_noise_floor"
    else:
        feedback_verdict = "hurts"
        violations.append(
            f"error feedback HURT parity ({ef_gap:.6f} with vs "
            f"{noef_gap:.6f} without, noise floor {noise_floor:.6f}) — "
            f"the feedback term is broken")
    return {
        "ok": not violations, "violations": violations,
        "feedback_verdict": feedback_verdict,
        "workers": workers, "seed": seed, "wire_dtype": wire_dtype,
        "quorum_fraction": quant_spec.quorum_fraction,
        "killed_frac": kill_frac,
        "init_eval_loss": init,
        "final_eval_loss": {
            "f32": rf["herd"]["final_eval_loss"],
            "quant": rq["herd"]["final_eval_loss"],
            "quant_no_feedback": rn["herd"]["final_eval_loss"]},
        "parity_gap": {"with_feedback": round(ef_gap, 6),
                       "without_feedback": round(noef_gap, 6)},
        "bytes": {"f32": rf["herd"]["wire"]["wire_bytes"],
                  "quant": rq["herd"]["wire"]["wire_bytes"],
                  "ratio": round(ratio, 3)},
        "bytes_per_round": {
            "f32": rf["herd"]["wire"]["bytes_per_round"],
            "quant": rq["herd"]["wire"]["bytes_per_round"]},
        "mean_round_wait_s": {
            "f32": _mean_wait(rf), "quant": _mean_wait(rq)},
    }


def _mean_wait(rep: dict) -> Optional[float]:
    waits = rep.get("herd", {}).get("round_waits_s") or []
    return round(float(np.mean(waits)), 4) if waits else None
