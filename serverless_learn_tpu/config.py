"""Typed configuration layer.

Successor of the reference's config "system" — four ``#define``s in
``src/serverless_learn.h:5-12`` plus scattered per-binary constants
(``src/master.cc:43,46,60``, ``src/file_server.cc:40,46``). Changing any
interval there required recompiling; here everything is a dataclass that can
be constructed programmatically, loaded from JSON, or overridden from CLI
flags.
"""

from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from typing import Any, Optional, Tuple


@dataclass(frozen=True)
class MeshConfig:
    """Logical device-mesh shape.

    Axes follow the canonical TPU-parallelism decomposition:

    * ``dp``  — data parallelism (gradient ``psum`` over ICI; the TPU-native
      successor of the reference's gossip exchange, ``src/worker.cc:194-219``).
    * ``fsdp`` — data parallelism with parameter/optimizer sharding (ZeRO-3
      style; params are all-gathered per layer, grads reduce-scattered).
    * ``tp``  — tensor (model) parallelism over attention heads / MLP hidden.
    * ``ep``  — expert parallelism (MoE experts sharded over devices; token
      dispatch/combine become all-to-alls on ICI).
    * ``sp``  — sequence/context parallelism (ring attention over an ICI ring).
    * ``pp``  — pipeline parallelism (stage-sharded, microbatched).

    Any axis of size 1 is inert; total size must equal the device count used.
    """

    dp: int = 1
    fsdp: int = 1
    ep: int = 1
    tp: int = 1
    sp: int = 1
    pp: int = 1

    AXIS_NAMES = ("dp", "fsdp", "ep", "tp", "sp", "pp")

    @property
    def shape(self) -> Tuple[int, ...]:
        return (self.dp, self.fsdp, self.ep, self.tp, self.sp, self.pp)

    @property
    def size(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n

    def nontrivial_axes(self) -> dict:
        """{axis: size} for axes of size > 1 — the compact human/log form."""
        return {a: s for a, s in zip(self.AXIS_NAMES, self.shape) if s > 1}

    def validate(self, n_devices: int) -> None:
        if self.size != n_devices:
            raise ValueError(
                f"Mesh shape {dict(zip(self.AXIS_NAMES, self.shape))} has size "
                f"{self.size} but {n_devices} devices are available."
            )


class UnsatisfiableMeshError(ValueError):
    """A device count cannot host the configured mesh's model axes."""


def scale_mesh(base: "MeshConfig", n_devices: int) -> "MeshConfig":
    """Scale a configured mesh to an elastic world of ``n_devices`` devices.

    The elastic contract (reference ``src/master.cc:79-91`` — any worker can
    join anytime) meets model sharding here: when the world re-forms, the
    *model* axes must keep their configured sizes (tp/pp/sp/ep change the
    program's collectives and, for pp, the checkpoint layout), while the
    *data* plane stretches to absorb whatever devices the new world has:

    * ``tp``/``pp``/``sp``/``ep`` — fixed at the configured size. A world
      whose device count isn't a multiple of their product is rejected.
      pp being FIXED is also what keeps interleaved-pipeline checkpoints
      valid across re-formations: an interleaved checkpoint's layer
      EXECUTION order is a function of the stage count
      (``TransformerConfig.pipeline_stages``), so a world change that
      resized pp would strand it. Elasticity therefore never resizes pp;
      serving/sequential replay of such checkpoints goes through
      ``unstack_pipeline_params``, which undoes the pinned order, and a
      mesh whose pp disagrees with ``pipeline_stages`` is rejected at
      build time (``models/transformer.py``).
    * ``fsdp`` — the configured value is a MEMORY FLOOR (the state provably
      fits at that sharding, e.g. an 8B state needs fsdp>=4); the actual
      axis is the smallest divisor of the remaining plane that is >= the
      floor, so growth beyond the floor goes to ``dp`` first (cheaper
      collectives) but never below the floor.
    * ``dp`` — absorbs the rest.

    Raises ``UnsatisfiableMeshError`` (loudly, per VERDICT r2 item 2) when
    no such assignment exists; elastic supervisors treat that world size as
    not-formable and wait for membership to change rather than silently
    falling back to dp-only.
    """
    model = base.tp * base.pp * base.sp * base.ep
    if n_devices < 1 or n_devices % model != 0:
        raise UnsatisfiableMeshError(
            f"{n_devices} devices cannot host model axes "
            f"tp={base.tp} pp={base.pp} sp={base.sp} ep={base.ep} "
            f"(need a positive multiple of {model})")
    plane = n_devices // model
    if base.fsdp > 1:
        fsdp = next((d for d in range(base.fsdp, plane + 1)
                     if plane % d == 0), None)
        if fsdp is None:
            raise UnsatisfiableMeshError(
                f"data plane of {plane} devices cannot satisfy the "
                f"fsdp>={base.fsdp} memory floor (model axes consume "
                f"{model} of {n_devices})")
    else:
        fsdp = 1
    return MeshConfig(dp=plane // fsdp, fsdp=fsdp, ep=base.ep, tp=base.tp,
                      sp=base.sp, pp=base.pp)


@dataclass(frozen=True)
class OptimizerConfig:
    name: str = "adamw"  # adamw | adam | sgd | adafactor | lion | rmsprop
    learning_rate: float = 1e-3
    weight_decay: float = 0.0
    b1: float = 0.9
    b2: float = 0.999
    momentum: float = 0.9  # sgd / rmsprop only
    warmup_steps: int = 0
    decay_steps: int = 0  # 0 => constant after warmup
    grad_clip_norm: float = 0.0  # 0 => no clipping
    # Exempt 1-D params (biases, norm scales) from weight decay — the
    # standard transformer recipe; decaying norm scales hurts.
    decay_exclude_1d: bool = True


@dataclass(frozen=True)
class TrainConfig:
    batch_size: int = 128  # global batch size
    num_steps: int = 100
    seed: int = 0
    dtype: str = "bfloat16"  # compute dtype
    param_dtype: str = "float32"
    log_every: int = 10
    checkpoint_every: int = 0  # 0 => disabled
    remat: bool = False  # jax.checkpoint the model apply
    donate_state: bool = True
    # Gradient accumulation: each step scans over `grad_accum` microbatches
    # of batch_size/grad_accum samples, averaging grads before the single
    # optimizer update. Trades step latency for a larger effective batch
    # without growing live activation memory.
    grad_accum: int = 1
    eval_every: int = 0  # 0 => no in-loop eval
    eval_steps: int = 10  # batches per eval pass
    # ZeRO-style update sharding over the dp axis (training/zero.py;
    # round 18). 0 = replicated update (the pre-round-18 behavior);
    # 1 = optimizer state + the update computation shard 1/dp per
    # replica (params re-assembled by an all-gather after the update);
    # 2 = additionally keep the post-backward gradient tree dp-sharded —
    # the full-gradient psum becomes a reduce-scatter into the owned
    # slice and no replica materializes the whole gradient tree.
    # Inert when the formed mesh has dp == 1 (e.g. the llama8b config at
    # its fsdp memory floor); elastic worlds re-partition on remesh.
    zero_stage: int = 0
    # Dtype of the cross-replica gradient exchange ("float32"/"f32" |
    # "bfloat16"/"bf16"). bf16 halves the reduce-scatter bytes (first
    # bite of the EQuARX quantized-exchange item) at the cost of
    # rounding the summed gradient to 8 mantissa bits — error-feedback
    # and stochastic rounding are deliberately NOT applied, so the
    # default stays f32 and bf16 is an explicit, measured opt-in.
    grad_reduce_dtype: str = "float32"


@dataclass(frozen=True)
class DataConfig:
    dataset: str = "synthetic_mnist"
    # Held-out split for eval passes. With a shard server: the published
    # dataset name to stream (falls back to `dataset` with a distinct
    # shuffle seed if unset). Without: eval data is synthesized with a seed
    # disjoint from training.
    eval_dataset: Optional[str] = None
    shard_server_addr: Optional[str] = None  # None => generate locally
    prefetch: int = 2
    seq_len: int = 128  # LM/MLM datasets
    # Synthetic classification data only: derive labels from a fixed random
    # projection of the input instead of sampling them independently, so the
    # task is learnable and loss curves mean something (the elastic tests
    # assert decreasing loss across world re-formations).
    learnable: bool = False
    # Host-pipeline image augmentation (pad-4 random crop + horizontal
    # flip) on training sources streamed from the data plane. Eval sources
    # never augment.
    augment: bool = False
    # Dynamic MLM masking rate for token-corpus datasets feeding MLM models.
    mask_rate: float = 0.15


@dataclass(frozen=True)
class LocalSGDConfig:
    """Gossip / DiLoCo outer-sync training (training/local_sgd.py) — the
    faithful TPU descendant of the reference's asynchronous model gossip
    (``src/worker.cc:194-219``), selected per run instead of per code path.
    """

    outer: str = ""  # "" = disabled | "gossip" | "average" (DiLoCo)
    inner_steps: int = 8  # local steps between outer syncs
    mix_rate: float = 0.5  # gossip mix toward the partner (reference rate)
    outer_lr: float = 0.7  # DiLoCo outer SGD learning rate
    outer_momentum: float = 0.9
    # ---- DiLoCo degradation policy (round 19, training/diloco_dcn.py) ----
    # "full": the leader waits for every live island's delta (or the round
    # timeout) — the historic behavior. "quorum": the leader closes the
    # outer round as soon as quorum_fraction of the live islands have
    # delivered; stragglers' late deltas are handled per late_policy.
    participation: str = "full"  # "full" | "quorum"
    quorum_fraction: float = 1.0  # live-island fraction that closes a round
    # Late deltas (posted after their round closed): "drop" discards them
    # (counted); "discount" applies each as a stale plain-SGD update on the
    # next led anchor with weight staleness_discount ** rounds_late.
    late_policy: str = "drop"  # "drop" | "discount"
    staleness_discount: float = 0.25
    # Leader-side delta sanity gate: non-finite deltas are ALWAYS
    # quarantined (never averaged into the anchor); with >= gate_min_peers
    # finite deltas in a round, a delta whose L2 exceeds
    # median + outlier_factor * MAD is quarantined as a norm outlier.
    delta_gate: bool = True
    outlier_factor: float = 12.0
    gate_min_peers: int = 4
    # ---- quantized DCN exchange (round 20, training/wire_codec.py) ----
    # Wire encoding for outer-boundary delta pushes and anchor
    # broadcasts: "float32"/"f32" (uncompressed, the historic bytes),
    # "int8" (blockwise, ~4x fewer bytes) or "fp8" (e4m3, where the
    # runtime supports it). Decoding is self-describing, so islands can
    # migrate dtypes without a flag day; checkpoint/replica persistence
    # is never wire-coded (its CRC machinery needs byte identity).
    wire_dtype: str = "float32"
    wire_block: int = 128          # values per quantization block
    # Per-island error feedback: carry each round's quantization
    # residual into the next round's delta before quantizing, so the
    # leader's outer Nesterov step sees an unbiased long-run signal.
    wire_error_feedback: bool = True


@dataclass(frozen=True)
class ElasticConfig:
    """Elastic-trainer knobs (``training/elastic.py``; round 20).

    ``remesh_wire_dtype`` selects the wire encoding of the remesh
    drain→save→remesh→restore state stream: ``float32`` keeps the
    historic bit-exact checkpoint save per epoch transition; ``int8`` /
    ``fp8`` stream a blockwise-quantized transient blob instead (~4x
    fewer DCN bytes per world change, value-preserving within codec
    tolerance — the ``numerics_fingerprint reason=remesh_restore``
    trail proves it per transition). Durable checkpoints (final save,
    emergency save, ``checkpoint_every``) stay full-precision and
    CRC-verified regardless.
    """

    remesh_wire_dtype: str = "float32"  # float32 | int8 | fp8
    remesh_wire_block: int = 128


@dataclass(frozen=True)
class NumericsConfig:
    """Training-quality observability knobs (``telemetry/numerics.py``,
    ``training/audit.py``; round 17).

    ``enabled`` adds in-graph per-subtree grad/param/update norms,
    update-to-param ratios, non-finite flags and parameter fingerprints
    to the jitted step (cheap reductions fused into the backward) and
    fetches them to the host every ``cadence`` steps — numerics adds
    ZERO per-step host syncs beyond the fetch cadence, and the fetch is
    charged to a ``numerics`` ledger phase so `slt goodput` shows its
    true overhead. When the non-finite flag trips, the auditor re-runs
    a checked provenance sweep on pre-donation values (the checkpoint
    host shadow when one is armed) and fires a critical
    ``numerics.nonfinite`` alert naming the first bad layer.

    ``inject_nan_step``/``inject_nan_subtree`` are the chaos knobs the
    acceptance harness uses: scale the named parameter subtree's
    gradient by NaN at exactly that step, so "`slt numerics` + `slt
    doctor` name the faulting layer and step from telemetry alone" is a
    runnable command, not a claim.
    """

    enabled: bool = False
    cadence: int = 20             # host-fetch/emit every N steps
    depth: int = 1                # subtree grouping depth (top-level=1)
    fingerprint: bool = True      # per-step parameter fingerprints
    fingerprint_log: str = ""     # JSONL path for fingerprint records
    chunks: int = 4               # positional chunk sums per subtree
    provenance: str = "sweep"     # "sweep" | "off" (NaN/Inf root-causing)
    # ---- chaos / acceptance-harness fault injection ----
    inject_nan_step: int = 0      # 0 = off; else poison grads at this step
    inject_nan_subtree: str = ""  # "" = whole grad tree


@dataclass(frozen=True)
class ControlConfig:
    """Control-plane endpoints & intervals.

    Successor of ``src/serverless_learn.h:4-12`` (MASTER_ADDR,
    FILE_SERVER_ADDR, GOSSIP_INTERVAL, SIMULATED_TRAIN_INTERVAL) and
    ``src/master.cc:43,46`` (push/checkup intervals).
    """

    coordinator_addr: str = "localhost:50052"
    shard_server_addr: str = "localhost:50053"
    heartbeat_interval_ms: int = 1000
    lease_ttl_ms: int = 5000


@dataclass(frozen=True)
class MembershipConfig:
    """Membership plane selection + SWIM gossip tuning + degradation policy
    (``control/gossip.py``, consumed by ``training/elastic.py`` and
    ``training/elastic_multihost.py``).

    ``mode`` selects how liveness is established:

    * ``"master"`` — the classic path: every worker heartbeats the
      coordinator on a timer and the coordinator sweeps lapsed leases.
      O(N) fan-out from one process; fine at 16 nodes.
    * ``"gossip"`` — SWIM-style probabilistic probing: each member pings
      one random peer per protocol period, falls back to ``indirect_probes``
      ping-req relays on timeout, and spreads state changes by piggybacking
      them on the probe traffic. Failure detection is O(1) messages per
      member per period and dissemination converges in O(log N) periods.
      The coordinator stays as the registration/bootstrap directory and
      lease heartbeats slow down to a fallback channel.

    The degradation-policy fields apply in BOTH modes (elastic reads them):
    they turn the implicit "any membership twitch → remesh" behavior into
    explicit policy.
    """

    mode: str = "master"  # "master" | "gossip"
    # Gossip wire plane. seed "" derives the coordinator's gossip address
    # as <coordinator_host>:<coordinator_port + 1> (the py-coordinator's
    # default when started with gossip enabled).
    seed: str = ""
    gossip_bind_host: str = "127.0.0.1"
    gossip_port: int = 0                 # 0 = ephemeral
    protocol_period_ms: int = 250        # one probe round per member
    ping_timeout_ms: int = 80            # direct-ack wait before ping-req
    indirect_probes: int = 3             # ping-req relays per failed probe
    # A SUSPECT member is declared dead after
    # suspicion_mult * ceil(log2(N + 1)) protocol periods without a
    # refutation (incarnation bump from the accused).
    suspicion_mult: float = 2.0
    # Each membership update piggybacks on probe traffic until it has been
    # sent retransmit_mult * ceil(log2(N + 1)) times.
    retransmit_mult: float = 3.0
    max_piggyback: int = 12              # updates per packet
    # ---- graceful-degradation policy (elastic / DiLoCo) ----
    # SUSPECT alone never triggers a remesh: keep training until the
    # suspicion either refutes (no churn at all) or confirms dead.
    train_through_suspicion: bool = True
    # Membership changes must hold still this long before elastic acts on
    # them — anti-flap hysteresis for asymmetric partitions where a member
    # bounces (evict + instant re-register would otherwise remesh twice).
    remesh_debounce_s: float = 0.0
    # Safe-pause: when the live view drops below quorum_fraction of the
    # largest world seen, stop stepping (and do NOT remesh down onto a
    # minority island) until quorum returns or the run is stopped.
    safe_pause: bool = False
    quorum_fraction: float = 0.5
    # DiLoCo: allow non-leaders to re-challenge a hung leader (the
    # liveness escape); False pins leadership strictly to min-id.
    leader_rechallenge: bool = True


@dataclass(frozen=True)
class CheckpointConfig:
    """Crash-safe training-state knobs (``training/checkpoint.py``,
    ``training/replicate.py``; round 15).

    ``verify`` gates restore-time size/CRC verification (corrupt steps
    raise ``CheckpointCorrupt``, get quarantined and fall back to the
    newest verified step). ``emergency_save`` hooks a rate-limited
    synchronous blob save into the flight recorder's death path
    (SIGTERM / unhandled exception), so a crash loses at most the
    in-flight step.

    The replication trio makes remesh/rejoin fast: ``cache_dir`` keeps a
    worker-local copy of every checkpoint file (a remeshing worker
    restores from local disk instead of a central-store round trip),
    ``serve_cache`` exposes that cache to peers over the shard-server
    wire protocol (pure-Python twin, ephemeral port unless
    ``serve_cache_port``), and ``peers`` + ``replica_fanout`` push each
    commit to that many peer caches so a REJOINING worker restores from
    the nearest live peer even when the central store is slow or
    partitioned.
    """

    verify: bool = True
    keep: int = 3                       # retained steps (Checkpointer GC)
    emergency_save: bool = True
    emergency_min_interval_s: float = 30.0
    # ---- peer state replication ----
    cache_dir: str = ""                 # "" = no worker-local cache
    peers: str = ""                     # comma-separated peer cache addrs
    replica_fanout: int = 2             # peers to push each commit to
    serve_cache: bool = False           # serve cache_dir to peers
    serve_cache_port: int = 0           # 0 = ephemeral


@dataclass(frozen=True)
class KVCacheConfig:
    """Paged KV cache of the serving engine (``inference/kvcache.py``,
    consumed by ``inference/continuous.py``).

    One device-resident block pool per layer (``[num_blocks, block_size,
    K, D]``), a host-side free-list allocator and per-slot block tables: a
    slot only holds blocks for tokens it has actually produced and
    retirement returns blocks to the free list immediately. On top of the
    pool ride hash-based shared-prefix reuse (``prefix_cache``: identical
    prompt prefixes map to refcounted read-only blocks, copy-on-write at
    the first divergent block) and chunked prefill (``prefill_chunk``:
    long prompts split into chunks the scheduler interleaves between
    decode chunks; a prefill program carries up to ``max_slots``
    consecutive chunks, of one prompt or of several; how many programs
    an iteration dispatches the engine derives from its own slots
    unless ``prefill_budget`` caps it).
    """

    block_size: int = 16          # tokens per KV block (page)
    # Total pool blocks per layer. 0 = auto: max_slots * ceil(max_seq_len
    # / block_size) plus one row of slack for the prefix cache — the
    # no-overcommit default; size it DOWN to overcommit memory (admission
    # backpressure + preemption keep it correct).
    num_blocks: int = 0
    # Prompt tokens per prefill chunk (0 = whole prompt): one ROW of a
    # prefill program. A program's rows are consecutive chunks of the
    # prompts that are mid-prefill, so it moves up to max_slots *
    # prefill_chunk tokens whatever this is; a wider chunk buys nothing
    # but more programs to compile.
    prefill_chunk: int = 32
    # Bounds how long the rows that are decoding wait for their next
    # chunk while prompts prefill. 0 = derived: a scheduler iteration
    # feeds the prefilling slots, oldest first, in at most
    # chunk_size // 2 prefill programs while a slot decodes (at most
    # about half of the iteration) and unbounded while none does. > 0 =
    # cap on the prompt tokens one iteration dispatches across all
    # prefilling slots (an interactive deployment that wants a tighter
    # stall).
    prefill_budget: int = 0
    prefix_cache: bool = True     # shared-prefix block reuse (trie)
    # Max blocks the prefix trie may pin after their owners retire
    # (0 = auto: num_blocks // 4). LRU-evicted under pool pressure.
    prefix_cache_blocks: int = 0
    # ---- fleetscope digests (round 22) ----
    # kv_stats' prefix_hit_rate is windowed over the last N lookups so
    # router picking tracks traffic shifts (the lifetime average rides
    # along under prefix_hit_rate_lifetime).
    prefix_hit_window: int = 256
    # Resident-prefix digest caps shipped on replica pings: hottest
    # prefixes reported, and max chain hashes per digest (shallow-first,
    # so a truncated digest under-counts redundancy, never inflates it).
    digest_top_k: int = 8
    digest_hashes: int = 64


@dataclass(frozen=True)
class WaterfallConfig:
    """Per-request waterfall ledger knobs (``telemetry/waterfall.py``,
    threaded through ``inference/continuous.py``).

    A decode gap counts as a STALL when it exceeds the request's EWMA
    inter-token baseline by ``stall_mult``x AND by at least
    ``min_stall_s`` — both bounds, so a 0.1 ms engine doesn't flag
    micro-jitter and a 100 ms engine doesn't need retuning. Attribution
    intersects the gap with the engine's boundary-event ring
    (``events_window`` entries); per-request storage is bounded by
    ``max_stall_events`` / ``max_gap_samples`` so the ledger stays
    compact at any request length.
    """

    enabled: bool = True
    ewma_alpha: float = 0.3        # decode-ITL baseline smoothing
    stall_mult: float = 2.0        # gap > mult * baseline => stall
    min_stall_s: float = 0.002     # ... and exceeds baseline by this
    max_stall_events: int = 64     # attributed stall entries kept/request
    max_gap_samples: int = 256     # raw decode gaps kept/request
    events_window: int = 256       # engine boundary-event ring size


@dataclass(frozen=True)
class FleetConfig:
    """Serving-fleet knobs (``fleet/``): the front-door router
    (``slt route``), replica self-registration (``serve --fleet``) and the
    burn-rate-driven autoscaler.

    The router is robustness-first: per-replica health gating from each
    replica's ``/healthz``+``/alerts``, least-loaded + session-affine
    picking, hedged retries for idempotent generation after a p95-based
    hedge delay, outlier ejection, and brownout shedding (a typed
    ``overloaded`` error before queues melt). The autoscaler consumes the
    queue-wait SLO burn-rate alerts (``health.slos``) — scale-out on
    fast-burn, scale-in only after a sustained calm window plus cooldown,
    always through a graceful drain.
    """

    service: str = "serve"        # replicas register as replica:<service>
    router_host: str = "127.0.0.1"
    router_port: int = 50070
    replicas: str = ""            # static comma-separated replica addrs
    discover_interval_s: float = 2.0   # coordinator membership poll
    health_interval_s: float = 1.0     # /healthz + liveness probe period
    # ---- admission / brownout shedding ----
    max_inflight: int = 64        # router-wide in-flight capacity
    queue_timeout_s: float = 2.0  # bounded admission wait before shedding
    shed_start_frac: float = 0.8  # brownout: shed priority<=0 above this
    # ---- hedging (idempotent requests only) ----
    hedge: bool = True
    hedge_after_p95_mult: float = 1.5
    hedge_min_delay_s: float = 0.05
    max_retries: int = 2          # failover resends after transport errors
    upstream_timeout_s: float = 60.0
    # ---- outlier ejection ----
    eject_consecutive_errors: int = 3
    eject_s: float = 5.0
    dead_after_probes: int = 3    # failed liveness probes => replica dead
    # ---- drain / retirement ----
    drain_grace_s: float = 10.0
    # ---- KV memory pressure (paged engines report kv stats on ping) ----
    # Below this pooled free-block fraction on EVERY eligible replica,
    # priority<=0 traffic sheds with the typed overload error — queue
    # depth alone cannot see a fleet whose KV pools are nearly exhausted.
    kv_shed_free_frac: float = 0.02
    # ---- canary version split (round 23) ----
    # Initial candidate weight-version fingerprint + traffic fraction;
    # FleetRouter.set_canary() reconfigures the split at runtime (the
    # config object stays frozen like every other section). Assignment
    # is session-sticky: one conversation never straddles versions.
    canary_version: Optional[str] = None
    canary_frac: float = 0.0
    # ---- autoscaler ----
    autoscale: bool = False
    min_replicas: int = 1
    max_replicas: int = 4
    alert_substr: str = "queue_wait"   # react to alerts naming this
    scale_out_cooldown_s: float = 30.0
    scale_in_cooldown_s: float = 120.0
    scale_in_calm_s: float = 60.0


@dataclass(frozen=True)
class HealthConfig:
    """Cluster-health engine knobs (``telemetry/health.py``).

    ``slos`` declares the SLO objectives the burn-rate alerter tracks
    (see ``telemetry.health.parse_slos`` for the two spec kinds):

        "health": {"enabled": true, "slos": [
          {"name": "ttft", "kind": "latency",
           "metric": "slt_request_ttft_seconds",
           "threshold_s": 0.5, "objective": 0.95},
          {"name": "errors", "kind": "ratio",
           "bad": "slt_server_errors_total",
           "total": "slt_server_requests_total", "objective": 0.999}]}

    The anomaly/staleness/straggler detectors are always armed while the
    engine runs; these fields tune their sensitivity.
    """

    enabled: bool = False           # CLI --health also turns the engine on
    sample_interval_s: float = 2.0  # registry sampling period
    # EWMA+MAD anomaly detectors (step time, tokens/sec, heartbeat RTT,
    # queue wait, remesh time).
    anomaly_z: float = 6.0          # |modified z| that fires
    anomaly_min_samples: int = 12   # warmup before any z is produced
    anomaly_window: int = 240       # bounded per-series sample ring
    # Staleness watchdogs (no step / round / chunk in factor x the EWMA
    # inter-event interval).
    stale_factor: float = 5.0
    stale_min_interval_s: float = 1.0
    # DiLoCo straggler scoring (arrival offset vs. round median).
    straggler_factor: float = 4.0       # MADs above median = late
    straggler_min_rounds: int = 2
    straggler_window_rounds: int = 20
    # Multi-window SLO burn-rate thresholds (the standard fast/slow pair).
    slo_fast_burn: float = 14.4
    slo_slow_burn: float = 6.0
    slo_short_window_s: float = 60.0
    slo_long_window_s: float = 720.0
    # Alert lifecycle + forensics.
    clear_after_ticks: int = 3       # clean ticks before auto-resolve
    anchor_lag_rounds: float = 2.0   # DiLoCo lag gauge alert threshold
    dump_cooldown_s: float = 300.0   # min gap between critical flight dumps
    # Alert-triggered device profiling (telemetry/profiler.py): when the
    # process was started with --profile-dir, a CRITICAL alert captures a
    # jax.profiler window of this many seconds (0 disables), rate-limited
    # to one capture per profile_cooldown_s.
    profile_on_critical_s: float = 3.0
    profile_cooldown_s: float = 600.0
    # Training-quality detectors (round 17, telemetry/numerics.LossHealth
    # over the numerics step ring): loss-spike z threshold (warning;
    # > 2x escalates to critical), plateau window in optimizer steps with
    # the minimum relative improvement that resets it, and the grad-norm
    # explosion z (critical).
    numerics_spike_z: float = 6.0
    numerics_plateau_window: int = 200
    numerics_plateau_min_rel: float = 1e-3
    numerics_explode_z: float = 8.0
    slos: tuple = ()                 # SLO spec objects (see docstring)


@dataclass(frozen=True)
class ExperimentConfig:
    model: str = "mlp_mnist"
    model_overrides: dict = field(default_factory=dict)
    mesh: MeshConfig = field(default_factory=MeshConfig)
    optimizer: OptimizerConfig = field(default_factory=OptimizerConfig)
    train: TrainConfig = field(default_factory=TrainConfig)
    data: DataConfig = field(default_factory=DataConfig)
    control: ControlConfig = field(default_factory=ControlConfig)
    local_sgd: LocalSGDConfig = field(default_factory=LocalSGDConfig)
    health: HealthConfig = field(default_factory=HealthConfig)
    membership: MembershipConfig = field(default_factory=MembershipConfig)
    fleet: FleetConfig = field(default_factory=FleetConfig)
    kv: KVCacheConfig = field(default_factory=KVCacheConfig)
    waterfall: WaterfallConfig = field(default_factory=WaterfallConfig)
    checkpoint: CheckpointConfig = field(default_factory=CheckpointConfig)
    numerics: NumericsConfig = field(default_factory=NumericsConfig)
    elastic: ElasticConfig = field(default_factory=ElasticConfig)

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), indent=2, sort_keys=True)

    @classmethod
    def from_json(cls, text: str) -> "ExperimentConfig":
        raw = json.loads(text)
        return cls.from_dict(raw)

    @classmethod
    def from_dict(cls, raw: dict) -> "ExperimentConfig":
        def build(tp, val):
            if val is None:
                return tp()
            return tp(**val)

        return cls(
            model=raw.get("model", "mlp_mnist"),
            model_overrides=raw.get("model_overrides", {}) or {},
            mesh=build(MeshConfig, raw.get("mesh")),
            optimizer=build(OptimizerConfig, raw.get("optimizer")),
            train=build(TrainConfig, raw.get("train")),
            data=build(DataConfig, raw.get("data")),
            control=build(ControlConfig, raw.get("control")),
            local_sgd=build(LocalSGDConfig, raw.get("local_sgd")),
            health=build(HealthConfig, raw.get("health")),
            membership=build(MembershipConfig, raw.get("membership")),
            fleet=build(FleetConfig, raw.get("fleet")),
            kv=build(KVCacheConfig, raw.get("kv")),
            waterfall=build(WaterfallConfig, raw.get("waterfall")),
            checkpoint=build(CheckpointConfig, raw.get("checkpoint")),
            numerics=build(NumericsConfig, raw.get("numerics")),
            elastic=build(ElasticConfig, raw.get("elastic")),
        )

    def override(self, **kwargs: Any) -> "ExperimentConfig":
        return dataclasses.replace(self, **kwargs)
