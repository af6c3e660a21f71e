"""Command-line entry points — the framework's L4 layer.

Successor of the reference's process surface (``./master``, ``./worker ADDR``,
``./file_server`` — reference ``src/Makefile:26-35``, ``src/worker.cc:233-258``),
where the worker's address was the only CLI argument in the whole system and
every interval change required recompiling (``src/serverless_learn.h:5-12``).
Here one typed CLI fronts everything:

    python -m serverless_learn_tpu train        # jitted training run
    python -m serverless_learn_tpu eval         # forward-only evaluation
    python -m serverless_learn_tpu generate     # KV-cache LM sampling
    python -m serverless_learn_tpu serve        # generation server (TCP/JSON)
    python -m serverless_learn_tpu route        # fleet router (health-aware front door)
    python -m serverless_learn_tpu loadgen      # open/closed-loop load generator
    python -m serverless_learn_tpu worker       # elastic worker (joins a cluster)
    python -m serverless_learn_tpu coordinator  # native membership daemon
    python -m serverless_learn_tpu shard-server # native data-plane daemon
    python -m serverless_learn_tpu publish      # push a dataset to the data plane
    python -m serverless_learn_tpu stats        # scrape a daemon's load/RPC stats
    python -m serverless_learn_tpu top          # live cluster telemetry view
    python -m serverless_learn_tpu trace        # cross-node timeline from span logs
    python -m serverless_learn_tpu doctor       # ranked cluster diagnosis
    python -m serverless_learn_tpu goodput      # goodput/badput accounting report
    python -m serverless_learn_tpu numerics     # training-quality: fingerprint diff/bisect
    python -m serverless_learn_tpu profile      # trigger a device-trace capture
    python -m serverless_learn_tpu bench        # perf regression gate (--gate)
    python -m serverless_learn_tpu check        # project-aware static analysis
    python -m serverless_learn_tpu race         # replay a recorded race-check log
    python -m serverless_learn_tpu chaos        # fault-injection chaos harness
    python -m serverless_learn_tpu models       # list registered model families

Every long-running command takes ``--metrics-port N`` to expose a
Prometheus-style ``/metrics`` endpoint (``telemetry/``); ``top`` polls one
or more of those endpoints into a refreshing single-screen cluster view.

Configs come from ``--config FILE.json`` plus ``--set dotted.key=value``
overrides plus dedicated flags (flags win).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import socket
import sys
from typing import List, Optional


def _parse_mesh(spec: str) -> dict:
    """'dp=8,tp=2' -> {'dp': 8, 'tp': 2}."""
    out = {}
    for part in spec.split(","):
        if not part:
            continue
        k, _, v = part.partition("=")
        out[k.strip()] = int(v)
    return out


def _coerce(text: str):
    """Parse a --set value: JSON if it parses, else the raw string."""
    try:
        return json.loads(text)
    except (json.JSONDecodeError, ValueError):
        return text


def _raw_config(args) -> dict:
    """--config file + --set overrides + dedicated flags (flags win), as
    the dict ``ExperimentConfig.from_dict`` takes."""
    raw = {}
    if getattr(args, "config", None):
        with open(args.config) as f:
            raw = json.load(f)
    for item in getattr(args, "set", None) or []:
        path, _, val = item.partition("=")
        if not _:
            raise SystemExit(f"--set expects dotted.key=value, got {item!r}")
        node = raw
        keys = path.split(".")
        for k in keys[:-1]:
            node = node.setdefault(k, {})
        node[keys[-1]] = _coerce(val)

    # Dedicated flags override both file and --set.
    def put(path: List[str], val):
        node = raw
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = val

    def flag(name):
        return getattr(args, name, None)

    if flag("model"):
        put(["model"], args.model)
    if flag("mesh"):
        put(["mesh"], {**raw.get("mesh", {}), **_parse_mesh(args.mesh)})
    if flag("batch_size") is not None:
        put(["train", "batch_size"], args.batch_size)
    if flag("steps") is not None:
        put(["train", "num_steps"], args.steps)
    if flag("checkpoint_every") is not None:
        put(["train", "checkpoint_every"], args.checkpoint_every)
    if flag("lr") is not None:
        put(["optimizer", "learning_rate"], args.lr)
    if flag("optimizer"):
        put(["optimizer", "name"], args.optimizer)
    if flag("seq_len") is not None:
        put(["data", "seq_len"], args.seq_len)
    if flag("dataset"):
        put(["data", "dataset"], args.dataset)
    if flag("shard_server"):
        put(["data", "shard_server_addr"], args.shard_server)
        put(["control", "shard_server_addr"], args.shard_server)
    if flag("coordinator"):
        put(["control", "coordinator_addr"], args.coordinator)
    return raw


def _config_from_args(args) -> "ExperimentConfig":
    """The config as the arguments give it. Touches no JAX: supervisors
    and the router parse their arguments with this and must leave the chip
    to the children they start (the elastic paths derive dp from their
    live world through ``config.scale_mesh``)."""
    from serverless_learn_tpu.config import ExperimentConfig

    return ExperimentConfig.from_dict(_raw_config(args))


def _trainer_config(args) -> "ExperimentConfig":
    """Config for a command that builds its trainer in THIS process: a
    config that names no mesh gets all of this process's devices on the dp
    axis. The devices are asked for here, where the trainer is about to be
    built, never where a supervisor parses arguments."""
    from serverless_learn_tpu.config import ExperimentConfig, MeshConfig

    raw = _raw_config(args)
    cfg = ExperimentConfig.from_dict(raw)
    if not raw.get("mesh"):
        import jax

        cfg = cfg.override(mesh=MeshConfig(dp=len(jax.devices())))
    return cfg


def _add_train_flags(p: argparse.ArgumentParser):
    p.add_argument("--config", help="JSON config file (ExperimentConfig)")
    p.add_argument("--set", action="append", metavar="dotted.key=value",
                   help="override any config field, e.g. --set train.seed=3")
    p.add_argument("--model", help="registered model name (see `models`)")
    p.add_argument("--mesh", help="mesh axes, e.g. dp=4,tp=2")
    p.add_argument("--batch-size", type=int)
    p.add_argument("--steps", type=int)
    p.add_argument("--lr", type=float)
    p.add_argument("--optimizer",
                   help="adamw | adam | sgd | adafactor | lion | rmsprop")
    p.add_argument("--seq-len", type=int)
    p.add_argument("--dataset")
    p.add_argument("--shard-server", metavar="ADDR",
                   help="stream data from this shard server")
    p.add_argument("--checkpoint-every", type=int, default=None)
    p.add_argument("--checkpoint-dir", help="save checkpoints to a local dir")
    p.add_argument("--checkpoint-store", metavar="ADDR",
                   help="save checkpoints to a shard server")
    p.add_argument("--checkpoint-name", default="ckpt",
                   help="checkpoint namespace inside the store (an elastic "
                        "worker saves under its --name)")
    p.add_argument("--profile-dir", help="arm the shared profiler service "
                        "on this role: /debug/profile?seconds=N on the "
                        "metrics endpoint (see `slt profile`), plus "
                        "alert-triggered captures with --health (config "
                        "health.profile_on_critical_s). train without "
                        "--metrics-port keeps the classic behavior: one "
                        "capture bracketing the whole run")
    p.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                   help="serve /metrics (Prometheus text) + /metrics.json "
                        "from this port (0 = auto; scraped by `top`)")
    p.add_argument("--events-log", metavar="PATH", default=None,
                   help="append one JSONL span record per request/RPC/"
                        "round here (this node's half of an `slt trace` "
                        "timeline); also arms the flight recorder")
    p.add_argument("--flight-dir", metavar="DIR", default=None,
                   help="write flight-recorder dumps (last spans/events + "
                        "metrics + device memory) here on SIGTERM/crash/"
                        "lease expiry (default: the events log's "
                        "directory, or cwd)")
    p.add_argument("--node", default=None,
                   help="node name stamped on span records (default "
                        "<hostname>-<pid>; SLT_NODE env overrides)")
    p.add_argument("--health", action="store_true",
                   help="run the cluster-health engine: EWMA/MAD anomaly "
                        "detectors, config-declared SLO burn-rate alerts "
                        "(health.slos), and staleness/straggler watchdogs "
                        "— served at /alerts on the metrics endpoint, "
                        "flipping /healthz to 503 on critical (config "
                        "health.enabled=true does the same)")
    p.add_argument("--numerics", action="store_true",
                   help="enable training-quality observability: in-graph "
                        "per-subtree tensor stats + fingerprints in the "
                        "jitted step, cadence-gated host fetch (config "
                        "numerics.cadence), NaN/Inf provenance on the "
                        "first non-finite step, and loss-health alerts "
                        "through --health (config numerics.enabled=true "
                        "does the same)")
    p.add_argument("-v", "--verbose", action="store_true")
    # Multi-host: either serverless bootstrap via the native coordinator
    # (--world-size) or explicit topology (--num-processes/--process-id).
    p.add_argument("--coordinator", metavar="ADDR",
                   help="native coordinator address")
    p.add_argument("--world-size", type=int,
                   help="form a JAX process group of this many hosts via "
                        "the native coordinator (requires --coordinator)")
    p.add_argument("--advertise-host", default="127.0.0.1",
                   help="host other processes can reach this one at")
    p.add_argument("--jax-coordinator", metavar="ADDR",
                   help="explicit JAX coordination service address")
    p.add_argument("--num-processes", type=int)
    p.add_argument("--process-id", type=int)


def _start_metrics(args):
    """Start the /metrics exporter when --metrics-port is given; the
    caller owns stop(). Logs the bound address so `top` users can copy it
    (port 0 auto-assigns). --profile-dir arms the SHARED profiler service
    on every role: /debug/profile on this endpoint, `slt profile`
    remotely, and (with the health engine) alert-triggered captures."""
    profile_dir = getattr(args, "profile_dir", None)
    if profile_dir:
        from serverless_learn_tpu.telemetry import profiler

        profiler.arm(profile_dir)
    port = getattr(args, "metrics_port", None)
    if port is None:
        return None
    from serverless_learn_tpu.telemetry import MetricsExporter
    from serverless_learn_tpu.utils.metrics import log_json

    exp = MetricsExporter(port=port, profile_dir=profile_dir).start()
    log_json({"event": "metrics", "addr": exp.addr,
              **({"profile_armed": True} if profile_dir else {})},
             stream=sys.stdout)
    return exp


def _start_health(args, cfg, exporter=None, registry=None):
    """Start the cluster-health engine when --health (or config
    health.enabled) asks for it; wires it behind the exporter's /alerts
    and /healthz when one exists. The caller owns stop()."""
    if not (getattr(args, "health", False) or cfg.health.enabled):
        return None
    from serverless_learn_tpu.telemetry.health import HealthEngine
    from serverless_learn_tpu.utils.metrics import log_json

    flight_dir = getattr(args, "flight_dir", None)
    engine = HealthEngine(registry=registry, config=cfg.health,
                          flight_dir=flight_dir).start()
    if exporter is not None:
        exporter.attach_health(engine)
    # Alert-triggered profiling: with --profile-dir armed and a positive
    # health.profile_on_critical_s, a critical fire captures a device
    # trace (rate-limited) — the incident's profile exists before anyone
    # looks at the alert.
    from serverless_learn_tpu.telemetry import profiler

    profile_armed = (profiler.armed()
                     and cfg.health.profile_on_critical_s > 0)
    if profile_armed:
        profiler.on_alert(engine,
                          seconds=cfg.health.profile_on_critical_s,
                          cooldown_s=cfg.health.profile_cooldown_s)
    log_json({"event": "health", "interval_s": engine.interval_s,
              "slos": [s["name"] for s in engine.slos],
              **({"profile_on_critical_s":
                  cfg.health.profile_on_critical_s}
                 if profile_armed else {}),
              **({"alerts_addr": exporter.addr} if exporter else {})},
             stream=sys.stdout)
    return engine


def _init_tracing_from_args(args):
    """Arm distributed tracing + the flight recorder when the user asked
    for either (--events-log / --flight-dir / --node). Installing the
    flight handlers means a SIGTERM'd or crashing process leaves a
    flight-<node>-<ts>.json with its last spans (`slt trace` ingests it)."""
    events_log = getattr(args, "events_log", None)
    flight_dir = getattr(args, "flight_dir", None)
    node = getattr(args, "node", None)
    if not (events_log or flight_dir or node):
        return
    from serverless_learn_tpu.telemetry import init_tracing
    from serverless_learn_tpu.utils.metrics import log_json

    if flight_dir is None:
        flight_dir = (os.path.dirname(os.path.abspath(events_log))
                      if events_log else ".")
    name = init_tracing(node=node, events_log=events_log,
                        flight_dir=flight_dir)
    log_json({"event": "tracing", "node": name,
              **({"events_log": events_log} if events_log else {}),
              "flight_dir": flight_dir}, stream=sys.stdout)


def _make_checkpointer(args, name: Optional[str] = None, cfg=None):
    from serverless_learn_tpu.training.checkpoint import (
        Checkpointer, LocalStore, ShardServerStore)
    from serverless_learn_tpu.training.replicate import maybe_replicated

    name = name or getattr(args, "checkpoint_name", None) or "ckpt"
    if args.checkpoint_store:
        store = ShardServerStore(args.checkpoint_store)
    elif args.checkpoint_dir:
        store = LocalStore(args.checkpoint_dir)
    else:
        return None
    ck = cfg.checkpoint if cfg is not None else None
    store = maybe_replicated(store, ck)
    if ck is not None:
        return Checkpointer(store, name=name, keep=ck.keep,
                            verify=ck.verify)
    return Checkpointer(store, name=name)


def _write_train_bundle(args, cfg, state=None, extra=None):
    """`--run-bundle DIR` (round 24): stamp the run's artifacts — event
    JSONL, numerics fingerprint trail, xray capture/summary, config +
    git + weight-version fingerprints — into one ``run.json`` manifest
    so `slt regress` can attribute any later delta against this run.
    Best-effort: a failed stamp warns and never fails the run."""
    out_dir = getattr(args, "run_bundle", None)
    if not out_dir:
        return None
    try:
        from serverless_learn_tpu.telemetry import regress, xray

        weight_version = None
        if state is not None and hasattr(state, "params"):
            try:
                from serverless_learn_tpu.telemetry import (
                    numerics as _numerics)

                weight_version = _numerics.weight_version(state.params)
            except Exception:
                pass
        return regress.write_bundle(
            out_dir, role="train",
            events=[p for p in [getattr(args, "events_log", None)] if p],
            fingerprints=[p for p in [cfg.numerics.fingerprint_log] if p],
            xray_summary=xray.get_last_summary(),
            xray_dirs=[p for p in [getattr(args, "profile_dir", None)]
                       if p],
            config=regress.config_stamp(cfg),
            config_fp=regress.config_fingerprint(cfg),
            git_sha_value=regress.git_sha(),
            weight_version=weight_version,
            extra=extra)
    except Exception as e:
        print(f"WARNING: --run-bundle write failed: {e}", file=sys.stderr)
        return None


def cmd_train(args) -> int:
    import contextlib

    import jax

    from serverless_learn_tpu.training.loop import run_training
    from serverless_learn_tpu.utils.metrics import log_json
    from serverless_learn_tpu.utils.tracing import get_tracer

    # Form the multi-host process group BEFORE reading the config: the
    # default mesh spans all *global* devices.
    world = None
    if args.world_size:
        if not args.coordinator:
            raise SystemExit("--world-size requires --coordinator")
        from serverless_learn_tpu.parallel.multihost import (
            bootstrap_via_coordinator)

        world = bootstrap_via_coordinator(
            args.coordinator, args.world_size,
            advertise_host=args.advertise_host)
    elif args.num_processes:
        from serverless_learn_tpu.parallel.multihost import initialize

        if args.process_id is None or not args.jax_coordinator:
            raise SystemExit(
                "--num-processes requires --jax-coordinator and --process-id")
        initialize(args.jax_coordinator, args.num_processes, args.process_id)

    _init_tracing_from_args(args)
    cfg = _trainer_config(args)
    if getattr(args, "numerics", False) and not cfg.numerics.enabled:
        import dataclasses as _dc

        cfg = cfg.override(numerics=_dc.replace(cfg.numerics,
                                                enabled=True))
    exporter = _start_metrics(args)
    health = _start_health(args, cfg, exporter=exporter)

    def _bracket_ctx():
        # --profile-dir semantics on train: with a metrics endpoint the
        # shared on-demand /debug/profile (+ alert-triggered captures)
        # is the tool — bracketing a long run in one device trace would
        # produce an unloadable capture. Without one (the classic local
        # workflow) bracket the whole run, through the shared profiler
        # lock so an on-demand request can never nest a start_trace.
        if args.profile_dir and exporter is None:
            from serverless_learn_tpu.telemetry.profiler import (
                capture_session)

            return capture_session(args.profile_dir)
        return contextlib.nullcontext()

    ckpt = None
    try:
        ckpt = _make_checkpointer(args, cfg=cfg)
        every = cfg.train.checkpoint_every

        if cfg.local_sgd.outer:
            # Gossip / DiLoCo outer-sync training over the dp replicas.
            if world is not None:
                raise SystemExit("local SGD is single-process (replicas are "
                                 "the dp mesh axis)")
            from serverless_learn_tpu.training.local_sgd import run_local_sgd

            with _bracket_ctx():
                state, meter = run_local_sgd(cfg, checkpointer=ckpt,
                                             verbose=args.verbose)
            summary = meter.steady_state()
            log_json({"event": "done", "mode": f"local_sgd/{cfg.local_sgd.outer}",
                      "final_step": int(jax.device_get(state.step)),
                      **{k: round(v, 3) for k, v in summary.items()}},
                     stream=sys.stdout)
            _write_train_bundle(args, cfg, state=state)
            return 0

        callback = None
        if ckpt is not None:
            # Shadow the newest state for the emergency-save death hook
            # (round 15): a SIGTERM'd or crashing run commits it
            # synchronously via the flight recorder, losing at most
            # emergency_min_interval_s of steps instead of everything
            # since the last periodic save. note_state keeps a HOST
            # copy — the live state's buffers are donated into the next
            # step and dead by the time the handler runs.
            if cfg.checkpoint.emergency_save:
                ckpt.arm_emergency(
                    min_interval_s=cfg.checkpoint.emergency_min_interval_s)

            def callback(step, state, stats):
                ckpt.note_state(state)
                if every and step % every == 0:
                    ckpt.save(state)

        trainer = None
        auditor = None
        if cfg.numerics.enabled:
            # Build the trainer here so the auditor can wire the
            # checkpointer's note_state host shadow as its pre-donation
            # provenance source (round 17); run_training reuses it.
            from serverless_learn_tpu.training.audit import NumericsAuditor
            from serverless_learn_tpu.training.train_step import (
                build_trainer)

            trainer = build_trainer(cfg)
            auditor = NumericsAuditor(
                cfg, bundle=trainer.bundle,
                shadow_fn=ckpt.host_shadow if ckpt is not None else None)
        with _bracket_ctx():
            state, meter = run_training(cfg, trainer=trainer,
                                        step_callback=callback,
                                        verbose=args.verbose,
                                        auditor=auditor)
        if auditor is not None:
            auditor.close()
        if ckpt is not None:
            ckpt.save(state)
            ckpt.wait()
        summary = meter.steady_state()
        from serverless_learn_tpu.telemetry import goodput as _goodput

        grep = _goodput.get_ledger().report(mfu=summary.get("mfu"))
        log_json({"event": "done",
                  "final_step": int(jax.device_get(state.step)),
                  **({"rank": world.rank, "world": world.num_processes}
                     if world else {}),
                  **{k: round(v, 3) for k, v in summary.items()},
                  "goodput": grep["goodput"],
                  "badput_breakdown": grep["badput_breakdown"],
                  "spans": get_tracer().summary()}, stream=sys.stdout)
        _write_train_bundle(args, cfg, state=state,
                            extra={"goodput": grep})
    finally:
        if ckpt is not None:
            ckpt.close()  # drain async upload, disarm the emergency hook
            if hasattr(ckpt.store, "close"):
                ckpt.store.close()
        if health is not None:
            health.stop()
        if exporter is not None:
            exporter.stop()
        if world is not None:
            world.shutdown()
    return 0


def cmd_eval(args) -> int:
    """Forward-only evaluation of a (possibly checkpointed) model."""
    from serverless_learn_tpu.training.loop import run_eval

    if args.world_size or args.num_processes:
        raise SystemExit(
            "--world-size/--num-processes form a multi-host group and apply "
            "to `train`; `eval` is single-process")
    cfg = _trainer_config(args)
    trainer = _build_inference_trainer(cfg)
    ckpt = _make_checkpointer(args)
    ckpt_step = None
    if ckpt is not None:
        ckpt_step = ckpt.latest_step()
        if ckpt_step is None:
            # Evaluating random init while the user pointed at a checkpoint
            # store would print plausible-but-meaningless numbers.
            raise SystemExit(
                "no checkpoint found in the configured store; drop "
                "--checkpoint-dir/--checkpoint-store to eval a fresh init")
        state = ckpt.restore(trainer.abstract_state(),
                             shardings=trainer.state_shardings)
    else:
        state = trainer.init()
    metrics = run_eval(cfg, trainer, state,
                       num_batches=args.eval_steps or cfg.train.eval_steps)
    print(json.dumps({"checkpoint_step": ckpt_step,
                      **{k: round(float(v), 6) for k, v in metrics.items()}}))
    return 0


def _build_inference_trainer(cfg):
    """build_trainer for forward-only commands: a config mesh SMALLER than
    the host's device count uses a device prefix (serving hardware rarely
    matches the training pod; `--set mesh.dp=1` must just work on an
    8-device host) instead of erroring on the exact-size check."""
    import jax

    from serverless_learn_tpu.parallel.mesh import make_mesh
    from serverless_learn_tpu.training.train_step import build_trainer

    devices = jax.devices()
    if cfg.mesh.size < len(devices):
        return build_trainer(
            cfg, mesh=make_mesh(cfg.mesh, devices=devices[:cfg.mesh.size]))
    return build_trainer(cfg)


def _serving_config(cfg):
    """The sequential-module twin of a (possibly pipeline-trained) config.

    ``generate``/``serve`` need the KV-cached sequential module — the
    pipeline execution knob is stripped (``pipeline_interleave``/``_stages``
    stay: the param conversion needs them to undo the interleaved layer
    order). The mesh is the caller's problem (``--set mesh.dp=1 ...``):
    serving hardware rarely matches the training pod."""
    ov = dict(cfg.model_overrides)
    was_pipeline = bool(ov.pop("pipeline", False))
    ov.pop("pipeline_microbatches", None)
    return (cfg.override(model_overrides=ov) if was_pipeline else cfg)


def _load_inference_params(args, cfg, trainer):
    """Params for a pure-forward workload: (params, checkpoint_step).

    With a checkpoint store: restore ONLY the params subtree on the host
    (template-free — see ``Checkpointer.restore_params_host``) and place
    it on device; optimizer moments (~2x params for adamw) never touch
    HBM. A pipeline-trained checkpoint's stacked ``pipe_blocks`` are
    unstacked into the serving module's per-layer layout. Without a
    store: a jitted params-only init."""
    import jax
    import jax.numpy as jnp

    ckpt = _make_checkpointer(args)
    if ckpt is not None:
        step = ckpt.latest_step()
        if step is None:
            raise SystemExit("no checkpoint found in the configured store")
        host_params = ckpt.restore_params_host(step=step)
        mcfg = getattr(trainer.bundle.module, "cfg", None)
        has_stack = (isinstance(host_params, dict)
                     and ("pipe_blocks" in host_params
                          or "pipe_blocks" in host_params.get("pipeline", {})))
        if (has_stack and mcfg is not None
                and not getattr(mcfg, "pipeline", False)):
            from serverless_learn_tpu.models.transformer import (
                unstack_pipeline_params)

            host_params = unstack_pipeline_params(host_params, mcfg)
        # The template-free restore skipped shape checking; validate
        # against the serving module's abstract params so a config/
        # checkpoint mismatch fails HERE with paths and shapes, not as a
        # dot-shape error deep inside the jitted forward. Keyed by path
        # (NOT a leaf zip, which silently truncates and mis-pairs when
        # the tree structures differ).
        abstract = jax.eval_shape(lambda: trainer.init_fn(0)).params
        got = {jax.tree_util.keystr(p): tuple(l.shape) for p, l in
               jax.tree_util.tree_flatten_with_path(host_params)[0]}
        want = {jax.tree_util.keystr(p): tuple(l.shape) for p, l in
                jax.tree_util.tree_flatten_with_path(abstract)[0]}
        problems = (
            [f"missing from checkpoint: {k}" for k in sorted(want - got.keys())]
            + [f"not in serving model: {k}" for k in sorted(got.keys() - want)]
            + [f"{k}: checkpoint {got[k]} vs serving {want[k]}"
               for k in sorted(got.keys() & want) if got[k] != want[k]])
        if problems:
            raise SystemExit(
                f"checkpoint params do not fit the serving config "
                f"({cfg.model} with overrides {cfg.model_overrides}): "
                + "; ".join(problems[:5])
                + (f" (+{len(problems) - 5} more)" if len(problems) > 5
                   else ""))
        return jax.tree_util.tree_map(
            jax.device_put, host_params, trainer.state_shardings.params), step
    init_params = jax.jit(
        lambda: trainer.bundle.module.init(
            jax.random.PRNGKey(cfg.train.seed),
            jnp.zeros((1, 8), jnp.int32))["params"],
        out_shardings=trainer.state_shardings.params)
    return init_params(), None


def _maybe_quantize(args, trainer, params):
    """(module, params) honoring ``--quant``: the checkpoint restores in
    its trained dtype, THEN projections quantize to int8 + scale and the
    serving module switches to the quant config — the restore-time shape
    validation stays against the float tree."""
    module = trainer.bundle.module
    if not getattr(args, "quant", None):
        return module, params
    import dataclasses

    import jax

    from serverless_learn_tpu.inference.quantize import quantize_params_int8

    qmodule = type(module)(dataclasses.replace(module.cfg, quant=args.quant))
    return qmodule, jax.jit(quantize_params_int8)(params)


def cmd_generate(args) -> int:
    """Autoregressive sampling from a (possibly checkpointed) causal LM."""
    import jax
    import jax.numpy as jnp

    from serverless_learn_tpu.inference.generate import generate

    if args.world_size or args.num_processes:
        raise SystemExit(
            "--world-size/--num-processes form a multi-host group and apply "
            "to `train`; `generate` is single-process")
    cfg = _serving_config(_trainer_config(args))
    trainer = _build_inference_trainer(cfg)
    params, ckpt_step = _load_inference_params(args, cfg, trainer)
    if args.prompt:
        ids = [int(t) for t in args.prompt.split(",")]
        prompt = jnp.asarray([ids], jnp.int32)
    else:
        prompt = jax.random.randint(
            jax.random.PRNGKey(args.seed), (1, args.prompt_len), 0,
            trainer.bundle.module.cfg.vocab_size)
    module, params = _maybe_quantize(args, trainer, params)
    stats = None
    if args.draft_layers:
        # Speculative decoding: prefix-draft (the target's own first N
        # layers) + one-pass verify. Greedy-exact by construction.
        from serverless_learn_tpu.inference.speculative import (
            prefix_draft, speculative_generate)

        if args.temperature != 0.0:
            raise SystemExit("--draft-layers is greedy-only "
                             "(temperature must be 0)")
        try:
            draft, dparams = prefix_draft(module, params,
                                          args.draft_layers)
            out, stats = speculative_generate(
                module, params, draft, dparams, prompt,
                max_new_tokens=args.max_new_tokens, K=args.spec_k,
                eos_id=args.eos_id)
        except ValueError as e:  # bad --draft-layers / --spec-k / window
            raise SystemExit(str(e))
    else:
        out = generate(module, params, prompt,
                       max_new_tokens=args.max_new_tokens,
                       temperature=args.temperature, top_k=args.top_k,
                       eos_id=args.eos_id,
                       rng=jax.random.PRNGKey(args.seed))
    rep = {"checkpoint_step": ckpt_step,
           "prompt": np_tolist(prompt),
           "tokens": np_tolist(out)}
    if stats is not None:
        rep["speculative"] = stats
    print(json.dumps(rep))
    return 0


def np_tolist(x):
    import numpy as np

    return np.asarray(x).tolist()


def cmd_serve(args) -> int:
    """Serve generation requests (JSON lines over TCP) from a causal LM."""
    from serverless_learn_tpu.inference.server import GenerationServer
    from serverless_learn_tpu.utils.metrics import log_json

    if args.world_size or args.num_processes:
        raise SystemExit("`serve` is single-process")
    _init_tracing_from_args(args)
    cfg = _serving_config(_trainer_config(args))
    trainer = _build_inference_trainer(cfg)
    params, _ = _load_inference_params(args, cfg, trainer)
    module, params = _maybe_quantize(args, trainer, params)
    kv = cfg.kv
    if args.kv_block_size is not None:
        kv = dataclasses.replace(kv, block_size=args.kv_block_size)
    if args.kv_num_blocks is not None:
        kv = dataclasses.replace(kv, num_blocks=args.kv_num_blocks)
    if args.prefill_chunk is not None:
        kv = dataclasses.replace(kv, prefill_chunk=args.prefill_chunk)
    if args.no_prefix_cache:
        kv = dataclasses.replace(kv, prefix_cache=False)
    server = GenerationServer(module, params,
                              host=args.host, port=args.port,
                              max_batch=args.max_batch,
                              chunk_size=args.chunk_size,
                              metrics_port=args.metrics_port,
                              event_log_path=args.events_log,
                              profile_dir=args.profile_dir,
                              kv=kv)
    health = _start_health(args, cfg, exporter=server._exporter,
                           registry=server.registry)
    registration = None
    if args.fleet:
        # Replica self-registration (fleet/registration.py): join the
        # coordinator directory at birth so the router discovers this
        # replica without a static list; SIGTERM deregisters FIRST (the
        # router stops routing here instantly), then drains in-flight
        # work before exiting.
        import signal

        from serverless_learn_tpu.fleet.registration import (
            FleetRegistration)

        registration = FleetRegistration(
            cfg.control.coordinator_addr, server.addr, service=args.fleet,
            metrics_addr=server.metrics_addr,
            heartbeat_interval_ms=cfg.control.heartbeat_interval_ms).start()
        grace = (cfg.fleet.drain_grace_s if args.drain_grace_s is None
                 else args.drain_grace_s)

        def _terminate(signum, frame):
            try:
                registration.stop()
            except Exception:
                pass
            server.drain(grace)
            raise SystemExit(0)

        signal.signal(signal.SIGTERM, _terminate)
    log_json({"event": "serving", "addr": server.addr,
              "model": cfg.model,
              **({"fleet": args.fleet,
                  "worker_id": registration.worker_id}
                 if registration else {}),
              **({"metrics_addr": server.metrics_addr}
                 if server.metrics_addr else {})}, stream=sys.stdout)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        if registration is not None:
            try:
                registration.stop()
            except Exception:
                pass
        if health is not None:
            health.stop()
        server.stop()
    return 0


def cmd_route(args) -> int:
    """Run the fleet router: one front-door address over N engine
    replicas (fleet/router.py). Replicas come from --replicas (static)
    and/or coordinator membership discovery (`serve --fleet`
    self-registration). Health-aware, least-loaded + session-affine,
    hedging, brownout-shedding; with --health + a queue-wait SLO in
    health.slos the burn-rate alerts can drive the autoscaler
    (--autoscale + --replica-cmd). Deliberately jax-free — a router node
    needs no devices."""
    import dataclasses as _dc
    import time as _time

    from serverless_learn_tpu.fleet.router import FleetRouter
    from serverless_learn_tpu.utils.metrics import log_json

    _init_tracing_from_args(args)
    cfg = _config_from_args(args)
    fcfg = cfg.fleet
    if args.host:
        fcfg = _dc.replace(fcfg, router_host=args.host)
    if args.port is not None:
        fcfg = _dc.replace(fcfg, router_port=args.port)
    replicas = []
    for chunk in (args.replicas or []):
        replicas.extend(a for a in chunk.split(",") if a.strip())
    if not replicas and fcfg.replicas:
        replicas = [a for a in fcfg.replicas.split(",") if a.strip()]
    # Discovery runs when a coordinator is explicitly named (flag or
    # config file) — the ControlConfig default must not make a
    # static-list router dial a coordinator nobody started.
    coordinator = args.coordinator
    if coordinator is None and not replicas:
        coordinator = cfg.control.coordinator_addr
    exporter = _start_metrics(args)
    health = _start_health(args, cfg, exporter=exporter)
    router = FleetRouter(config=fcfg, replicas=tuple(replicas),
                         coordinator_addr=coordinator)
    scaler = None
    if args.autoscale or fcfg.autoscale:
        from serverless_learn_tpu.fleet.autoscaler import (FleetAutoscaler,
                                                           ProcessLauncher)

        if health is None:
            raise SystemExit(
                "--autoscale needs the health engine (--health + a "
                "queue-wait SLO in health.slos) for burn-rate alerts")
        if not args.replica_cmd:
            raise SystemExit(
                "--autoscale needs --replica-cmd 'slt serve --fleet ...' "
                "to launch replicas")
        import shlex

        launcher = ProcessLauncher(shlex.split(args.replica_cmd),
                                   baseline=len(replicas))
        scaler = FleetAutoscaler(
            launcher, lambda: health.alerts(firing_only=True),
            min_replicas=fcfg.min_replicas,
            max_replicas=fcfg.max_replicas,
            alert_substr=fcfg.alert_substr,
            scale_out_cooldown_s=fcfg.scale_out_cooldown_s,
            scale_in_cooldown_s=fcfg.scale_in_cooldown_s,
            scale_in_calm_s=fcfg.scale_in_calm_s).start()
    router.start()
    log_json({"event": "routing", "addr": router.addr,
              "service": fcfg.service,
              "replicas": [r["addr"] for r in router.replicas()],
              **({"coordinator": coordinator} if coordinator else {}),
              **({"autoscale": True} if scaler else {}),
              **({"metrics_addr": exporter.addr} if exporter else {})},
             stream=sys.stdout)
    try:
        while True:
            _time.sleep(0.5)
    except KeyboardInterrupt:
        pass
    finally:
        if scaler is not None:
            scaler.stop()
            launcher.stop_all()
        if health is not None:
            health.stop()
        router.stop()
        if exporter is not None:
            exporter.stop()
    return 0


def cmd_loadgen(args) -> int:
    """Closed/open-loop load generation (fleet/loadgen.py): Poisson,
    diurnal or flash-crowd arrivals against any JSON-lines serving
    address (a replica or the router), producing a latency-vs-offered-
    load curve. --record appends fleet_*_p99_ms rows to
    bench_history.json (gated by `slt bench --gate --metric fleet`).
    --smoke runs the self-contained 2-replica kill/restart proof (CI)."""
    from serverless_learn_tpu.fleet import loadgen

    if args.waterfall_smoke:
        # Round-21 acceptance run: a seeded continuous-engine workload
        # whose preemption (pool overflow) and mid-decode compile
        # (outgrown warm shapes) are injected BY CONSTRUCTION; exit 0
        # iff the waterfalls name both causes on the right requests,
        # the decompositions sum, the ledger overhead stays <2% and
        # `slt doctor` names the dominant stall cause from JSONL alone.
        rep = loadgen.run_waterfall_smoke(
            seed=args.seed,
            history_path=args.history if args.record else None)
        print(json.dumps(rep, indent=None if args.compact else 2))
        return 0 if rep["ok"] else 1
    if args.fleetscope_smoke:
        # Round-22 acceptance run: the redundancy is injected BY
        # CONSTRUCTION (one stub replica pre-warmed with the shared
        # prefix, least-loaded spreading the rest); exit 0 iff the
        # router's live counters + route_decision stream account it,
        # digests snapshot, and prefix-aware replay strictly beats the
        # recorded picks with byte-identical same-log reports.
        rep = loadgen.run_fleetscope_smoke(
            seed=args.seed,
            history_path=args.history if args.record else None)
        print(json.dumps(rep, indent=None if args.compact else 2))
        return 0 if rep["ok"] else 1
    if args.canary_smoke:
        # Round-23 acceptance run: a 2-version stub fleet with a 50%
        # session-sticky split and golden probes; exit 0 iff the healthy
        # leg promotes, the injected one-token quality regression flips
        # the verdict to rollback naming the fingerprint evidence,
        # probes stay out of the user latency SLIs, and the probe
        # overhead share is exported and bounded.
        rep = loadgen.run_canary_smoke(
            seed=args.seed,
            history_path=args.history if args.record else None)
        print(json.dumps(rep, indent=None if args.compact else 2))
        return 0 if rep["ok"] else 1
    if args.smoke:
        rep = loadgen.run_smoke(
            seed=args.seed, rate_rps=args.rate or 40.0,
            duration_s=args.duration or 6.0,
            history_path=args.history if args.record else None)
        out = dict(rep)
        out["alerts"] = [{"alert": a.get("alert"), "state": a.get("state")}
                         for a in rep.get("alerts", [])]
        print(json.dumps(out, indent=None if args.compact else 2))
        return 0 if rep["ok"] else 1
    if not args.addr:
        print("loadgen needs --addr HOST:PORT (or --smoke)",
              file=sys.stderr)
        return 2
    if args.mode == "closed":
        rep = loadgen.run_closed_loop(
            args.addr, concurrency=args.concurrency,
            n_requests=args.requests, seed=args.seed,
            timeout_s=args.timeout)
        print(json.dumps(rep, indent=None if args.compact else 2))
        return 0
    rates = ([float(r) for chunk in args.rates for r in chunk.split(",")
              if r.strip()] if args.rates else [args.rate or 10.0])
    points = loadgen.run_curve(
        args.addr, rates, args.duration or 10.0, seed=args.seed,
        arrival=args.arrival, timeout_s=args.timeout)
    rows = loadgen.bench_rows(points, label=args.label,
                              device_kind=args.device_kind)
    if args.record:
        loadgen.record_rows(rows, args.history)
    rep = {"mode": "open", "arrival": args.arrival, "points": points,
           "bench_rows": rows,
           "recorded": bool(args.record),
           "hard_failures": sum(p["hard_failures"] for p in points)}
    print(json.dumps(rep, indent=None if args.compact else 2))
    return 0 if rep["hard_failures"] == 0 else 1


def cmd_diloco(args) -> int:
    """Run one DiLoCo island: local inner steps, anchor-delta outer syncs
    through the coordinator + shard-server plane (training/diloco_dcn.py).
    Launch one per host/world; islands tolerate each other joining,
    crashing, and leading interchangeably."""
    from serverless_learn_tpu.data.datasets import SyntheticSource
    from serverless_learn_tpu.models.registry import get_model
    from serverless_learn_tpu.training.checkpoint import (
        LocalStore, ShardServerStore)
    from serverless_learn_tpu.training.diloco_dcn import DilocoIsland
    from serverless_learn_tpu.utils.metrics import log_json

    if not args.coordinator:
        raise SystemExit("diloco requires --coordinator")
    _init_tracing_from_args(args)
    cfg = _trainer_config(args)
    if args.store_dir:
        store = LocalStore(args.store_dir)
    elif args.shard_server:
        # The EXPLICIT flag, not cfg.control.shard_server_addr — that
        # config field has a non-empty default, which would silently
        # point the exchange at a server nobody asked for.
        store = ShardServerStore(args.shard_server)
    else:
        raise SystemExit("diloco requires --shard-server or --store-dir "
                         "for the anchor/delta exchange")
    bundle = get_model(cfg.model, **cfg.model_overrides)
    if not args.dataset:
        # --shard-server names the anchor/delta EXCHANGE plane here; only
        # stream training data from it when the user explicitly passes
        # --dataset (otherwise make_source would try to stream the
        # config's default dataset name from a server that's just a
        # blob store for this run).
        cfg = cfg.override(data=dataclasses.replace(
            cfg.data, shard_server_addr=""))

    def source_factory(wid):
        from serverless_learn_tpu.training.loop import make_source

        if cfg.data.shard_server_addr:
            return iter(make_source(cfg, island.trainer))
        # Synthetic default: distinct stream per island.
        return iter(SyntheticSource(bundle.make_batch, cfg.data,
                                    cfg.train.batch_size, seed=1000 + wid))

    island = DilocoIsland(
        cfg, store, args.coordinator, args.run_name,
        source_factory=source_factory,
        round_timeout_s=args.round_timeout_s,
        liveness_factor=args.liveness_factor)
    log_json({"event": "diloco_island_up", "run": args.run_name,
              "worker_id": island.agent.worker_id,
              "inner_steps": island.inner_steps}, stream=sys.stdout)
    exporter = _start_metrics(args)
    health = _start_health(args, cfg, exporter=exporter)
    try:
        rep = island.run_rounds(args.rounds)
    finally:
        if health is not None:
            health.stop()
        if exporter is not None:
            exporter.stop()
    log_json({"event": "diloco_island_done", "rounds": rep.rounds_done,
              "steps": rep.steps_done, "led_rounds": rep.led_rounds,
              "joined_at_round": rep.joined_at_round,
              "final_loss": rep.losses[-1] if rep.losses else None},
             stream=sys.stdout)
    return 0


def cmd_worker(args) -> int:
    """Elastic worker: register with the coordinator, train, re-mesh on
    membership changes — the successor of ``./worker ADDR``.

    Two elasticity scopes:
    * default: single-host — the worker trains alone and resizes over its
      own local devices on membership epochs (independent trainee).
    * ``--multihost RUN``: this host joins the named multi-host elastic
      run — all tagged hosts form ONE SPMD world that re-forms (via
      coordinated checkpoint-restart) as hosts join or die.
    """
    from serverless_learn_tpu.training.checkpoint import (
        LocalStore, ShardServerStore)
    from serverless_learn_tpu.utils.metrics import log_json

    if args.world_size or args.num_processes:
        raise SystemExit(
            "--world-size/--num-processes form a fixed multi-host group and "
            "apply to `train`; `worker` is elastic (it re-meshes on "
            "membership changes instead — see --multihost)")
    _init_tracing_from_args(args)
    cfg = _config_from_args(args)
    if (args.ckpt_cache_dir is not None or args.ckpt_peers is not None
            or args.ckpt_serve_cache):
        import dataclasses as _dc

        cfg = cfg.override(checkpoint=_dc.replace(
            cfg.checkpoint,
            cache_dir=(args.ckpt_cache_dir
                       if args.ckpt_cache_dir is not None
                       else cfg.checkpoint.cache_dir),
            peers=(args.ckpt_peers if args.ckpt_peers is not None
                   else cfg.checkpoint.peers),
            serve_cache=(args.ckpt_serve_cache
                         or cfg.checkpoint.serve_cache)))
    if args.checkpoint_store:
        store = ShardServerStore(args.checkpoint_store)
    elif args.checkpoint_dir:
        store = LocalStore(args.checkpoint_dir)
    else:
        store = ShardServerStore(cfg.control.shard_server_addr)

    exporter = _start_metrics(args)
    health = _start_health(args, cfg, exporter=exporter)
    try:
        if args.multihost:
            from serverless_learn_tpu.training.elastic_multihost import (
                ElasticHostSupervisor)

            sup = ElasticHostSupervisor(
                cfg, store,
                coordinator_addr=cfg.control.coordinator_addr,
                run_name=args.multihost,
                label=args.name or None,
                advertise_host=args.advertise_host,
                n_chips=args.chips,
                min_hosts=args.min_hosts,
                verbose=args.verbose,
            )
            gens = sup.run()
            log_json({"event": "worker_done", "multihost": args.multihost,
                      "generations": len(gens),
                      "final_step": gens[-1].end_step if gens else None},
                     stream=sys.stdout)
            return 0

        from serverless_learn_tpu.training.elastic import ElasticTrainer

        et = ElasticTrainer(
            cfg, store,
            coordinator_addr=cfg.control.coordinator_addr,
            advertise_addr=args.advertise,
            name=args.name or f"worker-{socket.gethostname()}-{os.getpid()}",
            verbose=args.verbose,
        )
        state, losses = et.run()
        log_json({"event": "worker_done", "steps": len(losses),
                  "final_loss": losses[-1] if losses else None,
                  "transitions": len(et.transitions)}, stream=sys.stdout)
    finally:
        if health is not None:
            health.stop()
        if exporter is not None:
            exporter.stop()
    return 0


def _exec_daemon(binary: str, argv: List[str]) -> int:
    from serverless_learn_tpu.control.client import _BIN

    path = os.path.join(_BIN, binary)
    os.execv(path, [path] + argv)  # replaces this process, like the reference


def cmd_coordinator(args) -> int:
    from serverless_learn_tpu.control.daemons import native_daemon_usable

    argv = ["--port", str(args.port),
            "--lease_ttl_ms", str(args.lease_ttl_ms),
            "--sweep_ms", str(args.sweep_ms)]
    if args.state_file:
        argv += ["--state_file", args.state_file]
    if args.events_log:
        argv += ["--events_log", args.events_log]
    if args.gossip or args.gossip_port is not None:
        # SWIM gossip seed (round 11): python-daemon only — the native
        # coordinator predates the gossip plane.
        argv += ["--gossip_port", str(args.gossip_port
                                      if args.gossip_port is not None
                                      else args.port + 1)]
        from serverless_learn_tpu.control.py_daemons import main_coordinator

        return main_coordinator(argv)
    if native_daemon_usable("coordinator"):
        return _exec_daemon("coordinator", argv)
    # Committed binaries can't run in this image (glibc/libprotobuf
    # mismatch) and there's no toolchain to rebuild: serve the same wire
    # protocol from the pure-Python twin instead of dying.
    from serverless_learn_tpu.control.py_daemons import main_coordinator

    return main_coordinator(argv)


def cmd_shard_server(args) -> int:
    from serverless_learn_tpu.control.daemons import native_daemon_usable

    argv = ["--port", str(args.port)]
    if args.root:
        argv += ["--root", args.root]
    if args.events_log:
        argv += ["--events_log", args.events_log]
    if native_daemon_usable("shard_server"):
        return _exec_daemon("shard_server", argv)
    from serverless_learn_tpu.control.py_daemons import main_shard_server

    return main_shard_server(argv)


def cmd_publish(args) -> int:
    from serverless_learn_tpu.config import DataConfig
    from serverless_learn_tpu.data.shard_client import (
        publish_dataset, publish_from_bundle)

    if args.format == "synthetic":
        from serverless_learn_tpu.models.registry import get_model

        if not args.model:
            raise SystemExit("--format synthetic requires --model")
        bundle = get_model(args.model)
        data_cfg = DataConfig(seq_len=args.seq_len)
        meta = publish_from_bundle(
            args.shard_server, args.dataset, bundle.make_batch, data_cfg,
            num_records=args.num_records,
            records_per_shard=args.records_per_shard or 512, seed=args.seed)
    else:
        from serverless_learn_tpu.data import raw

        if not args.path:
            raise SystemExit(f"--format {args.format} requires --path")
        if args.format == "tokens":
            arrays = raw.load_token_corpus(args.path, seq_len=args.seq_len)
        elif args.format == "text":
            # Real text ingestion: optional GPT-2-format BPE vocab (else
            # byte-level fallback), documents packed densely into rows
            # (data/tokenizer.py — round-4 verdict #8).
            from serverless_learn_tpu.data.tokenizer import load_text_corpus

            arrays = load_text_corpus(
                args.path, seq_len=args.seq_len, vocab_file=args.vocab,
                merges_file=args.merges)
        elif args.format == "imagefolder":
            # Streaming: decodes + uploads one shard at a time — an eager
            # decode of an ImageNet-sized split would need ~250 GB of RAM.
            # Default shard size follows the imagefolder recipe (256
            # records ~= 50 MB), not the generic 512.
            from serverless_learn_tpu.data.shard_client import (
                publish_imagefolder)

            meta = publish_imagefolder(
                args.shard_server, args.dataset, args.path, split=args.split,
                records_per_shard=args.records_per_shard or 256)
            arrays = None
        else:
            arrays = raw.LOADERS[args.format](args.path, split=args.split)
        if arrays is not None:
            meta = publish_dataset(args.shard_server, args.dataset, arrays,
                                   records_per_shard=args.records_per_shard
                                   or 512)
    print(json.dumps({"dataset": args.dataset,
                      "num_records": meta.num_records,
                      "num_shards": meta.num_shards,
                      "fields": [f.name for f in meta.fields]}))
    return 0


def cmd_stats(args) -> int:
    from serverless_learn_tpu.control.client import (
        CoordinatorClient, ShardClient)
    from serverless_learn_tpu.telemetry import publish_rpc_stats
    from serverless_learn_tpu.utils.tracing import rpc_stats

    cls = CoordinatorClient if args.kind == "coordinator" else ShardClient
    c = cls(args.addr)
    rep = c.stats()
    out = {"rpc": rpc_stats(rep)}
    # Mirror the scrape into the process registry as slt_rpc_* series so a
    # co-resident exporter (--metrics-port elsewhere in this process) and
    # `top` see daemon RPC latencies beside host metrics.
    publish_rpc_stats(out["rpc"], daemon=args.kind)
    if args.kind == "shard-server":
        out["bytes_served"] = rep.bytes_served
        out["bytes_stored"] = rep.bytes_stored
        out["active_streams"] = rep.active_streams
        out["crc_failures"] = rep.crc_failures
        out["throttled_chunks"] = rep.throttled_chunks
        out["starved_streams_served"] = rep.starved_streams_served
    c.close()
    print(json.dumps(out, indent=2))
    return 0


def cmd_trace(args) -> int:
    """Merge per-node span logs (--events-log JSONL, daemon --events_log,
    flight-recorder dumps) into one skew-corrected causal timeline: a
    Perfetto/chrome://tracing `trace_event` JSON plus a critical-path
    summary on stdout."""
    from serverless_learn_tpu.telemetry import timeline

    tl = timeline.reconstruct(args.logs, skew=not args.no_skew,
                              root=args.root)
    if args.trace_id:
        tl.spans = [s for s in tl.spans if s.trace_id == args.trace_id]
    if not tl.spans:
        print(json.dumps({"error": "no spans found in the given logs",
                          "skipped_records": tl.skipped}), file=sys.stderr)
        return 1
    if args.out:
        with open(args.out, "w") as f:
            json.dump(timeline.to_trace_events(tl), f)
    summary = timeline.summarize(tl, top=args.top)
    if args.out:
        summary["out"] = args.out
    print(json.dumps(summary, indent=None if args.compact else 2))
    return 0


def cmd_doctor(args) -> int:
    """Ranked cluster diagnosis: merge JSONL event logs, flight-recorder
    dumps, live /alerts scrapes and bench_history.json into one report —
    what fired, on which node, with correlated trace ids and cross-run
    perf regressions. Exit 0 = no critical alert firing, 1 = critical
    firing (or self-check failure) — scriptable as a gate."""
    from serverless_learn_tpu.telemetry import doctor

    if args.self_check:
        health_cfg = None
        if args.config:
            # Parse only the health section — doctor must run on nodes
            # with no devices (and never pay a jax import).
            from serverless_learn_tpu.config import ExperimentConfig

            with open(args.config) as f:
                health_cfg = ExperimentConfig.from_dict(
                    json.load(f)).health
        rep = doctor.self_check(health_cfg)
        print(json.dumps(rep, indent=None if args.compact else 2))
        return 0 if rep["ok"] else 1
    endpoints = []
    for chunk in args.endpoints or []:
        endpoints.extend(e for e in chunk.split(",") if e.strip())
    if not args.logs and not endpoints and not args.xray:
        print("doctor needs event logs/flight dumps, --endpoints and/or "
              "--xray (or --self-check)", file=sys.stderr)
        return 2
    rep = doctor.diagnose(args.logs, endpoints,
                          bench_history=args.bench_history, top=args.top,
                          xray_dirs=args.xray or [])
    print(json.dumps(rep, indent=None if args.compact else 2))
    return 1 if rep["summary"]["critical_firing"] else 0


def cmd_goodput(args) -> int:
    """Goodput/badput accounting report: per-phase wall-clock breakdown,
    productive fraction, MFU-weighted goodput. Live (`--endpoints` scrape
    of /goodput) or offline (`--from-events` / positional JSONL logs,
    aggregating the phase records every traced run emits). The phases —
    `unattributed` included — sum to the total run time by construction."""
    from serverless_learn_tpu.telemetry import goodput

    if args.self_check:
        rep = goodput.self_check()
        print(json.dumps(rep, indent=None if args.compact else 2))
        return 0 if rep["ok"] else 1
    endpoints = []
    for chunk in args.endpoints or []:
        endpoints.extend(e for e in chunk.split(",") if e.strip())
    logs = list(args.logs or []) + list(args.from_events or [])
    if not logs and not endpoints:
        print("goodput needs JSONL event logs (--from-events / positional) "
              "and/or --endpoints (or --self-check)", file=sys.stderr)
        return 2
    out: dict = {}
    if endpoints:
        from serverless_learn_tpu.telemetry.exporter import fetch_text

        scraped = {}
        for addr in endpoints:
            try:
                scraped[addr] = json.loads(fetch_text(addr, "/goodput"))
            except Exception as e:
                scraped[addr] = {"error": f"{type(e).__name__}: {e}"}
        out["endpoints"] = scraped
    if logs:
        from serverless_learn_tpu.telemetry import timeline

        records = timeline.load_events(logs)
        out["nodes"] = goodput.aggregate_events(records)
        if not out["nodes"]:
            out["warning"] = ("no phase records found — was the run "
                              "started with --events-log?")
    print(json.dumps(out, indent=None if args.compact else 2))
    return 0


def cmd_numerics(args) -> int:
    """Training-quality observability (telemetry/numerics.py):

    * ``slt numerics diff A B`` — bisect two recorded fingerprint trails
      (``--events-log`` JSONL, a dedicated ``numerics.fingerprint_log``,
      or a flight dump) to the FIRST step and the FIRST parameter
      subtree that diverged. Exit 1 when they diverged — scriptable as
      the parity gate ROADMAP items 1-2 need.
    * ``slt numerics summary LOG...`` — per-run stat digest: audited
      steps, grad-norm/update-ratio ranges, non-finite incidents with
      their provenance (first bad layer), replica divergence.
    * ``slt numerics --self-check`` — CI smoke: stat math exactness,
      seeded-NaN naming, seeded-divergence bisection, detector firing.
    """
    from serverless_learn_tpu.telemetry import numerics

    if args.self_check:
        rep = numerics.self_check()
        print(json.dumps(rep, indent=None if args.compact else 2))
        return 0 if rep["ok"] else 1
    if args.action == "diff":
        if len(args.paths) != 2:
            print("numerics diff needs exactly two fingerprint trails",
                  file=sys.stderr)
            return 2
        rep = numerics.diff_fingerprint_logs(
            numerics.load_records(args.paths[0]),
            numerics.load_records(args.paths[1]),
            rtol=args.rtol, atol=args.atol)
        # The diff's own "a"/"b" carry the divergent digest values, so
        # the trail labels get distinct keys.
        rep = {"log_a": args.paths[0], "log_b": args.paths[1], **rep}
        print(json.dumps(rep, indent=None if args.compact else 2))
        return 1 if rep.get("diverged") else 0
    if args.action == "summary":
        if not args.paths:
            print("numerics summary needs JSONL event logs",
                  file=sys.stderr)
            return 2
        records = []
        for path in args.paths:
            records.extend(numerics.load_records(path))
        stats = [r for r in records if r.get("event") == "numerics_stats"]
        bad = [r for r in records
               if r.get("event") == "numerics_nonfinite"]
        out = {"records": len(records), "audited_steps": len(stats),
               "steps": [r.get("step") for r in stats[:3]]
               + (["..."] if len(stats) > 6 else [])
               + [r.get("step") for r in stats[-3:]]
               if stats else [],
               "nonfinite_incidents": [
                   {"step": r.get("step"), "first": r.get("first"),
                    "bad_subtrees": r.get("bad_subtrees")}
                   for r in bad]}
        if stats:
            gnorms = [r["grad_norm"] for r in stats
                      if isinstance(r.get("grad_norm"), (int, float))]
            ratios = [r["update_ratio"] for r in stats
                      if isinstance(r.get("update_ratio"), (int, float))]
            if gnorms:
                out["grad_norm"] = {"min": round(min(gnorms), 6),
                                    "max": round(max(gnorms), 6),
                                    "last": round(gnorms[-1], 6)}
            if ratios:
                out["update_ratio"] = {"min": round(min(ratios), 9),
                                       "max": round(max(ratios), 9),
                                       "last": round(ratios[-1], 9)}
        print(json.dumps(out, indent=None if args.compact else 2))
        return 1 if bad else 0
    print("numerics needs an action (diff | summary) or --self-check",
          file=sys.stderr)
    return 2


def cmd_profile(args) -> int:
    """Trigger an on-demand device-trace capture on a live node through
    its metrics endpoint (/debug/profile — armed by --profile-dir on any
    role). Prints the capture reply (output directory, seconds)."""
    from serverless_learn_tpu.telemetry.exporter import fetch_text

    try:
        rep = json.loads(fetch_text(
            args.endpoint, f"/debug/profile?seconds={args.seconds:g}",
            timeout=args.seconds + 30.0))
    except Exception as e:
        print(json.dumps({"ok": False,
                          "error": f"{type(e).__name__}: {e}",
                          "endpoint": args.endpoint}), file=sys.stderr)
        return 1
    print(json.dumps(rep, indent=2))
    return 0 if rep.get("ok") else 1


def cmd_xray(args) -> int:
    """Step-interior hardware attribution from an XLA device trace
    (telemetry/xray.py): classify device events (compute / collective /
    copy / host), compute exposed-collective and idle time per step,
    roofline verdicts for costed ops, HBM watermarks — and one verdict
    sentence naming where the step's hardware time went."""
    from serverless_learn_tpu.telemetry import xray

    if args.self_check:
        rep = xray.self_check()
        print(json.dumps(rep, indent=None if args.compact else 2))
        return 0 if rep["ok"] else 1
    if not args.captures:
        print("xray needs capture dirs (profiler out_dirs / jax.profiler "
              "logdirs) or --self-check", file=sys.stderr)
        return 2
    out = {}
    ok = True
    for path in args.captures:
        try:
            summary = xray.analyze_dir(path,
                                       device_kind=args.device_kind)
            if not args.full:
                # The per-step list can be long on a dense capture; the
                # default report keeps the first/last few.
                steps = summary.get("steps") or {}
                per = steps.get("per_step") or []
                if len(per) > 2 * args.top:
                    steps["per_step"] = per[:args.top] + per[-args.top:]
                    steps["per_step_truncated"] = len(per)
            out[path] = summary
        except (FileNotFoundError, OSError, ValueError) as e:
            out[path] = {"error": f"{type(e).__name__}: {e}"}
            ok = False
    print(json.dumps(out if len(out) > 1 else next(iter(out.values())),
                     indent=None if args.compact else 2))
    return 0 if ok else 1


def cmd_waterfall(args) -> int:
    """Per-request lifecycle waterfalls (telemetry/waterfall.py): merge
    engine request-span records (each carrying the per-request ledger)
    with router ``waterfall_hop`` records by trace_id, then print the
    percentile decompositions — TTFT p99 = queue + admit + compile +
    prefill, ITL p99 with the stall-cause breakdown — plus phase bars
    for the slowest requests. Exit 1 when a decomposition invariant is
    violated (the ledger itself is lying)."""
    from serverless_learn_tpu.telemetry import waterfall

    if args.self_check:
        rep = waterfall.self_check(fixture_path=args.fixture)
        print(json.dumps(rep, indent=None if args.compact else 2))
        return 0 if rep["ok"] else 1
    if not args.paths:
        print("waterfall needs engine/router event logs (--events-log "
              "JSONL, flight-recorder dumps, or dirs of them) or "
              "--self-check", file=sys.stderr)
        return 2
    try:
        rep = waterfall.report(args.paths, top=args.top)
    except (FileNotFoundError, OSError, ValueError) as e:
        print(f"waterfall: {type(e).__name__}: {e}", file=sys.stderr)
        return 2
    if args.bench_history:
        from serverless_learn_tpu.utils.benchlog import record

        for row in waterfall.bench_rows(rep["summary"],
                                        device_kind=args.device_kind):
            record(row, args.bench_history, better="min",
                   rel_threshold=0.25,
                   key_fields=("metric", "device_kind"))
    if args.json:
        print(json.dumps(rep, indent=None if args.compact else 2))
    else:
        print(waterfall.render(rep))
    inv = rep.get("summary", {}).get("invariants") or {}
    bad = (inv.get("ttft_decomp_bad") or 0) + (inv.get("stall_sum_bad")
                                               or 0)
    return 1 if bad else 0


def cmd_fleetscope(args) -> int:
    """Fleet-wide KV/prefix redundancy accounting + counterfactual
    routing replay (telemetry/fleetscope.py): merge router
    ``route_decision`` events, ``fleet_digest`` snapshots and the
    round-21 request waterfalls, then print the redundancy accounting
    (redundant-prefill fraction, residency-spread histogram, affinity
    effectiveness) and the deterministic policy replay — recorded vs
    least-loaded vs prefix-aware vs prefill/decode split — with the
    TTFT-p99 bound and prefill-compute savings."""
    from serverless_learn_tpu.telemetry import fleetscope

    if args.self_check:
        rep = fleetscope.self_check(fixture_path=args.fixture)
        print(json.dumps(rep, indent=None if args.compact else 2))
        return 0 if rep["ok"] else 1
    if not args.paths:
        print("fleetscope needs router event logs (--events-log JSONL "
              "with route_decision records, or dirs of them) or "
              "--self-check", file=sys.stderr)
        return 2
    try:
        rep = fleetscope.report(args.paths)
    except (FileNotFoundError, OSError, ValueError) as e:
        print(f"fleetscope: {type(e).__name__}: {e}", file=sys.stderr)
        return 2
    if args.bench_history:
        from serverless_learn_tpu.utils.benchlog import record

        for row in fleetscope.bench_rows(rep,
                                         device_kind=args.device_kind):
            record(row, args.bench_history, better="min",
                   rel_threshold=0.25,
                   key_fields=("metric", "device_kind"))
    if args.json:
        print(json.dumps(rep, sort_keys=True,
                         indent=None if args.compact else 2))
    else:
        print(fleetscope.render(rep))
    return 0 if rep["summary"]["primary_decisions"] > 0 else 1


def cmd_canary(args) -> int:
    """Version-scoped serving SLIs + the promote/hold/rollback verdict
    engine (telemetry/canary.py): merge ``fleet_version`` /
    ``canary_config`` / ``canary_probe`` / ``route_decision`` records
    from router event logs into per-weight-version SLIs (probe traffic
    excluded), then print the deterministic verdict with its named
    evidence. Exit 0 on promote/hold, 1 on rollback — scriptable as a
    deployment gate."""
    from serverless_learn_tpu.telemetry import canary

    if args.self_check:
        rep = canary.self_check(fixture_path=args.fixture)
        print(json.dumps(rep, indent=None if args.compact else 2))
        return 0 if rep["ok"] else 1
    if not args.paths:
        print("canary needs router event logs (--events-log JSONL with "
              "fleet_version/canary_probe/route_decision records, or "
              "dirs of them) or --self-check", file=sys.stderr)
        return 2
    try:
        rep = canary.report(args.paths)
    except (FileNotFoundError, OSError, ValueError) as e:
        print(f"canary: {type(e).__name__}: {e}", file=sys.stderr)
        return 2
    if not rep["records"]:
        # read_records tolerates missing/garbled files (doctor's rules);
        # a verdict over ZERO records would be a vacuous "hold" — a gate
        # pointed at the wrong log must fail loudly instead.
        print(f"canary: no records in {', '.join(args.paths)}",
              file=sys.stderr)
        return 2
    if args.bench_history:
        from serverless_learn_tpu.utils.benchlog import record

        for row in canary.bench_rows(rep, device_kind=args.device_kind):
            record(row, args.bench_history, better="min",
                   rel_threshold=0.25,
                   key_fields=("metric", "device_kind"))
    if args.json:
        print(json.dumps(rep, sort_keys=True,
                         indent=None if args.compact else 2))
    else:
        print(canary.render(rep))
    return 1 if rep["verdict"]["decision"] == "rollback" else 0


def cmd_bench(args) -> int:
    """Headline benchmark + the perf regression gate. `--gate` compares
    against bench_history.json with the noise-aware threshold
    (telemetry/benchgate.py) and exits 1 on regression — the CI loop
    from measurement to enforcement. `--dry-run` skips the measurement
    and gates the committed history's latest entries (no device needed)."""
    from serverless_learn_tpu.telemetry import benchgate

    history = args.history or "bench_history.json"
    entry = None
    if not args.dry_run:
        # A real measurement: reuse bench.py's headline measure() (the
        # repo-root module — run from a checkout) and record through the
        # shared history guard, then gate the fresh entry against
        # everything before it.
        try:
            import bench as bench_mod
        except ImportError:
            print("bench.py not importable (run from the repo root), or "
                  "use --dry-run to gate the committed history",
                  file=sys.stderr)
            return 2
        from serverless_learn_tpu.utils.benchlog import record

        entry = bench_mod.measure()
        bench_mod.write_run_bundle(entry, history)
        record(entry, history, better="max", rel_threshold=args.threshold,
               key_fields=("metric", "device_kind", "batch_per_chip"))
    # Default scope: the headline series (bench.py's own guard keys).
    # The ladder's multi-mode rows carry record-time flags and documented
    # shared-chip variance; gate them deliberately via --metric, or
    # sweep everything report-style via --all.
    metric = None if args.all else (args.metric
                                    or benchgate.HEADLINE_METRIC)
    rep = benchgate.run_gate(history, entry=entry,
                             rel_threshold=args.threshold,
                             metric=metric)
    if getattr(args, "attribute", False) and not rep.get("ok") \
            and rep.get("regressions"):
        # Round 24: a failed gate names its cause. Attribution compares
        # the failing row against the best-passing comparable row — via
        # their RunBundles when both carry `bundle` pointers, via the
        # row-level attribution columns otherwise — and never raises
        # (the gate must keep gating even over pre-bundle history).
        from serverless_learn_tpu.telemetry import regress
        from serverless_learn_tpu.utils.benchlog import load_history

        rep["attribution"] = regress.attribute_gate_failures(
            rep, load_history(history),
            history_dir=os.path.dirname(os.path.abspath(history)))
    print(json.dumps(rep, indent=None if args.compact else 2))
    for a in rep.get("attribution") or []:
        cause = a.get("dominant") or a.get("note") or a.get("error") \
            or "no attribution available"
        print(f"gate FAILED ({a.get('metric')}): {cause}",
              file=sys.stderr)
    if not args.gate:
        return 0
    return 0 if rep.get("ok") else 1


def cmd_regress(args) -> int:
    """Cross-run differential attribution: compare two RunBundles and
    decompose the headline delta along every ledger that covers it —
    goodput phases, xray step interiors, waterfall TTFT/stalls, DCN
    wire bytes, config drift, numerics bisection — each decomposition
    machine-checked to sum to its headline delta (telemetry/regress.py).
    Byte-identical report on identical inputs; exit 1 when a sum
    invariant fails (the ledgers disagree about the same run — a
    telemetry bug worth failing on)."""
    from serverless_learn_tpu.telemetry import regress

    if args.self_check:
        rep = regress.self_check(fixture_dir=args.fixture)
        print(json.dumps(rep, sort_keys=True,
                         indent=None if args.compact else 2))
        return 0 if rep["ok"] else 1
    if not args.run_a or not args.run_b:
        print("usage: slt regress RUN_A RUN_B (bundle dirs or run.json "
              "paths), or slt regress --self-check", file=sys.stderr)
        return 2
    try:
        bundle_a = regress.RunBundle.load(args.run_a)
        bundle_b = regress.RunBundle.load(args.run_b)
    except (IOError, OSError, ValueError) as e:
        print(f"regress: cannot load bundle: {e}", file=sys.stderr)
        return 2
    rep = regress.compare(bundle_a, bundle_b, metric=args.metric,
                          tolerance=args.tolerance)
    if args.json:
        print(json.dumps(rep, sort_keys=True,
                         indent=None if args.compact else 2))
    else:
        print(regress.render(rep))
    return 0 if rep["invariants"]["ok"] else 1


def cmd_check(args) -> int:
    """Project-aware static analysis (serverless_learn_tpu/analysis/):
    lock-order + blocking-under-lock (SLT001), metric-name drift (SLT002),
    jit purity (SLT003), thread lifecycle (SLT004), wire-protocol compat
    (SLT005), config-schema drift (SLT006). Exit 0 = no finding beyond
    the committed baseline; `--update-baseline` rewrites it (every entry
    then needs a reviewed justification). Deliberately jax-free so it
    runs on toolchain-less CI nodes and from native/Makefile."""
    from serverless_learn_tpu.analysis import run_check
    from serverless_learn_tpu.analysis.rules import TITLES

    if args.list_rules:
        for rid in sorted(TITLES):
            print(f"{rid}  {TITLES[rid]}")
        return 0
    root = args.root
    if root is None:
        # Default to the checkout containing this package, so `slt check`
        # works from any cwd.
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        rep = run_check(root, rule_ids=args.rule or None,
                        baseline_path=args.baseline,
                        update_baseline=args.update_baseline,
                        changed_only=args.changed_only)
    except ValueError as e:
        raise SystemExit(str(e))
    if args.json:
        print(json.dumps(rep, indent=None if args.compact else 2))
    else:
        for f in rep["findings"]:
            loc = f"{f['path']}:{f['line']}" if f["line"] else f["path"]
            print(f"{loc}: {f['rule']} [{f['severity']}] {f['message']}")
        c = rep["counts"]
        scope = " (changed files only)" if rep.get("changed_only") else ""
        print(f"slt check: {c['new']} finding(s), {c['baselined']} "
              f"baselined, {rep['files_scanned']} files{scope} "
              f"({', '.join(rep['rules'])})")
        if c["stale_baseline_entries"]:
            print(f"note: {c['stale_baseline_entries']} stale baseline "
                  f"entr{'y' if c['stale_baseline_entries'] == 1 else 'ies'}"
                  f" no longer match any finding (run --update-baseline)")
    return 0 if rep["ok"] else 1


def cmd_race(args) -> int:
    """Offline happens-before replay (analysis/racecheck.py): rebuild
    the vector-clock order from a JSONL event log recorded under
    ``SLT_RACECHECK=1 SLT_RACECHECK_LOG=path`` and re-run the race
    check deterministically. Exit 0 = no unordered conflicting access
    beyond the allowlist; 2 = races found. The live monitor already
    failed the recording session — this command is for triage: the same
    log replays to the same verdict every time, with both stacks."""
    from serverless_learn_tpu.analysis import racecheck

    try:
        mon = racecheck.replay_log(args.log)
    except OSError as e:
        raise SystemExit(f"cannot read {args.log}: {e}")
    races = mon.races(include_allowlisted=args.include_allowlisted)
    if args.json:
        print(json.dumps({"log": args.log, "races": races,
                          "ok": not mon.races()}, indent=2))
    else:
        print(mon.report())
        if args.include_allowlisted:
            for r in mon.races(include_allowlisted=True):
                if r["allowlisted"]:
                    just = racecheck.ALLOWLIST.get(
                        (r["class"], r["attr"]), "")
                    print(f"  allowlisted: {r['class']}.{r['attr']} "
                          f"({r['kind']}) — {just}")
    return 0 if not mon.races() else 2


def cmd_jit(args) -> int:
    """Offline compile-log replay (analysis/jitcheck.py): rebuild
    budgets, freeze/thaw nesting and per-jit compile counts from a JSONL
    log recorded under ``SLT_JITCHECK=1 SLT_JITCHECK_LOG=path`` and
    re-derive the verdicts deterministically. Exit 0 = every compile
    within budget, none frozen, no donated-buffer reuse; 2 =
    violations. ``--self-check`` validates the verdict engine itself
    against synthetic seeded logs (the CI step that proves the detector
    detects). jax-free: a toolchain-less node can audit a log a TPU run
    produced."""
    from serverless_learn_tpu.analysis import jitcheck

    if args.self_check:
        failures = jitcheck.self_check()
        if failures:
            for f in failures:
                print(f"self-check FAILED: {f}", file=sys.stderr)
            return 2
        print("slt jit --self-check: verdict engine OK (clean log "
              "passes; budget/frozen/donation-reuse each convict)")
        return 0
    if not args.log:
        print("usage: slt jit LOG (or --self-check)", file=sys.stderr)
        return 2
    try:
        rep = jitcheck.replay_log(args.log)
    except OSError as e:
        raise SystemExit(f"cannot read {args.log}: {e}")
    if args.json:
        print(json.dumps({"log": args.log, "compiles": rep["compiles"],
                          "sites": rep["sites"],
                          "violations": rep["violations"],
                          "ok": not rep["violations"]}, indent=2))
    else:
        print(f"slt jit: {rep['compiles']} compile(s) across "
              f"{len(rep['sites'])} site(s), "
              f"{len(rep['violations'])} violation(s) "
              f"[{rep['events']} events]")
        for site, n in sorted(rep["sites"].items()):
            print(f"  {site}: {n} compile(s)")
        for v in rep["violations"]:
            print(f"  VIOLATION [{v['kind']}] {v.get('site', '?')}"
                  + (f" (budget {v['budget']}, compiled {v['n']}x)"
                     if v["kind"] == "budget" else "")
                  + (f" in frozen window {v.get('label')!r}"
                     if v["kind"] == "frozen" else ""))
            for fr in v.get("stack", [])[-5:]:
                print(f"    {fr}")
    return 0 if not rep["violations"] else 2


def cmd_chaos(args) -> int:
    """Deterministic chaos harness over the SWIM gossip membership
    (chaos/sim.py): `run` executes a FaultPlan (kills, restarts,
    partitions, stragglers, skew) against N simulated members on virtual
    time; `soak` generates a seeded random schedule. Exit 0 iff every
    convergence/progress invariant held. Deliberately jax-free — a
    2-minute 50-node soak runs in seconds on a CPU-only CI node."""
    from serverless_learn_tpu.chaos.plan import FaultPlan
    from serverless_learn_tpu.chaos.sim import ChaosSim
    from serverless_learn_tpu.control.gossip import GossipConfig

    if args.mode == "recover":
        # Crash/recovery proof over the REAL checkpoint stack
        # (chaos/recover.py): kills mid-run and mid-save, checkpoint
        # corruption, store partitions — asserts bounded RPO, measures
        # RTO, and emits doctor-attributable telemetry.
        from serverless_learn_tpu.chaos.recover import RecoveryRun

        plan = None
        if args.plan:
            try:
                with open(args.plan) as f:
                    plan = FaultPlan.from_json(f.read())
            except (OSError, ValueError) as e:
                print(f"bad fault plan: {e}", file=sys.stderr)
                return 2
        events_log = args.events_log
        smoke_tmp = None
        if args.smoke and not events_log:
            # The smoke's doctor-attribution half needs an event trail.
            import tempfile

            fd, smoke_tmp = tempfile.mkstemp(prefix="slt-recover-smoke-",
                                             suffix=".jsonl")
            os.close(fd)
            events_log = smoke_tmp
        try:
            run = RecoveryRun(
                seed=args.seed, steps=args.steps,
                checkpoint_every=args.ckpt_every, plan=plan,
                events_log=events_log,
                store_latency_s=args.store_latency_ms / 1000.0,
                peer_cache=not args.no_peer_cache)
        except ValueError as e:
            print(f"bad recover plan: {e}", file=sys.stderr)
            return 2
        rep = run.run()
        if args.smoke:
            # Self-contained CI proof: the default plan already kills
            # mid-run AND mid-save, corrupts a checkpoint and partitions
            # the store; on top of the harness's own RPO/garbage
            # invariants, require that doctor NAMES the recoveries and
            # the corruption from the events log alone.
            from serverless_learn_tpu.telemetry.doctor import diagnose

            verdict = diagnose(paths=[events_log])["summary"]["verdict"]
            rep["doctor_verdict"] = verdict
            if "recovery incident" not in verdict:
                rep["ok"] = False
                rep["violations"].append(
                    "doctor failed to name the recovery incidents")
            if not rep["incidents"]:
                rep["ok"] = False
                rep["violations"].append("smoke plan injected no incidents")
            if "corruption detected" not in verdict:
                rep["ok"] = False
                rep["violations"].append(
                    "doctor failed to name the checkpoint corruption")
            if smoke_tmp is not None:
                try:
                    os.remove(smoke_tmp)
                except OSError:
                    pass
        if not args.full:
            rep = dict(rep)
            rep["incidents"] = len(rep["incidents"])
        print(json.dumps(rep, indent=None if args.compact else 2))
        return 0 if rep["ok"] else 1

    if args.mode == "herd":
        # Vmapped many-client DiLoCo herd (training/herd.py): N real
        # tiny-model workers, non-IID shards, speed skew, FaultPlan
        # churn on the gossip simulator's event heap, quorum
        # participation + delta quarantine. Needs jax (the one chaos
        # mode that does) — imported here so run/soak/fleet stay
        # jax-free.
        from serverless_learn_tpu.training.herd import (HerdSim, HerdSpec,
                                                        run_smoke,
                                                        run_wire_ab)

        if args.wire_ab:
            # Round 20: quantized-vs-f32 loss parity under churn, with a
            # no-error-feedback negative control (training/wire_codec.py
            # through the vmapped herd). Exit 1 unless parity holds AND
            # wire bytes shrink >= 3.5x.
            dtype = args.wire_dtype or "int8"
            if dtype in ("f32", "float32"):
                print("--wire-ab compares a quantized leg against f32; "
                      "pass --wire-dtype int8|fp8", file=sys.stderr)
                return 2
            try:
                rep = run_wire_ab(workers=args.workers or 48,
                                  seed=args.seed, wire_dtype=dtype)
            except ValueError as e:
                print(f"bad wire A/B: {e}", file=sys.stderr)
                return 2
            if args.record and args.history:
                from serverless_learn_tpu.utils.benchlog import record

                for leg, wire in (("f32", "float32"),
                                  ("quant", rep["wire_dtype"])):
                    wait = rep["mean_round_wait_s"][leg]
                    if wait is None:
                        continue
                    record({
                        "metric": "herd_diloco_round_wait_ms",
                        "value": round(wait * 1e3, 2),
                        "unit": "virtual ms/round",
                        "device_kind": "herd-sim-cpu",
                        "batch_per_chip": 4,
                        "wire_dtype": wire,
                        "workers": rep["workers"],
                        "diloco_round_wait_s": wait,
                        "dcn_bytes_per_round":
                            rep["bytes_per_round"][leg],
                    }, args.history, better="min",
                        key_fields=("metric", "device_kind",
                                    "batch_per_chip"))
            print(json.dumps(rep, indent=None if args.compact else 2))
            return 0 if rep["ok"] else 1

        if args.smoke:
            import tempfile

            events_log, smoke_tmp = args.events_log, None
            if not events_log:
                fd, smoke_tmp = tempfile.mkstemp(
                    prefix="slt-herd-smoke-", suffix=".jsonl")
                os.close(fd)
                events_log = smoke_tmp
            workers = args.workers or 48
            rep = run_smoke(workers=workers, seed=args.seed,
                            events_log=events_log)
            # Doctor must name the quarantined worker and the partial
            # participation from the events log ALONE.
            from serverless_learn_tpu.telemetry.doctor import diagnose

            verdict = diagnose(paths=[events_log])["summary"]["verdict"]
            rep["doctor_verdict"] = verdict
            poisoned = str(workers - 3)
            if "quarantin" not in verdict or poisoned not in verdict:
                rep["ok"] = False
                rep["violations"].append(
                    f"doctor failed to name quarantined worker "
                    f"{poisoned} from the events log")
            if "participation" not in verdict:
                rep["ok"] = False
                rep["violations"].append(
                    "doctor failed to name the partial participation")
            if smoke_tmp is not None:
                try:
                    os.remove(smoke_tmp)
                except OSError:
                    pass
        else:
            plan = None
            if args.plan:
                try:
                    with open(args.plan) as f:
                        plan = FaultPlan.from_json(f.read())
                except (OSError, ValueError) as e:
                    print(f"bad fault plan: {e}", file=sys.stderr)
                    return 2
            try:
                spec = HerdSpec(
                    n_workers=args.workers or 256, rounds=args.rounds,
                    inner_steps=args.inner_steps,
                    quorum_fraction=args.quorum,
                    late_policy=args.late_policy,
                    poison_worker=args.poison_worker,
                    poison_round=args.poison_round,
                    wire_dtype=args.wire_dtype or "float32")
                sim = HerdSim(spec, seed=args.seed, plan=plan,
                              events_log=args.events_log)
            except ValueError as e:
                print(f"bad herd spec: {e}", file=sys.stderr)
                return 2
            rep = sim.run(args.duration)
        if not args.full:
            rep = dict(rep)
            rep["faults_injected"] = len(rep["faults_injected"])
            det = [v for v in rep["detection_periods"].values()
                   if v is not None]
            rep["detection_periods"] = {
                "n": len(rep["detection_periods"]),
                "max": max(det) if det else None}
        print(json.dumps(rep, indent=None if args.compact else 2))
        return 0 if rep["ok"] else 1

    if args.mode == "fleet":
        # Real-socket fleet chaos (chaos/fleet.py): stub replicas behind
        # TcpChaosProxy, a live router, open-loop load, REAL seconds.
        # Default plan: kill one replica, restart it later — the doctor
        # acceptance shape.
        from serverless_learn_tpu.chaos.fleet import FleetChaosRun

        if args.plan:
            try:
                with open(args.plan) as f:
                    plan = FaultPlan.from_json(f.read())
            except (OSError, ValueError) as e:
                print(f"bad fault plan: {e}", file=sys.stderr)
                return 2
        else:
            plan = FaultPlan.from_obj({"faults": [
                {"at": 0.8, "op": "kill", "node": "replica-0"},
                {"at": 2.4, "op": "restart", "node": "replica-0"}]})
        try:
            run = FleetChaosRun(n_replicas=min(args.nodes, 16), plan=plan,
                                seed=args.seed,
                                events_log=args.events_log)
        except ValueError as e:
            print(f"bad fleet plan: {e}", file=sys.stderr)
            return 2
        rep = run.run(args.duration)
        if not args.full:
            rep = dict(rep)
            rep["faults_injected"] = len(rep["faults_injected"])
        print(json.dumps(rep, indent=None if args.compact else 2))
        return 0 if rep["ok"] else 1

    gossip = GossipConfig(
        protocol_period_s=args.period_ms / 1000.0,
        ping_timeout_s=args.period_ms / 1000.0 * 0.3)
    if args.mode == "run":
        if not args.plan:
            print("chaos run needs --plan FILE.json (see chaos/plan.py "
                  "for the DSL)", file=sys.stderr)
            return 2
        try:
            with open(args.plan) as f:
                plan = FaultPlan.from_json(f.read())
        except (OSError, ValueError) as e:
            print(f"bad fault plan: {e}", file=sys.stderr)
            return 2
    else:  # soak
        import random as random_mod

        plan = FaultPlan.random_soak(
            args.nodes, args.duration or 120.0,
            random_mod.Random(f"soak-{args.seed}"))
    sim = ChaosSim(args.nodes, seed=args.seed, plan=plan,
                   gossip=gossip, events_log=args.events_log)
    rep = sim.run(args.duration)
    if not args.full:
        rep = dict(rep)
        rep["faults_injected"] = len(rep["faults_injected"])
        det = [v for v in rep["detection_periods"].values()
               if v is not None]
        rep["detection_periods"] = {
            "n": len(rep["detection_periods"]),
            "max": max(det) if det else None}
    print(json.dumps(rep, indent=None if args.compact else 2))
    return 0 if rep["ok"] else 1


def cmd_top(args) -> int:
    """Live cluster telemetry: poll /metrics endpoints, render one screen
    (per-worker throughput, inference latency percentiles, membership)."""
    from serverless_learn_tpu.telemetry.top import run_top

    endpoints = []
    for chunk in args.endpoints:
        endpoints.extend(e for e in chunk.split(",") if e.strip())
    return run_top(endpoints, interval_s=args.interval, once=args.once)


def cmd_models(args) -> int:
    from serverless_learn_tpu.models.registry import list_models

    for name in list_models():
        print(name)
    return 0


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="serverless_learn_tpu",
        description="TPU-native elastic training framework")
    sub = p.add_subparsers(dest="cmd", required=True)

    t = sub.add_parser("train", help="run a training job on local devices")
    _add_train_flags(t)
    t.add_argument("--run-bundle", metavar="DIR", default=None,
                   help="stamp this run's RunBundle manifest (run.json: "
                        "events/fingerprint logs, xray summary, config "
                        "+ git/weight fingerprints, goodput) into DIR "
                        "for `slt regress` cross-run attribution")
    t.set_defaults(fn=cmd_train)

    e = sub.add_parser("eval", help="forward-only eval (optionally from ckpt)")
    _add_train_flags(e)
    e.add_argument("--eval-steps", type=int, default=None,
                   help="eval batches (default: train.eval_steps)")
    e.set_defaults(fn=cmd_eval)

    g = sub.add_parser("generate", help="sample tokens from a causal LM")
    _add_train_flags(g)
    g.add_argument("--prompt", help="comma-separated prompt token ids")
    g.add_argument("--prompt-len", type=int, default=8,
                   help="random prompt length when --prompt is unset")
    g.add_argument("--max-new-tokens", type=int, default=32)
    g.add_argument("--temperature", type=float, default=0.0)
    g.add_argument("--top-k", type=int, default=0)
    g.add_argument("--eos-id", type=int, default=None)
    g.add_argument("--seed", type=int, default=0)
    g.add_argument("--quant", choices=["int8"], default=None,
                   help="weight-only quantization: restore the trained "
                        "checkpoint, then store projections int8 + scale "
                        "(half the decode HBM traffic)")
    g.add_argument("--draft-layers", type=int, default=0,
                   help="speculative decoding: draft with the target's "
                        "own first N layers, verify K drafts in one "
                        "target pass (greedy-exact; speedup tracks "
                        "draft/target agreement)")
    g.add_argument("--spec-k", type=int, default=4,
                   help="drafted tokens per verify pass (--draft-layers)")
    g.set_defaults(fn=cmd_generate)

    sv = sub.add_parser("serve", help="serve LM generation over TCP (JSON lines)")
    _add_train_flags(sv)
    sv.add_argument("--host", default="127.0.0.1",
                    help="bind address (0.0.0.0 to accept remote clients)")
    sv.add_argument("--port", type=int, default=50060)
    sv.add_argument("--max-batch", type=int, default=8,
                    help="decode slots: concurrent requests sharing one "
                         "device batch")
    sv.add_argument("--quant", choices=["int8"], default=None,
                    help="weight-only int8 serving (see generate --quant)")
    sv.add_argument("--chunk-size", type=int, default=32,
                    help="decode tokens per jitted chunk between admission "
                         "boundaries")
    sv.add_argument("--kv-block-size", type=int, default=None,
                    help="KV pool: tokens per block (config kv.block_size)")
    sv.add_argument("--kv-num-blocks", type=int, default=None,
                    help="KV pool: blocks per layer; 0 = auto "
                         "no-overcommit sizing (config kv.num_blocks)")
    sv.add_argument("--prefill-chunk", type=int, default=None,
                    help="KV pool: prompt tokens per prefill chunk "
                         "interleaved between decode boundaries "
                         "(config kv.prefill_chunk; 0 = whole prompt)")
    sv.add_argument("--no-prefix-cache", action="store_true",
                    help="disable shared-prefix block reuse "
                         "(config kv.prefix_cache)")
    sv.add_argument("--fleet", nargs="?", const="serve", default=None,
                    metavar="SERVICE",
                    help="join the serving fleet: register with the "
                         "coordinator (control.coordinator_addr) as "
                         "replica:<SERVICE> at startup so `slt route` "
                         "discovers this replica, and deregister + drain "
                         "in-flight requests on SIGTERM (default service "
                         "name: serve)")
    sv.add_argument("--drain-grace-s", type=float, default=None,
                    help="with --fleet: max seconds to wait for in-flight "
                         "requests on SIGTERM (default: config "
                         "fleet.drain_grace_s)")
    sv.set_defaults(fn=cmd_serve)

    rt = sub.add_parser("route",
                        help="fleet router: one front door over N engine "
                             "replicas (health-gated, least-loaded + "
                             "session-affine, hedging, brownout shedding)")
    rt.add_argument("--config", help="JSON config file (fleet/health "
                                     "sections)")
    rt.add_argument("--set", action="append", metavar="dotted.key=value",
                    help="override any config field, e.g. "
                         "--set fleet.max_inflight=128")
    rt.add_argument("--host", default=None,
                    help="bind address (default fleet.router_host)")
    rt.add_argument("--port", type=int, default=None,
                    help="bind port (default fleet.router_port; 0 = auto)")
    rt.add_argument("--replicas", action="append", metavar="ADDR[,ADDR]",
                    default=None,
                    help="static replica list (comma- or repeat-"
                         "separated); without it, replicas are discovered "
                         "from the coordinator (`serve --fleet`)")
    rt.add_argument("--coordinator", metavar="ADDR", default=None,
                    help="coordinator to poll for replica:<service> "
                         "members (default: control.coordinator_addr "
                         "when no --replicas are given)")
    rt.add_argument("--autoscale", action="store_true",
                    help="run the burn-rate autoscaler (needs --health, "
                         "a queue-wait SLO in health.slos, and "
                         "--replica-cmd)")
    rt.add_argument("--replica-cmd", metavar="CMD", default=None,
                    help="command line that launches one replica "
                         "(e.g. 'python -m serverless_learn_tpu serve "
                         "--fleet --port 0 ...'); scale-in SIGTERMs the "
                         "youngest, which deregisters + drains")
    rt.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                    help="serve /metrics (+/alerts,/healthz with "
                         "--health) from this port (0 = auto)")
    rt.add_argument("--health", action="store_true",
                    help="run the health engine over the router's "
                         "metrics — declare a queue-wait SLO on "
                         "slt_router_queue_wait_seconds in health.slos "
                         "to arm burn-rate scale-out alerts")
    rt.add_argument("--events-log", metavar="PATH", default=None,
                    help="append router alert/span JSONL here (doctor/"
                         "trace input)")
    rt.add_argument("--flight-dir", metavar="DIR", default=None)
    rt.add_argument("--node", default=None)
    rt.add_argument("--profile-dir", default=None, help=argparse.SUPPRESS)
    rt.set_defaults(fn=cmd_route)

    lg = sub.add_parser("loadgen",
                        help="closed/open-loop load generator: Poisson/"
                             "diurnal/flash-crowd arrivals, latency-vs-"
                             "offered-load curves into bench_history.json")
    lg.add_argument("--addr", metavar="HOST:PORT", default=None,
                    help="serving address (router or single replica)")
    lg.add_argument("--mode", choices=["open", "closed"], default="open")
    lg.add_argument("--arrival", choices=["poisson", "diurnal", "flash"],
                    default="poisson")
    lg.add_argument("--rate", type=float, default=None,
                    help="offered rps (open loop; --smoke default 40)")
    lg.add_argument("--rates", action="append", metavar="R[,R]",
                    default=None,
                    help="sweep these offered rates into one curve")
    lg.add_argument("--duration", type=float, default=None,
                    help="seconds per curve point (default 10; "
                         "--smoke default 6)")
    lg.add_argument("--requests", type=int, default=100,
                    help="closed loop: total requests")
    lg.add_argument("--concurrency", type=int, default=8,
                    help="closed loop: worker count")
    lg.add_argument("--timeout", type=float, default=30.0,
                    help="per-request client timeout")
    lg.add_argument("--seed", type=int, default=0,
                    help="arrival + payload RNG seed (same seed = "
                         "identical request schedule)")
    lg.add_argument("--label", default="fleet",
                    help="bench row metric prefix "
                         "(<label>_loadgen_<rate>rps_p99_ms)")
    lg.add_argument("--device-kind", default="fleet",
                    help="bench row comparability key")
    lg.add_argument("--history", default="bench_history.json",
                    help="bench history file for --record")
    lg.add_argument("--record", action="store_true",
                    help="append the curve's rows to the bench history "
                         "(gate them via `slt bench --gate --metric "
                         "<label>`)")
    lg.add_argument("--smoke", action="store_true",
                    help="self-contained CI proof: 2-replica stub fleet, "
                         "open-loop load, one replica killed + restarted "
                         "mid-run; exit 0 iff zero failed requests")
    lg.add_argument("--waterfall-smoke", action="store_true",
                    help="request-waterfall acceptance run: seeded "
                         "continuous-engine workload with injected "
                         "preemption + forced new-bucket compile; exit 0 "
                         "iff both causes land on the correct requests, "
                         "TTFT/stall decompositions sum and the ledger "
                         "overhead stays under 2%% of decode wall-clock; "
                         "--record appends serve_itl/ttft rows")
    lg.add_argument("--fleetscope-smoke", action="store_true",
                    help="fleet-redundancy acceptance run: 3 stub "
                         "replicas with real paged prefix caches, one "
                         "pre-warmed with the shared prefix, prefix-heavy "
                         "closed-loop load through a real router; exit 0 "
                         "iff live redundancy counters fire, fleet_digest "
                         "snapshots appear, prefix-aware replay beats the "
                         "recorded stream strictly, and same-log reports "
                         "are byte-identical; --record appends the "
                         "fleetscope_smoke_p99_ms row with redundancy "
                         "attribution columns")
    lg.add_argument("--canary-smoke", action="store_true",
                    help="canary acceptance run: a 3-replica stub fleet "
                         "serving two weight versions under a 50%% "
                         "session-sticky split with golden probes; exit "
                         "0 iff the healthy leg PROMOTES, an injected "
                         "one-token output regression flips the verdict "
                         "to ROLLBACK on fingerprint evidence, probe "
                         "traffic stays out of the user latency SLIs and "
                         "its overhead share stays bounded; --record "
                         "appends the canary_candidate_p99_ms row with "
                         "verdict attribution columns")
    lg.add_argument("--compact", action="store_true",
                    help="single-line JSON (for scripts)")
    lg.set_defaults(fn=cmd_loadgen)

    w = sub.add_parser("worker", help="elastic worker: join a cluster & train")
    _add_train_flags(w)
    w.add_argument("--advertise", default="local:0",
                   help="address advertised to peers")
    w.add_argument("--name", default=None,
                   help="worker name = checkpoint namespace. Default is "
                        "unique per host+process; pass a stable "
                        "name to resume a predecessor's checkpoints. Two "
                        "LIVE workers may never share a name (refused at "
                        "startup)")
    w.add_argument("--multihost", metavar="RUN", default=None,
                   help="join the named multi-host elastic run: all hosts "
                        "tagged with RUN form one SPMD world that re-forms "
                        "(checkpoint-restart) as hosts join or die")
    w.add_argument("--min-hosts", type=int, default=1,
                   help="with --multihost: wait for at least this many "
                        "hosts before forming the first world")
    w.add_argument("--chips", type=int, default=1,
                   help="with --multihost: TPU chips this host contributes. "
                        "Registered with the coordinator so every supervisor "
                        "can size satisfiable worlds for the configured mesh "
                        "WITHOUT touching the local chips itself (the inner "
                        "trainer must be the only libtpu owner)")
    w.add_argument("--ckpt-cache-dir", default=None,
                   help="worker-local checkpoint cache dir (round 15): "
                        "remesh restores read local disk instead of the "
                        "central store; served to peers with "
                        "--ckpt-serve-cache")
    w.add_argument("--ckpt-peers", default=None,
                   help="comma-separated peer cache addrs to replicate "
                        "checkpoints to (and restore from when the "
                        "central store is slow or partitioned)")
    w.add_argument("--ckpt-serve-cache", action="store_true",
                   help="serve --ckpt-cache-dir to peers over the "
                        "shard-server wire protocol (ephemeral port)")
    w.set_defaults(fn=cmd_worker)

    c = sub.add_parser("coordinator", help="run the membership daemon")
    c.add_argument("--port", type=int, default=50052)
    c.add_argument("--lease-ttl-ms", type=int, default=5000)
    c.add_argument("--sweep-ms", type=int, default=500)
    c.add_argument("--state-file", default=None,
                   help="persist membership here: a restarted coordinator "
                        "resumes the same epoch and worker ids, so "
                        "heartbeating workers carry on without re-mesh churn")
    c.add_argument("--events-log", metavar="PATH", default=None,
                   help="append a JSONL server-side span per traced RPC "
                        "(requests carrying TraceContext) — one input of "
                        "`slt trace`")
    c.add_argument("--gossip", action="store_true",
                   help="run a SWIM gossip seed beside the RPC port "
                        "(UDP, port+1 by default): liveness comes from "
                        "gossip probes instead of O(N) lease heartbeats; "
                        "workers opt in with membership.mode=gossip")
    c.add_argument("--gossip-port", type=int, default=None,
                   help="UDP port for the gossip seed (default: RPC "
                        "port + 1; implies --gossip)")
    c.set_defaults(fn=cmd_coordinator)

    s = sub.add_parser("shard-server", help="run the data-plane daemon")
    s.add_argument("--port", type=int, default=50053)
    s.add_argument("--root", help="blob root directory")
    s.add_argument("--events-log", metavar="PATH", default=None,
                   help="append a JSONL server-side span per traced RPC")
    s.set_defaults(fn=cmd_shard_server)

    pub = sub.add_parser("publish",
                         help="publish a dataset to the data plane")
    pub.add_argument("--shard-server", required=True, metavar="ADDR")
    pub.add_argument("--dataset", required=True)
    pub.add_argument("--format", default="synthetic",
                     choices=["synthetic", "mnist", "cifar10", "imagefolder",
                              "tokens", "text"],
                     help="synthetic: sample a model's batch schema; "
                          "mnist/cifar10: parse the standard raw-file "
                          "distributions under --path; imagefolder: decode "
                          "an ImageNet-layout class-directory tree to "
                          "256x256 uint8 records (train-time 224 crops "
                          "happen host-side); tokens: chunk a corpus file "
                          "(.bin token dump or raw text); text: tokenize a "
                          "text corpus (--vocab/--merges for GPT-2-format "
                          "BPE, else byte-level) and pack documents densely")
    pub.add_argument("--path", help="raw dataset directory/file "
                                    "(non-synthetic formats)")
    pub.add_argument("--split", default="train", choices=["train", "test"])
    pub.add_argument("--model", default=None,
                     help="synthetic format: model whose batch schema to "
                          "publish")
    pub.add_argument("--num-records", type=int, default=4096,
                     help="synthetic format: how many records")
    pub.add_argument("--records-per-shard", type=int, default=None,
                     help="records per shard (default 512; imagefolder "
                          "defaults to 256 records ~= 50 MB shards)")
    pub.add_argument("--seq-len", type=int, default=128)
    pub.add_argument("--seed", type=int, default=0)
    pub.add_argument("--vocab", default=None,
                     help="text format: GPT-2-style vocab.json")
    pub.add_argument("--merges", default=None,
                     help="text format: GPT-2-style merges.txt")
    pub.set_defaults(fn=cmd_publish)

    dl = sub.add_parser("diloco",
                        help="DiLoCo island: local training + anchor-delta "
                             "outer syncs over the control/data plane")
    _add_train_flags(dl)
    dl.add_argument("--run-name", required=True,
                    help="islands sharing this name form one DiLoCo run")
    dl.add_argument("--rounds", type=int, default=10,
                    help="outer rounds to participate in")
    dl.add_argument("--store-dir", default=None,
                    help="local directory store (testing); production uses "
                         "--shard-server")
    dl.add_argument("--round-timeout-s", type=float, default=60.0,
                    help="leader waits at most this long for straggler "
                         "deltas before averaging what's posted")
    dl.add_argument("--liveness-factor", type=float, default=3.0,
                    help="non-leader escape hatch: after this many "
                         "round-timeouts without a new anchor, re-check "
                         "LATEST and challenge a hung leader")
    dl.set_defaults(fn=cmd_diloco)

    st = sub.add_parser("stats", help="scrape a daemon's load/RPC stats")
    st.add_argument("--addr", required=True)
    st.add_argument("--kind", choices=["coordinator", "shard-server"],
                    default="shard-server")
    st.set_defaults(fn=cmd_stats)

    tr = sub.add_parser("trace",
                        help="merge multi-node span logs into one skew-"
                             "corrected timeline (Perfetto trace_event "
                             "JSON + critical-path report)")
    tr.add_argument("logs", nargs="+", metavar="LOG",
                    help="JSONL span logs (--events-log), daemon "
                         "--events_log files, flight-*.json dumps, or "
                         "directories/globs of them")
    tr.add_argument("--out", metavar="FILE", default=None,
                    help="write Chrome/Perfetto trace_event JSON here "
                         "(load at ui.perfetto.dev or chrome://tracing)")
    tr.add_argument("--no-skew", action="store_true",
                    help="trust each node's wall clock instead of "
                         "correcting skew from client/server span pairs")
    tr.add_argument("--root", default=None,
                    help="anchor clock correction at this node "
                         "(default: the node with the most spans)")
    tr.add_argument("--trace-id", default=None,
                    help="restrict the timeline to one trace")
    tr.add_argument("--top", type=int, default=5,
                    help="slowest traces / critical-path hops to report")
    tr.add_argument("--compact", action="store_true",
                    help="single-line JSON summary (for scripts)")
    tr.set_defaults(fn=cmd_trace)

    dr = sub.add_parser("doctor",
                        help="ranked cluster diagnosis from event logs, "
                             "flight dumps, live /alerts scrapes and "
                             "bench history")
    dr.add_argument("logs", nargs="*", metavar="LOG",
                    help="JSONL event logs (--events-log), daemon "
                         "--events_log files, flight-*.json dumps, or "
                         "directories/globs of them")
    dr.add_argument("--endpoints", action="append", metavar="HOST:PORT",
                    default=None,
                    help="scrape these /alerts endpoints live (comma- or "
                         "repeat-separated)")
    dr.add_argument("--bench-history", metavar="FILE", default=None,
                    help="bench_history.json for cross-run perf "
                         "regression checks (default: ./bench_history."
                         "json when present)")
    dr.add_argument("--config", default=None,
                    help="config whose health section tunes/declares the "
                         "rules (used by --self-check)")
    dr.add_argument("--top", type=int, default=10,
                    help="ranked alerts to report")
    dr.add_argument("--compact", action="store_true",
                    help="single-line JSON report (for scripts)")
    dr.add_argument("--self-check", action="store_true",
                    help="smoke-test the health engine: rules parse, a "
                         "healthy fixture stays quiet, a stalled counter "
                         "fires the watchdog; exit 0 on success (CI)")
    dr.add_argument("--xray", action="append", metavar="CAPTURE_DIR",
                    default=None,
                    help="analyze these profiler capture dirs with "
                         "`slt xray` and fold the hardware-attribution "
                         "verdicts into the diagnosis")
    dr.set_defaults(fn=cmd_doctor)

    gp = sub.add_parser("goodput",
                        help="goodput/badput accounting: per-phase "
                             "wall-clock breakdown from live /goodput "
                             "scrapes or JSONL event logs")
    gp.add_argument("logs", nargs="*", metavar="LOG",
                    help="JSONL event logs / flight dumps / directories "
                         "containing phase records (offline mode)")
    gp.add_argument("--from-events", action="append", metavar="LOG",
                    default=None,
                    help="same as the positional logs (explicit offline "
                         "mode)")
    gp.add_argument("--endpoints", action="append", metavar="HOST:PORT",
                    default=None,
                    help="scrape these /goodput endpoints live (comma- or "
                         "repeat-separated)")
    gp.add_argument("--compact", action="store_true",
                    help="single-line JSON (for scripts)")
    gp.add_argument("--self-check", action="store_true",
                    help="smoke-test the ledger math on a fabricated "
                         "timeline: exclusivity exact, phases sum to the "
                         "total, offline aggregation agrees; exit 0 on "
                         "success (CI)")
    gp.set_defaults(fn=cmd_goodput)

    nm = sub.add_parser(
        "numerics",
        help="training-quality observability: fingerprint diff/bisect, "
             "run summaries, self-check",
        description="Bisect two recorded fingerprint trails to the first "
                    "divergent step + parameter subtree (diff), digest a "
                    "run's numerics trail (summary), or run the CI "
                    "self-check. Producers: train with numerics.enabled "
                    "(--numerics) writes numerics_stats/"
                    "numerics_fingerprint records into --events-log and "
                    "the optional numerics.fingerprint_log.")
    nm.add_argument("action", nargs="?", choices=["diff", "summary"],
                    help="diff: bisect two trails; summary: digest logs")
    nm.add_argument("paths", nargs="*",
                    help="JSONL trails (event logs, fingerprint logs, "
                         "flight dumps)")
    nm.add_argument("--rtol", type=float, default=1e-5,
                    help="relative tolerance for digest agreement")
    nm.add_argument("--atol", type=float, default=1e-6,
                    help="absolute tolerance for digest agreement")
    nm.add_argument("--self-check", action="store_true",
                    help="run the numerics self-check (CI smoke)")
    nm.add_argument("--compact", action="store_true")
    nm.set_defaults(fn=cmd_numerics)

    pf = sub.add_parser("profile",
                        help="capture an on-demand jax.profiler device "
                             "trace on a live node (needs --profile-dir "
                             "+ --metrics-port on the target)")
    pf.add_argument("endpoint", metavar="HOST:PORT",
                    help="the target's metrics endpoint")
    pf.add_argument("--seconds", type=float, default=3.0,
                    help="capture window length")
    pf.set_defaults(fn=cmd_profile)

    xr = sub.add_parser("xray",
                        help="step-interior hardware attribution from a "
                             "jax.profiler capture: op taxonomy, exposed "
                             "collectives per mesh axis, roofline "
                             "verdicts, HBM watermarks, per-step "
                             "breakdown")
    xr.add_argument("captures", nargs="*", metavar="CAPTURE_DIR",
                    help="profiler capture dirs (--profile-dir output, "
                         "`slt profile` replies) or direct "
                         "*.trace.json[.gz] files")
    xr.add_argument("--device-kind", default=None,
                    help="override the device kind for roofline peaks "
                         "(default: capture-meta.json's stamp)")
    xr.add_argument("--top", type=int, default=5,
                    help="per-step rows kept from each end of a long "
                         "capture (see --full)")
    xr.add_argument("--full", action="store_true",
                    help="keep every per-step row")
    xr.add_argument("--compact", action="store_true",
                    help="single-line JSON (for scripts)")
    xr.add_argument("--self-check", action="store_true",
                    help="CI smoke: the synthetic pipeline invariants "
                         "hold exactly and the committed fixture capture "
                         "re-analyzes to its committed summary; exit 1 "
                         "on drift")
    xr.set_defaults(fn=cmd_xray)

    wf = sub.add_parser("waterfall",
                        help="per-request lifecycle waterfalls from "
                             "engine+router event logs: TTFT/ITL "
                             "percentile decompositions, stall-cause "
                             "attribution, hedge provenance, phase bars")
    wf.add_argument("paths", nargs="*", metavar="EVENTS",
                    help="JSONL event logs (--events-log output, flight "
                         "dumps) or directories of them; engine and "
                         "router logs merge by trace_id")
    wf.add_argument("--top", type=int, default=10,
                    help="slowest requests to render as phase bars")
    wf.add_argument("--json", action="store_true",
                    help="full JSON report instead of the rendering")
    wf.add_argument("--compact", action="store_true",
                    help="single-line JSON (for scripts)")
    wf.add_argument("--device-kind", default="cpu",
                    help="device-kind stamp for --bench-history rows")
    wf.add_argument("--bench-history", metavar="FILE", default=None,
                    help="append serve_itl_p99_ms / serve_ttft_p99_ms "
                         "rows (with decomposition attribution columns) "
                         "to this bench history for `slt bench --gate`")
    wf.add_argument("--fixture", metavar="FILE", default=None,
                    help="committed fixture JSONL for --self-check "
                         "(default: the embedded synthetic records)")
    wf.add_argument("--self-check", action="store_true",
                    help="CI smoke: synthetic+fixture records survive "
                         "read->merge->summarize with every invariant "
                         "(TTFT decomposition, stall sums, hedge "
                         "provenance, reserved spec_verify phase) "
                         "intact; exit 1 on drift")
    wf.set_defaults(fn=cmd_waterfall)

    fsc = sub.add_parser("fleetscope",
                         help="fleet-wide KV/prefix redundancy accounting"
                              " + counterfactual routing replay from "
                              "router route_decision event logs")
    fsc.add_argument("paths", nargs="*", metavar="EVENTS",
                     help="JSONL event logs (router --events-log output) "
                          "or directories of them; route_decision, "
                          "fleet_digest and request-span records merge")
    fsc.add_argument("--json", action="store_true",
                     help="full JSON report (sorted keys — byte-identical"
                          " for identical logs) instead of the rendering")
    fsc.add_argument("--compact", action="store_true",
                     help="single-line JSON (for scripts)")
    fsc.add_argument("--device-kind", default="cpu",
                     help="device-kind stamp for --bench-history rows")
    fsc.add_argument("--bench-history", metavar="FILE", default=None,
                     help="append the fleetscope_ttft_p99_ms row (with "
                          "fleet_redundant_prefill_frac / "
                          "fleet_prefix_dup_factor attribution columns) "
                          "to this bench history for `slt bench --gate`")
    fsc.add_argument("--fixture", metavar="FILE", default=None,
                     help="committed fixture JSONL for --self-check "
                          "(default: the embedded synthetic records)")
    fsc.add_argument("--self-check", action="store_true",
                     help="CI smoke: the fabricated 3-replica fixture "
                          "survives read->account->replay with exact "
                          "redundancy accounting, strict prefix-aware "
                          "improvement, byte-identical reports and a "
                          "TTFT bound below the recorded p99; exit 1 on "
                          "drift")
    fsc.set_defaults(fn=cmd_fleetscope)

    cnr = sub.add_parser("canary",
                         help="version-scoped serving SLIs + the "
                              "promote/hold/rollback verdict engine "
                              "from router event logs")
    cnr.add_argument("paths", nargs="*", metavar="EVENTS",
                     help="JSONL event logs (router --events-log output) "
                          "or directories of them; fleet_version, "
                          "canary_config, canary_probe, route_decision "
                          "and request-span records merge")
    cnr.add_argument("--json", action="store_true",
                     help="full JSON report (sorted keys — byte-identical"
                          " for identical logs) instead of the rendering")
    cnr.add_argument("--compact", action="store_true",
                     help="single-line JSON (for scripts)")
    cnr.add_argument("--device-kind", default="cpu",
                     help="device-kind stamp for --bench-history rows")
    cnr.add_argument("--bench-history", metavar="FILE", default=None,
                     help="append the canary_candidate_p99_ms row (with "
                          "canary_probe_match_frac / "
                          "canary_ttft_p99_delta_frac / canary_verdict "
                          "attribution columns) to this bench history "
                          "for `slt bench --gate`")
    cnr.add_argument("--fixture", metavar="FILE", default=None,
                     help="committed fixture JSONL for --self-check "
                          "(default: the embedded synthetic records)")
    cnr.add_argument("--self-check", action="store_true",
                     help="CI smoke: the committed 2-version fixture "
                          "reproduces the hand-computed verdicts — "
                          "promote on parity, rollback on an injected "
                          "probe-fingerprint regression, rollback on an "
                          "injected TTFT-p99 regression — each naming "
                          "its evidence, with probe traffic provably "
                          "excluded from user SLIs and byte-identical "
                          "reports; exit 1 on drift")
    cnr.set_defaults(fn=cmd_canary)

    bn = sub.add_parser("bench",
                        help="headline benchmark + perf regression gate "
                             "over bench_history.json")
    bn.add_argument("--gate", action="store_true",
                    help="exit 1 when a series regresses past the "
                         "noise-aware threshold (CI gate)")
    bn.add_argument("--dry-run", action="store_true",
                    help="skip the measurement; gate the committed "
                         "history's latest entries (no device needed)")
    bn.add_argument("--history", metavar="FILE", default=None,
                    help="bench history file (default: "
                         "./bench_history.json)")
    bn.add_argument("--threshold", type=float, default=0.05,
                    help="relative regression threshold (widened by "
                         "2x a row's recorded spread_rel)")
    bn.add_argument("--metric", default=None,
                    help="gate series whose metric name contains this "
                         "substring (default: the headline "
                         "resnet18_cifar series; *_ms series gate with "
                         "better=min)")
    bn.add_argument("--all", action="store_true",
                    help="sweep every series in the history (report "
                         "mode — the ladder's multi-mode rows carry "
                         "documented shared-chip variance)")
    bn.add_argument("--compact", action="store_true",
                    help="single-line JSON report (for scripts)")
    bn.add_argument("--attribute", action="store_true",
                    help="on gate failure, attribute each regression "
                         "against the best-passing comparable row — via "
                         "RunBundles when both rows carry `bundle` "
                         "pointers, via the row-level attribution "
                         "columns otherwise — and print the dominant "
                         "cause on stderr (telemetry/regress.py)")
    bn.set_defaults(fn=cmd_bench)

    rg = sub.add_parser("regress",
                        help="cross-run differential attribution: "
                             "decompose a headline delta between two "
                             "RunBundles along every ledger (goodput, "
                             "xray, waterfall, dcn, config, numerics) "
                             "with machine-checked sum invariants")
    rg.add_argument("run_a", nargs="?", default=None,
                    help="baseline run: bundle dir or run.json path")
    rg.add_argument("run_b", nargs="?", default=None,
                    help="candidate run: bundle dir or run.json path")
    rg.add_argument("--metric", default=None,
                    help="headline metric substring to pair bench rows "
                         "on (default: first comparable pair)")
    rg.add_argument("--tolerance", type=float, default=0.05,
                    help="decomposition residual tolerance relative to "
                         "the decomposition's own scale (default 0.05)")
    rg.add_argument("--json", action="store_true",
                    help="machine-readable report on stdout (sorted "
                         "keys — byte-identical on identical inputs)")
    rg.add_argument("--compact", action="store_true",
                    help="single-line JSON (with --json)")
    rg.add_argument("--self-check", action="store_true",
                    help="pin the decomposition contract: synthetic "
                         "exactness, residual flagging, determinism, "
                         "and the committed two-run fixture's "
                         "hand-computed report byte-for-byte; exit 1 "
                         "on drift")
    rg.add_argument("--fixture", default=None, metavar="DIR",
                    help="fixture dir for --self-check (default: "
                         "tests/fixtures/regress)")
    rg.set_defaults(fn=cmd_regress)

    ck = sub.add_parser("check",
                        help="project-aware static analysis: lock order, "
                             "metric drift, jit purity, thread lifecycle, "
                             "proto compat, config drift, guarded-by, "
                             "resource lifecycle, atomicity, dtype flow, "
                             "donation safety, recompile hazards, "
                             "sharding drift (SLT001-SLT013)")
    ck.add_argument("--rule", action="append", metavar="SLTxxx",
                    help="run only this rule (repeatable)")
    ck.add_argument("--changed-only", action="store_true",
                    help="scope per-file rules to files git reports "
                         "changed vs HEAD (fast pre-commit mode; "
                         "project-wide rules still see the full tree)")
    ck.add_argument("--list-rules", action="store_true",
                    help="print the rule registry and exit")
    ck.add_argument("--json", action="store_true",
                    help="machine-readable report on stdout")
    ck.add_argument("--compact", action="store_true",
                    help="single-line JSON (with --json)")
    ck.add_argument("--root", default=None,
                    help="repo root to scan (default: the checkout "
                         "containing this package)")
    ck.add_argument("--baseline", default=None, metavar="FILE",
                    help="baseline-suppression file, relative to the "
                         "root (default: serverless_learn_tpu/analysis/"
                         "baseline.json)")
    ck.add_argument("--update-baseline", action="store_true",
                    help="rewrite the baseline from the current findings "
                         "(then hand-edit each justification)")
    ck.set_defaults(fn=cmd_check)

    rc = sub.add_parser("race",
                        help="replay a recorded SLT_RACECHECK_LOG access "
                             "log through the vector-clock monitor: "
                             "deterministic offline triage of a race a "
                             "CI run caught")
    rc.add_argument("log", help="JSONL event log written by a run with "
                                "SLT_RACECHECK=1 SLT_RACECHECK_LOG=path")
    rc.add_argument("--json", action="store_true",
                    help="machine-readable race list on stdout")
    rc.add_argument("--include-allowlisted", action="store_true",
                    help="also report races the racecheck ALLOWLIST "
                         "suppresses (with their justifications)")
    rc.set_defaults(fn=cmd_race)

    jt = sub.add_parser("jit",
                        help="replay a recorded SLT_JITCHECK_LOG compile "
                             "log through the budget/frozen-window/"
                             "donation verdict engine: deterministic "
                             "offline triage of a recompile or donated-"
                             "buffer reuse a CI run caught")
    jt.add_argument("log", nargs="?", default=None,
                    help="JSONL event log written by a run with "
                         "SLT_JITCHECK=1 SLT_JITCHECK_LOG=path")
    jt.add_argument("--self-check", action="store_true",
                    help="validate the verdict engine against synthetic "
                         "seeded logs (clean log passes; budget-exceed, "
                         "frozen-compile and donation-reuse each "
                         "convict) and exit")
    jt.add_argument("--json", action="store_true",
                    help="machine-readable verdict on stdout")
    jt.set_defaults(fn=cmd_jit)

    ch = sub.add_parser("chaos",
                        help="fault-injection chaos harness: run a "
                             "FaultPlan (or a seeded random soak) against "
                             "N simulated gossip members on virtual time")
    ch.add_argument("mode",
                    choices=["run", "soak", "fleet", "recover", "herd"],
                    help="run: execute --plan on the gossip simulator; "
                         "soak: seeded random schedule of kills/"
                         "partitions/stragglers; fleet: execute --plan "
                         "(kill/restart/pause/delay/heal) against a REAL "
                         "router + stub replicas through TcpChaosProxy; "
                         "recover: kill/corrupt/partition the REAL "
                         "checkpoint stack and assert bounded RPO + "
                         "measured RTO per incident; herd: N vmapped "
                         "DiLoCo workers running REAL tiny-model inner "
                         "steps under churn, speed skew, quorum "
                         "participation and delta quarantine")
    ch.add_argument("--plan", metavar="FILE.json",
                    help="FaultPlan (chaos/plan.py DSL); required for run")
    ch.add_argument("--nodes", type=int, default=50,
                    help="simulated cluster size")
    ch.add_argument("--seed", type=int, default=0,
                    help="fault-resolution + protocol RNG seed; same "
                         "(plan, seed) => identical run")
    ch.add_argument("--duration", type=float, default=None,
                    help="virtual seconds to simulate (default: plan end "
                         "+ convergence budget; soak defaults to 120)")
    ch.add_argument("--period-ms", type=float, default=500.0,
                    help="gossip protocol period (virtual ms)")
    ch.add_argument("--events-log", metavar="PATH", default=None,
                    help="write health-engine-shaped alert + fault JSONL "
                         "here — feed it to `slt doctor` to check the "
                         "telemetry names every injected incident")
    ch.add_argument("--full", action="store_true",
                    help="full report (per-fault and per-node detail)")
    ch.add_argument("--compact", action="store_true",
                    help="single-line JSON (for scripts)")
    ch.add_argument("--steps", type=int, default=260,
                    help="recover: virtual training steps to run")
    ch.add_argument("--ckpt-every", type=int, default=20,
                    help="recover: checkpoint interval (the RPO bound)")
    ch.add_argument("--store-latency-ms", type=float, default=0.0,
                    help="recover: injected per-read latency on the "
                         "CENTRAL store (peer/cache reads stay fast — "
                         "how the replica win is measured)")
    ch.add_argument("--no-peer-cache", action="store_true",
                    help="recover: disable the local cache + peer "
                         "replica tier (store-only restores)")
    ch.add_argument("--smoke", action="store_true",
                    help="recover: self-contained CI proof — seeded "
                         "default plan (kill mid-run AND mid-save, "
                         "corrupt, partition), assert the RPO bound, "
                         "and require `slt doctor` to name every "
                         "recovery + the corruption from the events "
                         "log alone; herd: small-N seeded proof — "
                         "mid-round kill + poisoned worker, assert "
                         "byte-identical same-seed reports and doctor "
                         "naming the quarantined worker")
    ch.add_argument("--workers", type=int, default=0,
                    help="herd: vmapped client count (0 = 256, or 48 "
                         "with --smoke)")
    ch.add_argument("--rounds", type=int, default=5,
                    help="herd: outer rounds to run")
    ch.add_argument("--inner-steps", type=int, default=4,
                    help="herd: local steps per worker per round")
    ch.add_argument("--quorum", type=float, default=1.0,
                    help="herd: live-view fraction that closes a round "
                         "(1.0 = wait for everyone or the timeout)")
    ch.add_argument("--late-policy", choices=["drop", "discount"],
                    default="drop",
                    help="herd: stragglers' late deltas are dropped or "
                         "staleness-discounted onto the anchor")
    ch.add_argument("--poison-worker", type=int, default=-1,
                    help="herd: inject a NaN delta from this worker "
                         "(the quarantine drill; -1 = off)")
    ch.add_argument("--poison-round", type=int, default=-1,
                    help="herd: round at which --poison-worker emits "
                         "the NaN delta")
    ch.add_argument("--wire-dtype", choices=["f32", "int8", "fp8"],
                    default=None,
                    help="herd: wire encoding of the simulated delta/"
                         "anchor exchange (training/wire_codec.py; "
                         "default f32 = uncompressed)")
    ch.add_argument("--wire-ab", action="store_true",
                    help="herd: seeded quantized-vs-f32 loss-parity A/B "
                         "under churn (quorum 0.8, mid-round 20% kill) "
                         "with a no-error-feedback negative control; "
                         "exit 1 unless parity holds and wire bytes "
                         "shrink >= 3.5x")
    ch.add_argument("--record", action="store_true",
                    help="herd --wire-ab: append round-wait/DCN-bytes "
                         "rows (per leg) to --history for "
                         "`slt bench --gate`")
    ch.add_argument("--history", metavar="PATH", default=None,
                    help="herd --wire-ab: bench history file for "
                         "--record")
    ch.set_defaults(fn=cmd_chaos)

    tp = sub.add_parser("top", help="live cluster telemetry: poll /metrics "
                                    "endpoints, one-screen view")
    tp.add_argument("endpoints", nargs="+", metavar="HOST:PORT",
                    help="metrics endpoints (comma- or space-separated), "
                         "as printed by --metrics-port")
    tp.add_argument("--interval", type=float, default=2.0,
                    help="refresh period in seconds")
    tp.add_argument("--once", action="store_true",
                    help="print one snapshot and exit (no screen control; "
                         "counter rates need two polls and show as '-')")
    tp.set_defaults(fn=cmd_top)

    m = sub.add_parser("models", help="list registered model families")
    m.set_defaults(fn=cmd_models)

    return p


def main(argv: Optional[List[str]] = None) -> int:
    from serverless_learn_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    args = build_parser().parse_args(argv)
    try:
        return args.fn(args)
    except BrokenPipeError:
        # Downstream consumer (e.g. `| head`) closed the pipe — not an error.
        try:
            sys.stdout.close()
        except Exception:
            pass
        return 0


if __name__ == "__main__":
    sys.exit(main())
