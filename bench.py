"""Headline benchmark: ResNet-18 CIFAR-10 train-step throughput on TPU.

Prints ONE JSON line: {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}
plus MFU/step-time fields, and appends to ``bench_history.json`` — the
regression guard round 1 lacked (its own README number silently dipped 2.6%).
A run below 97% of the historical best sets ``"regression": true`` and warns
on stderr; the run still reports honestly rather than failing. The FULL
bench ladder (r50, BERT, Llama-1B LoRA, flash timing, decode, data plane)
re-measures through the same guard via ``benchmarks/ladder.py``.

Baseline: the reference (`sheaconlon/serverless_learn`) publishes no numbers
(README is one line; BASELINE.md). Its workers are CPU processes whose
training is *simulated* (`src/worker.cc:221-231`), so the honest denominator
for BASELINE.json's ">=10x the repo's CPU-worker samples/sec" target is a real
CPU worker running the same ResNet-18 train step. Measured in this container
(JAX CPU backend, batch 128, single device, steady state): 12.09 samples/sec.
"""

import json
import os
import sys
import time

CPU_WORKER_BASELINE_SPS = 12.09  # ResNet-18 CIFAR b128, JAX CPU, this image

# Hardware-attribution window (round 16): AFTER the timed steps, a short
# profiled window feeds `telemetry/xray.py` so every history row carries
# exposed_comms_frac / hw_util / roofline columns next to the analytic
# MFU — and the two can disagree visibly (a warning row, below, when the
# analytic number claims more FLOP-time than the hardware shows busy).
XRAY_STEPS = 5
MFU_VS_HW_TOLERANCE = 0.10

# Batch sweep on the v5e chip (samples/sec/chip, MFU):
#   256 -> ~26.9k | 512 -> ~29.8k | 2048 -> 31.3k, 46% | 4096 -> 32.7-33.7k,
#   48-49.8% | 8192 -> 34.0k, 50.2% (round 4: first crossing of the 50% MFU
#   bar; beyond 8192 the activation footprint stops paying for itself)
BATCH = 8192
WARMUP = 3
STEPS = 20

HISTORY = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "bench_history.json")


def measure() -> dict:
    """One headline measurement: ResNet-18/CIFAR train throughput on the
    local chip(s). Pure measurement — no history side effects (the ladder
    reuses it). A fresh goodput ledger brackets the run, so every history
    row carries its own goodput/badput breakdown (compile vs timed steps)
    — schema-tolerant consumers (`benchgate.py`, `doctor.py`) read only
    the fields they know, so old rows stay readable."""
    import jax

    from serverless_learn_tpu.config import (
        DataConfig, ExperimentConfig, MeshConfig, OptimizerConfig, TrainConfig)
    from serverless_learn_tpu.data.datasets import SyntheticSource
    from serverless_learn_tpu.telemetry.goodput import PhaseLedger
    from serverless_learn_tpu.training.train_step import build_trainer
    from serverless_learn_tpu.utils.flops import compiled_step_flops, mfu

    from serverless_learn_tpu.training import zero as zero_mod

    ledger = PhaseLedger(emit=False)  # bench rows, not JSONL traffic
    ledger.ensure_started()
    n_dev = len(jax.devices())
    cfg = ExperimentConfig(
        model="resnet18_cifar",
        mesh=MeshConfig(dp=n_dev),
        optimizer=OptimizerConfig(name="sgd", learning_rate=0.1, momentum=0.9),
        # Round 18: the headline measures the ZeRO-sharded update (the
        # production configuration); the gate's comparability keys are
        # unchanged, so the row competes with the replicated-update
        # history — holding samples/s/chip while opt-state bytes/chip
        # shrink 1/dp is exactly the claim.
        train=TrainConfig(batch_size=BATCH * n_dev,
                          zero_stage=1 if n_dev > 1 else 0),
        data=DataConfig(),
    )
    trainer = build_trainer(cfg)
    state = trainer.init()
    src = iter(SyntheticSource(trainer.bundle.make_batch, cfg.data,
                               cfg.train.batch_size, seed=0))
    batch = trainer.shard_batch(next(src))
    with ledger.phase("compile"):  # warmup = trace+compile badput
        for _ in range(WARMUP):
            state, metrics = trainer.step(state, batch)
        # Fetching the scalar is the sync point: the host has the value
        # only once every step before it has finished.
        float(jax.device_get(metrics["loss"]))
    t0 = time.perf_counter()
    with ledger.phase("step"):
        for _ in range(STEPS):
            state, metrics = trainer.step(state, batch)
        float(jax.device_get(metrics["loss"]))
    dt = time.perf_counter() - t0
    step_s = dt / STEPS
    sps_chip = cfg.train.batch_size / step_s / n_dev
    flops = compiled_step_flops(trainer.step_fn, state, batch,
                                n_devices=n_dev)
    utilization = mfu(flops, step_s, n_chips=n_dev)
    record = {
        "metric": "resnet18_cifar_train_samples_per_sec_per_chip",
        "value": round(sps_chip, 1),
        "unit": "samples/sec/chip",
        "vs_baseline": round(sps_chip / CPU_WORKER_BASELINE_SPS, 2),
        "batch_per_chip": BATCH,
        "device_kind": jax.devices()[0].device_kind,
        "step_time_ms": round(step_s * 1e3, 2),
    }
    if utilization is not None:
        record["mfu"] = round(utilization, 4)
    # ZeRO layout accounting (round 18): the per-chip resident opt-state
    # bytes ride every row, so the history shows the 1/dp shrink next to
    # the throughput it must not cost.
    record["zero_stage"] = cfg.train.zero_stage
    record["opt_state_bytes_per_chip"] = int(
        zero_mod.bytes_per_chip(state.opt_state))
    record.update(_xray_columns(trainer, state, batch, n_dev, step_s,
                                utilization))
    grep = ledger.report(mfu=utilization)
    record["goodput"] = grep["goodput"]
    record["badput_breakdown"] = grep["badput_breakdown"]
    # Cross-run identity stamps (round 24): git_sha + config fingerprint
    # make any two history rows joinable for `slt regress`; readers
    # treat missing stamps as joinable-but-unattributable, never errors.
    from serverless_learn_tpu.telemetry import regress

    sha = regress.git_sha(os.path.dirname(os.path.abspath(__file__)))
    if sha:
        record["git_sha"] = sha
    fp = regress.config_fingerprint(cfg)
    if fp:
        record["config_fingerprint"] = fp
    return record


def _xray_columns(trainer, state, batch, n_dev, step_s, analytic_mfu):
    """Hardware-counted attribution columns from a short profiled window
    run AFTER the timed steps (the headline timing stays untouched). A
    capture or reduction that fails fails the run — a row without its
    per-layer columns would read as a measurement.
    ``hw_util`` is the device-busy fraction the trace actually
    shows — when the analytic MFU exceeds it by more than the tolerance,
    the row carries a warning instead of silently trusting the cost
    model."""
    import shutil
    import tempfile

    import jax

    from serverless_learn_tpu.telemetry import profiler, xray
    from serverless_learn_tpu.utils.flops import (
        compiled_step_cost, peak_flops_per_chip, peak_hbm_bytes_per_s)

    out = {}
    tmp = tempfile.mkdtemp(prefix="slt-bench-xray-")
    try:
        with profiler.capture_session(tmp):
            for _ in range(XRAY_STEPS):
                state, metrics = trainer.step(state, batch)
            float(jax.device_get(metrics["loss"]))
        summary = xray.analyze_dir(
            tmp, device_kind=jax.devices()[0].device_kind,
            n_devices=n_dev)
        xray.set_last_summary(summary)
        out["exposed_comms_frac"] = summary["exposed_comms_frac"]
        out["hw_util"] = summary["busy_frac"]
        # dp-axis gradient-exchange seconds (round 18): the before/after
        # ZeRO capture comparison reads this column straight off two
        # history rows; the SLT002-catalogued gauge mirrors it.
        from serverless_learn_tpu.training import zero as zero_mod

        rs_s = zero_mod.publish_grad_reduce_gauge(summary)
        if rs_s is not None:
            out["grad_reduce_scatter_s"] = round(rs_s, 6)
        roof = summary.get("roofline") or {}
        if roof.get("hbm_bound_frac") is not None:
            out["hbm_bound_frac"] = roof["hbm_bound_frac"]
        achieved = roof.get("achieved_vs_roofline")
        if achieved is None:
            # No per-op costs in the trace: judge the whole step against
            # the roofline from XLA's compiled cost model instead.
            # Per-chip roofline: the compiled cost is whole-mesh, the
            # published peaks are per chip.
            cost = compiled_step_cost(trainer.step_fn, state, batch,
                                      n_devices=n_dev) or {}
            mod = xray.module_roofline(
                (cost.get("flops") or 0) / n_dev or None,
                (cost.get("bytes_accessed") or 0) / n_dev or None,
                step_s, peak_flops_per_chip(), peak_hbm_bytes_per_s())
            if mod:
                achieved = mod.get("achieved_vs_roofline")
                out["step_bound"] = mod["bound"]
        if achieved is not None:
            out["achieved_vs_roofline"] = achieved
        if (analytic_mfu is not None
                and analytic_mfu > out["hw_util"] + MFU_VS_HW_TOLERANCE):
            out["mfu_vs_hw_warning"] = (
                f"analytic mfu {analytic_mfu:.3f} exceeds hardware busy "
                f"fraction {out['hw_util']:.3f} — cost-model overcount?")
            print(f"WARNING: {out['mfu_vs_hw_warning']}",
                  file=sys.stderr)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return out


def write_run_bundle(rec, history_path) -> "str | None":
    """Stamp this measurement's RunBundle (round 24): the full xray
    summary + goodput breakdown + the row itself under
    ``<history_dir>/bundles/<run_id>/run.json``, with ``rec["bundle"]``
    set to the history-relative pointer BEFORE the row is recorded —
    any two gated rows then resolve to their bundles and `slt regress`
    can decompose the delta. A bundle that cannot be written fails the
    run."""
    from serverless_learn_tpu.telemetry import regress, xray

    run_id = (time.strftime("bench-%Y%m%dT%H%M%S")
              + f"-{os.getpid()}")
    hist_dir = os.path.dirname(os.path.abspath(history_path))
    out_dir = os.path.join(hist_dir, "bundles", run_id)
    rec["bundle"] = os.path.join("bundles", run_id)
    regress.write_bundle(
        out_dir, run_id=run_id, role="bench",
        bench_rows=[rec],
        xray_summary=xray.get_last_summary(),
        config={"model": "resnet18_cifar",
                "zero_stage": rec.get("zero_stage")},
        config_fp=rec.get("config_fingerprint"),
        git_sha_value=rec.get("git_sha"))
    return rec["bundle"]


def main():
    from serverless_learn_tpu.utils.benchlog import record as record_history
    from serverless_learn_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    KEYS = ("metric", "device_kind", "batch_per_chip")
    rec = measure()
    write_run_bundle(rec, HISTORY)
    rec = record_history(
        rec, HISTORY, better="max", rel_threshold=0.03, key_fields=KEYS)
    print(json.dumps(rec))
    return 0


if __name__ == "__main__":
    sys.exit(main())
