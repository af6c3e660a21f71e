"""`slt xray` hardware attribution + DCN byte accounting
(`telemetry/xray.py`, `telemetry/dcn.py`; round 16).

Fast tier: classifier coverage over the known op-name inventory, parser
determinism against the committed fixture capture (a sanitized tiny-model
run — `tests/fixtures/xray/make_fixture.py` regenerates it), roofline
math on fabricated op costs, the attribution-sums-to-total invariant,
exposed-collective interval math, mesh-axis recovery, doctor verdicts
from a capture alone, the benchgate attribution columns, the /goodput
xray section + `slt top` HW pane, and the DCN counter round-trip through
all three instrumented consumers (remesh store wiring, ReplicatedStore
peer pushes, and a real one-round DiLoCo island).

The acceptance test profiles a REAL tiny-model training run on the CPU
tier-1 path and requires >= 95% of device-event time attributed to a
taxonomy class with the per-step breakdown summing to the goodput
ledger's step time within 5%.
"""

import glob
import json
import os
import socket
import tempfile
import threading

import pytest

from serverless_learn_tpu.telemetry import dcn, xray
from serverless_learn_tpu.telemetry.registry import (MetricsRegistry,
                                                     get_registry)

FIXTURE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tests", "fixtures", "xray", "tiny-train")
EXPECTED = os.path.join(os.path.dirname(FIXTURE_DIR),
                        "expected_summary.json")


# -- classifier --------------------------------------------------------------

def test_classifier_coverage_on_known_op_names():
    """Every op-name family the traces actually contain classifies into a
    non-unknown taxonomy class — including suffixed instances, async
    halves, and underscore-named fusions."""
    expect = {
        "dot.3": "compute", "fusion.12": "compute",
        "convolution.2": "compute", "tanh.4": "compute",
        "reduce-window": "compute", "custom-call.1": "compute",
        "convert_convert_fusion": "compute",
        "slice_concatenate_fusion.7": "compute",
        "all-reduce.3": "collective", "all-reduce-start.1": "collective",
        "all-reduce-done.1": "collective", "reduce-scatter": "collective",
        "all-gather.9": "collective", "collective-permute.2": "collective",
        "all-to-all": "collective", "send.1": "collective",
        "recv-done.4": "collective",
        "copy.4": "copy", "copy-start.2": "copy", "copy-done.2": "copy",
        "transpose.8": "copy", "bitcast-convert.1": "copy",
        "dynamic-update-slice.9": "copy",
        "infeed.5": "host", "outfeed-done.2": "host",
        "%fusion.3": "compute",
    }
    got = {name: xray.classify_op(name) for name in expect}
    assert got == expect
    # An unreadable name is unknown, not silently compute.
    assert xray.classify_op("TfrtCpuExecutable::Execute") == "unknown"


def test_collective_axis_recovery():
    axes = {"dp": 8, "fsdp": 2, "tp": 2}
    arg = {"long_name": "replica_groups={{0,1,2,3,4,5,6,7}}"}
    assert xray.collective_axis(arg, axes) == "dp"
    two = {"long_name": "replica_groups={{0,1},{2,3}}"}
    # Ambiguous: fsdp and tp both have size 2 -> not recoverable.
    assert xray.collective_axis(two, axes) is None
    assert xray.collective_axis(two, {"dp": 4, "tp": 2}) == "tp"
    assert xray.collective_axis({}, axes) is None
    assert xray.collective_axis(arg, None) is None


# -- parser determinism + fixture drift --------------------------------------

def test_parser_determinism_on_fixture():
    files = xray.find_trace_files(FIXTURE_DIR)
    assert files, f"fixture capture missing under {FIXTURE_DIR}"
    a = [xray.load_device_events(xray._read_json(fp)) for fp in files]
    b = [xray.load_device_events(xray._read_json(fp)) for fp in files]
    assert a == b
    s1 = xray.analyze_dir(FIXTURE_DIR)
    s2 = xray.analyze_dir(FIXTURE_DIR)
    assert s1 == s2


def test_fixture_matches_committed_summary():
    """The committed expected summary IS the drift gate `slt xray
    --self-check` enforces in CI; keep the test and the CLI in
    agreement."""
    with open(EXPECTED) as f:
        want = json.load(f)
    got = xray.analyze_dir(FIXTURE_DIR)
    assert {k: got.get(k) for k in want} == want
    # The fixture is a real capture of a ledger-bracketed run: the
    # stamped ledger's per-step time agrees with the trace's.
    assert 0.95 <= got["ledger_step_agreement"] <= 1.05
    assert got["coverage_frac"] >= 0.95
    assert got["per_collective_s"].get("all-reduce@dp", 0) > 0


def test_self_check_green():
    rep = xray.self_check()
    assert rep["ok"], rep["checks"]


# -- attribution invariants --------------------------------------------------

def test_attribution_sums_to_total():
    s = xray.analyze_events(xray.synthetic_events())
    summed = sum(r["seconds"] for r in s["classes"].values())
    assert abs(summed - s["device_time_s"]) < 1e-12
    for st in s["steps"]["per_step"]:
        assert abs(st["busy_s"] + st["idle_s"] - st["wall_s"]) < 1e-12
    # And on the real fixture capture:
    f = xray.analyze_dir(FIXTURE_DIR)
    summed = sum(r["seconds"] for r in f["classes"].values())
    assert abs(summed - f["device_time_s"]) < 1e-6 * max(
        1.0, f["device_time_s"])


def test_exposed_collective_interval_math():
    """A collective fully overlapped by compute is NOT exposed; a
    collective with nothing concurrent is fully exposed; a half-overlap
    splits exactly."""
    def ev(name, ts, dur):
        base = xray.op_base(name)
        return {"lane": "0/1", "name": name, "base": base,
                "class": xray.classify_op(base), "axis": None,
                "ts_us": float(ts), "dur_us": float(dur),
                "module": "jit_step"}

    events = [
        ev("dot.1", 0.0, 100.0),
        ev("all-reduce.2", 0.0, 100.0),    # fully overlapped
        ev("all-gather.3", 100.0, 100.0),  # fully exposed
        ev("dot.4", 200.0, 50.0),
        ev("reduce-scatter.5", 200.0, 100.0),  # half exposed
    ]
    s = xray.analyze_events(events)
    assert abs(s["exposed_comms_frac"] * s["window_s"] - 150e-6) < 1e-12


# -- roofline ----------------------------------------------------------------

def test_roofline_math_on_fabricated_costs():
    peak_f, peak_b = 100e12, 1e12  # ridge = 100 FLOPs/byte

    def ev(name, dur_us, flops, nbytes):
        base = xray.op_base(name)
        return {"lane": "0/1", "name": name, "base": base,
                "class": xray.classify_op(base), "axis": None,
                "ts_us": 0.0, "dur_us": dur_us, "module": "m",
                "flops": flops, "bytes": nbytes}

    events = [
        # 1e9 FLOPs in 20us at AI 1e5: roofline time 10us -> eff 0.5.
        ev("dot.1", 20.0, 1e9, 1e4),
        # 1e9 bytes in 2000us at AI 0.1: roofline 1000us -> eff 0.5.
        ev("fusion.2", 2000.0, 1e8, 1e9),
        ev("tanh.3", 30.0, None, None),  # uncosted: excluded
    ]
    roof = xray.roofline_verdicts(events, peak_f, peak_b)
    assert roof["n_costed"] == 2
    assert roof["ridge_flops_per_byte"] == 100.0
    by_op = {r["op"]: r for r in roof["ops"]}
    assert by_op["dot"]["bound"] == "compute-bound"
    assert by_op["fusion"]["bound"] == "hbm-bound"
    assert abs(by_op["dot"]["roofline_efficiency"] - 0.5) < 1e-6
    assert abs(by_op["fusion"]["roofline_efficiency"] - 0.5) < 1e-6
    # Time-weighted: 2000us of 2020us costed time is hbm-bound.
    assert abs(roof["hbm_bound_frac"] - 2000.0 / 2020.0) < 1e-6
    # No peaks -> no verdicts, never a guess.
    assert xray.roofline_verdicts(events, None, None) == {"n_costed": 0}

    mod = xray.module_roofline(1e12, 1e9, 0.02, peak_f, peak_b)
    assert mod["bound"] == "compute-bound"
    assert abs(mod["achieved_vs_roofline"] - 0.5) < 1e-6
    assert xray.module_roofline(None, 1e9, 0.02, peak_f, peak_b) is None


# -- acceptance: profiled tiny-model run vs the goodput ledger ---------------

def test_tiny_train_attribution_agrees_with_ledger(tmp_path):
    """The round-16 acceptance: on a profiled tiny-model training run
    (CPU tier-1 path), >= 95% of captured device-event time lands in a
    taxonomy class and the per-step breakdown sums to the goodput
    ledger's step time within 5%."""
    import jax

    from serverless_learn_tpu.config import (DataConfig, ExperimentConfig,
                                             MeshConfig, OptimizerConfig,
                                             TrainConfig)
    from serverless_learn_tpu.data.datasets import SyntheticSource
    from serverless_learn_tpu.telemetry import profiler
    from serverless_learn_tpu.telemetry.goodput import PhaseLedger
    from serverless_learn_tpu.training.train_step import build_trainer

    n_dev = len(jax.devices())
    cfg = ExperimentConfig(
        model="mlp_mnist",
        mesh=MeshConfig(dp=n_dev),
        optimizer=OptimizerConfig(name="sgd", learning_rate=0.1),
        train=TrainConfig(batch_size=1024),
        data=DataConfig(),
    )
    trainer = build_trainer(cfg)
    state = trainer.init()
    src = iter(SyntheticSource(trainer.bundle.make_batch, cfg.data,
                               cfg.train.batch_size, seed=0))
    batch = trainer.shard_batch(next(src))
    ledger = PhaseLedger(emit=False)
    ledger.ensure_started()
    # Wait for the whole step, not only its loss: the update's last ops
    # on a slow device thread otherwise land inside the capture (or fall
    # out of its end) when the host is loaded, and tear a step.
    with ledger.phase("compile"):
        state, m = trainer.step(state, batch)
        jax.block_until_ready((state, m))
    n_steps = 4
    out = str(tmp_path / "capture")
    with profiler.capture_session(out):
        for _ in range(n_steps):
            with ledger.phase("step"):
                state, m = trainer.step(state, batch)
                jax.block_until_ready((state, m))
    s = xray.analyze_dir(out, n_devices=n_dev)
    assert s["coverage_frac"] >= 0.95, s["classes"]
    assert s["steps"]["n"] == n_steps
    led_step = ledger.report()["phases"]["step"]["seconds"]
    assert led_step > 0
    ratio = s["steps"]["total_wall_s"] / led_step
    assert 0.95 <= ratio <= 1.05, (s["steps"], led_step)
    # The verdict names SOMETHING, and the breakdown is non-degenerate.
    assert s["verdict"]
    assert s["classes"].get("compute", {}).get("seconds", 0) > 0


# -- doctor ------------------------------------------------------------------

def test_doctor_names_plateau_cause_from_capture_alone():
    from serverless_learn_tpu.telemetry import doctor

    rep = doctor.diagnose(xray_dirs=[FIXTURE_DIR])
    verdict = rep["summary"]["verdict"]
    assert f"xray[{FIXTURE_DIR}]" in verdict
    assert rep["xray"][0]["summary"]["verdict"] in verdict


def test_doctor_reads_stamped_capture_meta(tmp_path):
    """A capture-meta.json with an xray stamp feeds the verdict without
    re-analysis — the alert-triggered capture path."""
    from serverless_learn_tpu.telemetry import doctor

    meta = {"event": "profile_capture", "reason": "alert:stale.train_step",
            "xray": {"verdict": "step is 31% exposed all-reduce on the "
                                "dp axis", "exposed_comms_frac": 0.31}}
    p = tmp_path / "capture-meta.json"
    p.write_text(json.dumps(meta))
    rep = doctor.diagnose(paths=[str(p)])
    assert "31% exposed all-reduce on the dp axis" in \
        rep["summary"]["verdict"]


# -- DCN byte accounting -----------------------------------------------------

def _dcn_bytes(consumer, registry=None):
    rows = dcn.snapshot(registry)
    for r in rows:
        if r["consumer"] == consumer:
            return r["tx_bytes"] + r["rx_bytes"]
    return 0.0


def test_instrument_store_counts_data_calls_only():
    from serverless_learn_tpu.training.checkpoint import LocalStore

    reg = MetricsRegistry()
    with tempfile.TemporaryDirectory() as root:
        store = dcn.instrument_store(LocalStore(root), "diloco",
                                     registry=reg)
        store.put("a/b", b"x" * 1000)
        assert store.get("a/b") == b"x" * 1000
        assert store.get_range("a/b", 0, 100) == b"x" * 100
        store.exists("a/b")
        store.list("a")
        rows = {r["consumer"]: r for r in dcn.snapshot(reg)}
        assert rows["diloco"]["tx_bytes"] == 1000
        assert rows["diloco"]["rx_bytes"] == 1100
        assert rows["diloco"]["transfers"] == 3
        assert rows["diloco"]["bandwidth_bytes_per_s"] is None or \
            rows["diloco"]["bandwidth_bytes_per_s"] > 0
        # Idempotent wrapping: same consumer never double-counts.
        again = dcn.instrument_store(store, "diloco", registry=reg)
        assert again is store
        # restore_sources re-wraps so failover reads stay attributed.
        label, src = store.restore_sources()[0]
        assert isinstance(src, dcn.InstrumentedStore)


def test_dcn_roundtrip_replica_push():
    """ReplicatedStore's async peer push (consumer=replica_push) counts
    bytes on the process registry."""
    from serverless_learn_tpu.training.checkpoint import LocalStore
    from serverless_learn_tpu.training.replicate import ReplicatedStore

    before = _dcn_bytes("replica_push")
    with tempfile.TemporaryDirectory() as root:
        peer = LocalStore(os.path.join(root, "peer"))
        rs = ReplicatedStore(LocalStore(os.path.join(root, "primary")),
                             peers=[peer], fanout=1)
        rs.put("ckpt/step-1", b"y" * 2048)
        assert rs.flush(timeout_s=10.0)
        rs.close()
    assert _dcn_bytes("replica_push") >= before + 2048


def test_dcn_roundtrip_remesh_store_wiring():
    """ElasticTrainer wires its checkpoint store through the remesh
    meter: bytes moved via the wrapped store count under
    consumer=remesh."""
    from serverless_learn_tpu.config import ExperimentConfig
    from serverless_learn_tpu.training.checkpoint import LocalStore
    from serverless_learn_tpu.training.elastic import ElasticTrainer

    before = _dcn_bytes("remesh")
    with tempfile.TemporaryDirectory() as root:
        et = ElasticTrainer(ExperimentConfig(model="mlp_mnist"),
                            LocalStore(root))
        et.ckpt.store.put("elastic/step-1", b"z" * 4096)
        assert et.ckpt.store.get("elastic/step-1") == b"z" * 4096
    assert _dcn_bytes("remesh") >= before + 2 * 4096


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_dcn_roundtrip_diloco_one_round():
    """A real one-island DiLoCo round: the delta PUT and anchor GET cross
    the instrumented store and land in slt_dcn_bytes_total
    {consumer=diloco}."""
    import jax

    from serverless_learn_tpu.config import (
        DataConfig, ExperimentConfig, LocalSGDConfig, MeshConfig,
        OptimizerConfig, TrainConfig)
    from serverless_learn_tpu.control.daemons import start_coordinator
    from serverless_learn_tpu.data.datasets import SyntheticSource
    from serverless_learn_tpu.parallel.mesh import make_mesh
    from serverless_learn_tpu.training.checkpoint import LocalStore
    from serverless_learn_tpu.training.diloco_dcn import DilocoIsland

    cfg = ExperimentConfig(
        model="mlp_mnist",
        mesh=MeshConfig(dp=1),
        optimizer=OptimizerConfig(name="sgd", learning_rate=0.1),
        train=TrainConfig(batch_size=16, donate_state=False),
        data=DataConfig(),
        local_sgd=LocalSGDConfig(outer="average", inner_steps=1,
                                 outer_lr=1.0, outer_momentum=0.0))
    port = _free_port()
    proc = start_coordinator(port=port, lease_ttl_ms=1500, sweep_ms=100)
    before = _dcn_bytes("diloco")
    try:
        mesh = make_mesh(cfg.mesh, devices=[jax.devices()[0]])

        def source_factory(wid):
            from serverless_learn_tpu.models.registry import get_model

            bundle = get_model(cfg.model, **cfg.model_overrides)
            return iter(SyntheticSource(bundle.make_batch, cfg.data,
                                        cfg.train.batch_size, seed=7))

        with tempfile.TemporaryDirectory() as root:
            isl = DilocoIsland(cfg, LocalStore(root),
                               f"127.0.0.1:{port}", "xraydcn", mesh=mesh,
                               source_factory=source_factory,
                               round_timeout_s=8.0)
            report = isl.run_rounds(1)
            isl.stop()
        assert report.rounds_done == 1
    finally:
        proc.terminate()
        proc.wait(timeout=5)
    assert _dcn_bytes("diloco") > before


# -- /goodput + slt top ------------------------------------------------------

def test_goodput_endpoint_serves_xray_section():
    from serverless_learn_tpu.telemetry.exporter import (MetricsExporter,
                                                         fetch_text)

    summary = xray.analyze_events(xray.synthetic_events(),
                                  device_kind="TPU v5 lite")
    xray.set_last_summary(summary)
    srv = MetricsExporter(registry=MetricsRegistry()).start()
    try:
        gp = json.loads(fetch_text(srv.addr, "/goodput"))
        assert gp["xray"]["verdict"] == summary["verdict"]
        assert gp["xray"]["exposed_comms_frac"] == \
            summary["exposed_comms_frac"]
    finally:
        srv.stop()
        xray.set_last_summary(None)


def test_top_renders_hw_pane():
    """`slt top --once` renders the HW pane from the /goodput xray
    section and the per-consumer DCN bandwidth gauges."""
    import io

    from serverless_learn_tpu.telemetry.exporter import MetricsExporter
    from serverless_learn_tpu.telemetry.top import run_top

    reg = MetricsRegistry()
    dcn.record_transfer("diloco", "tx", 10_000_000, 1.0, registry=reg)
    dcn.record_transfer("remesh", "rx", 2_000_000, 1.0, registry=reg)
    xray.set_last_summary(xray.analyze_events(
        xray.synthetic_events(), device_kind="TPU v5 lite"))
    srv = MetricsExporter(registry=reg).start()
    try:
        out = io.StringIO()
        assert run_top([srv.addr], once=True, stream=out) == 0
        text = out.getvalue()
        assert "HW" in text
        assert "diloco=10.0MB/s" in text
        assert "remesh=2.0MB/s" in text
        assert "exposed all-reduce" in text
    finally:
        srv.stop()
        xray.set_last_summary(None)


# -- benchgate attribution columns -------------------------------------------

def test_benchgate_attribution_columns():
    from serverless_learn_tpu.telemetry import benchgate

    base = {"metric": "resnet18_cifar_train_samples_per_sec_per_chip",
            "device_kind": "TPU v5 lite", "batch_per_chip": 8192}
    history = [dict(base, value=34000.0, exposed_comms_frac=0.10,
                    hw_util=0.80)]
    flat = dict(base, value=34100.0, exposed_comms_frac=0.12, hw_util=0.78)
    check = benchgate.gate_entry(flat, history)
    assert check["ok"], check
    # Collectives newly exposed: same throughput, +20pts exposed -> fail.
    worse = dict(base, value=34100.0, exposed_comms_frac=0.30,
                 hw_util=0.80)
    check = benchgate.gate_entry(worse, history)
    assert not check["ok"]
    assert any(a["column"] == "exposed_comms_frac" and not a["ok"]
               for a in check["attribution"])
    # Hardware got lazier: hw_util collapse fails even with value flat.
    lazy = dict(base, value=34100.0, hw_util=0.50)
    check = benchgate.gate_entry(lazy, history)
    assert not check["ok"]
    # Rows predating the columns neither gate nor mask.
    old = dict(base, value=34100.0)
    assert benchgate.gate_entry(old, history)["ok"]
    assert benchgate.gate_entry(
        dict(base, value=34100.0, exposed_comms_frac=0.5),
        [dict(base, value=34000.0)])["ok"]


def test_bench_gate_dry_run_covers_attribution_history(tmp_path):
    """The CI shape: `slt bench --gate --dry-run` over a history whose
    rows carry attribution columns — green when flat, red when the
    latest row exposes collectives."""
    from serverless_learn_tpu.telemetry.benchgate import run_gate

    base = {"metric": "resnet18_cifar_train_samples_per_sec_per_chip",
            "device_kind": "TPU v5 lite", "batch_per_chip": 8192}
    good = [dict(base, value=34000.0, exposed_comms_frac=0.10),
            dict(base, value=34100.0, exposed_comms_frac=0.11)]
    p = tmp_path / "hist.json"
    p.write_text(json.dumps(good))
    assert run_gate(str(p))["ok"]
    bad = good[:1] + [dict(base, value=34100.0, exposed_comms_frac=0.40)]
    p.write_text(json.dumps(bad))
    rep = run_gate(str(p))
    assert not rep["ok"]
    assert rep["regressions"]


# -- registry hygiene --------------------------------------------------------

def test_dcn_metrics_on_global_registry_render():
    """The instrumented consumers write the process registry; the
    Prometheus rendering must carry the consumer/direction labels `slt
    top` drills into."""
    dcn.record_transfer("replica_push", "tx", 123, 0.01)
    text = get_registry().render_prometheus()
    assert 'slt_dcn_bytes_total{consumer="replica_push",direction="tx"}' \
        in text
    assert "slt_dcn_effective_bandwidth_bytes_per_s" in text
