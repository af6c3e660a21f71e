"""Round 17: training-quality observability (telemetry/numerics.py,
training/audit.py, `slt numerics`).

Covers the ISSUE-12 acceptance surface: stat math exactness on
fabricated tensors, injected-NaN provenance naming the seeded layer and
faulting step, fingerprint bisection finding a seeded step-k subtree
divergence between two recorded runs, the loss-health detectors firing
through the HealthEngine into a flight dump, donation safety of the
cadence-gated fetch, and (slow tier) a tiny real train run proving the
host-sync cadence and the < 2% ledger overhead bound.
"""

import json
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from serverless_learn_tpu.config import (
    DataConfig, ExperimentConfig, HealthConfig, MeshConfig, NumericsConfig,
    OptimizerConfig, TrainConfig)
from serverless_learn_tpu.telemetry import numerics


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {"dense_0": {"kernel": rng.normal(size=(8, 4)).astype(np.float32),
                        "bias": rng.normal(size=(4,)).astype(np.float32)},
            "head": {"kernel": rng.normal(size=(4, 2)).astype(np.float32)}}


# -- stat math exactness ------------------------------------------------------


def test_tree_stats_exact_vs_numpy():
    tree = _tree()
    stats = jax.device_get(numerics.tree_stats(tree))
    for name in ("dense_0", "head"):
        leaves = [np.asarray(l) for l in
                  jax.tree_util.tree_leaves(tree[name])]
        flat = np.concatenate([l.ravel() for l in leaves])
        np.testing.assert_allclose(float(stats[name]["l2"]),
                                   np.sqrt((flat ** 2).sum()), rtol=1e-6)
        np.testing.assert_allclose(float(stats[name]["rms"]),
                                   np.sqrt((flat ** 2).sum()) /
                                   np.sqrt(flat.size), rtol=1e-6)
        np.testing.assert_allclose(float(stats[name]["absmax"]),
                                   np.abs(flat).max(), rtol=1e-6)
        assert int(stats[name]["nonfinite"]) == 0


def test_tree_stats_nonfinite_counted_not_poisoning():
    """NaN/Inf are COUNTED but excluded from the norms — the detectors
    baseline on the norms, and one NaN must not turn every later z-score
    into NaN-vs-NaN."""
    tree = _tree()
    tree["head"]["kernel"] = tree["head"]["kernel"].copy()
    tree["head"]["kernel"][0, 0] = np.nan
    tree["head"]["kernel"][1, 0] = np.inf
    stats = jax.device_get(numerics.tree_stats(tree))
    assert int(stats["head"]["nonfinite"]) == 2
    assert math.isfinite(float(stats["head"]["l2"]))
    assert math.isfinite(float(stats["head"]["absmax"]))


def test_global_norm_matches_numpy():
    tree = _tree(3)
    got = float(jax.device_get(numerics.global_norm(tree)))
    want = float(np.sqrt(sum((np.asarray(l) ** 2).sum()
                             for l in jax.tree_util.tree_leaves(tree))))
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_step_summary_update_ratio_exact():
    params = _tree(1)
    grads = jax.tree_util.tree_map(lambda x: 0.1 * x, params)
    updates = jax.tree_util.tree_map(lambda x: -0.01 * x, params)
    out = jax.device_get(numerics.step_summary(params, grads, updates,
                                               loss=jnp.float32(1.0)))
    p_l2 = float(out["param/head/l2"])
    u_l2 = float(out["update/head/l2"])
    np.testing.assert_allclose(float(out["ratio/head"]), u_l2 / p_l2,
                               rtol=1e-6)
    # global rollups present; updates = -0.01 * params => exact ratio
    np.testing.assert_allclose(float(out["update_ratio"]), 0.01, rtol=1e-5)
    assert int(out["nonfinite_total"]) == 0
    assert "fp/dense_0/l2" in out and "fp/head/c0" in out


def test_fingerprint_chunks_localize_perturbation():
    tree = _tree(2)
    fa = jax.device_get(numerics.fingerprint(tree))
    tree2 = jax.tree_util.tree_map(np.array, tree)
    tree2["dense_0"]["kernel"] = tree2["dense_0"]["kernel"].copy()
    tree2["dense_0"]["kernel"][0, 0] += 1.0
    fb = jax.device_get(numerics.fingerprint(tree2))
    # untouched subtree agrees exactly
    for k, v in fa["head"].items():
        assert float(v) == float(fb["head"][k])
    worst = numerics.diff_fingerprints(
        {k: {f: float(x) for f, x in d.items()} for k, d in fa.items()},
        {k: {f: float(x) for f, x in d.items()} for k, d in fb.items()})
    assert worst is not None and worst["subtree"] == "dense_0"


def _pinned_tree():
    k = jax.random.split(jax.random.PRNGKey(7), 6)
    return {"a": {"w": jax.random.normal(k[0], (37, 11), jnp.float32),
                  "b": jax.random.normal(k[1], (5,), jnp.bfloat16)},
            "b": {"x": {"y": jax.random.normal(k[2], (3, 4, 5)) * 3,
                        "z": jnp.array([1.0, jnp.inf, -2.0], jnp.float32)}},
            "c": jax.random.normal(k[3], (2,), jnp.float32)}


def test_fingerprint_reduced_leaf_by_leaf_keeps_the_digest():
    """The digest is reduced leaf by leaf, with no float32 copy of a whole
    subtree; what it renders is the digest the concatenating version gave
    (these strings are that version's, over mixed dtypes, a non-finite
    element, chunk ranges that cross leaves and a subtree shorter than
    its chunks)."""
    tree = _pinned_tree()
    assert numerics.weight_version(tree) == "263e4513bf0b"
    assert numerics.weight_version(tree, depth=2, chunks=3) == "6a73a63a48e1"
    fp = jax.device_get(numerics.fingerprint(tree))
    assert float(fp["c"]["c1"]) == pytest.approx(-0.4773978590965271)
    assert float(fp["c"]["c2"]) == float(fp["c"]["c3"]) == 0.0
    assert float(fp["b"]["absmax"]) == pytest.approx(7.170856475830078)
    # Inside a jitted step it reads the same.
    jitted = jax.device_get(jax.jit(numerics.fingerprint)(tree))
    for name, digest in fp.items():
        for key, value in digest.items():
            assert float(jitted[name][key]) == pytest.approx(float(value),
                                                             rel=1e-6)


# -- NaN/Inf provenance -------------------------------------------------------


def _mlp_bundle():
    from serverless_learn_tpu.models.registry import get_model

    return get_model("mlp_mnist", features=(16, 16), dtype=jnp.float32)


def test_provenance_names_seeded_nan_param(devices):
    bundle = _mlp_bundle()
    rng = jax.random.PRNGKey(0)
    x = jnp.zeros((4, 28, 28, 1), jnp.float32)
    params = jax.device_get(bundle.module.init(rng, x))["params"]
    params["dense_1"]["kernel"] = np.asarray(
        params["dense_1"]["kernel"]).copy()
    params["dense_1"]["kernel"][0, 0] = np.nan
    rep = numerics.nonfinite_provenance(bundle.module, params,
                                        {"image": np.zeros((4, 28, 28, 1),
                                                           np.float32)})
    assert rep["first"] == "params:dense_1"
    assert rep["kind"] == "nan"
    assert rep["param"]["subtree"] == "dense_1"


def test_provenance_names_overflowing_activation(devices):
    """Params finite but huge: the forward overflows to inf INSIDE the
    model — the intermediates sweep (not the param scan) must name the
    first overflowing layer."""
    bundle = _mlp_bundle()
    rng = jax.random.PRNGKey(0)
    x = jnp.ones((2, 28, 28, 1), jnp.float32)
    params = jax.device_get(bundle.module.init(rng, x))["params"]
    params["dense_1"]["kernel"] = np.full_like(
        np.asarray(params["dense_1"]["kernel"]), 3.0e38)
    rep = numerics.nonfinite_provenance(bundle.module, params,
                                        {"image": np.ones((2, 28, 28, 1),
                                                          np.float32)})
    assert rep["param"] is None  # 3e38 is a finite float32
    assert rep["first"] is not None
    assert rep["first"].startswith("intermediates:dense_1")
    assert rep["kind"] == "inf"


# -- fingerprint bisection between two recorded runs --------------------------


def _numerics_cfg(**over):
    return ExperimentConfig(
        model="mlp_mnist",
        model_overrides=dict(features=(16, 16), dtype=jnp.float32),
        mesh=MeshConfig(dp=8),
        optimizer=OptimizerConfig(name="sgd", learning_rate=0.05,
                                  momentum=0.0),
        train=TrainConfig(batch_size=8, num_steps=8, dtype="float32",
                          param_dtype="float32"),
        data=DataConfig(),
        numerics=NumericsConfig(enabled=True, cadence=1, **over))


def _run_recording_fps(perturb_at=None, steps=8):
    """Run the real jitted trainer, recording per-step fingerprint
    records from the step's in-graph numerics output; optionally perturb
    one subtree's params mid-run (the seeded divergence)."""
    from serverless_learn_tpu.training.train_step import build_trainer

    cfg = _numerics_cfg()
    tr = build_trainer(cfg)
    state = tr.init()
    batch = tr.bundle.make_batch(np.random.default_rng(0), cfg.data, 8)
    sharded = tr.shard_batch(batch)
    records = []
    for t in range(steps):
        if perturb_at is not None and t + 1 == perturb_at:
            bumped = jax.tree_util.tree_map(
                lambda x: np.asarray(jax.device_get(x)),
                state.params)
            bumped["head"]["kernel"] = bumped["head"]["kernel"] + 1e-3
            state = state.replace(params=jax.tree_util.tree_map(
                lambda h, p: jax.device_put(h.astype(p.dtype), p.sharding),
                bumped, state.params))
        state, metrics = tr.step(state, sharded)
        host = {k: float(v) for k, v in
                jax.device_get(metrics["numerics"]).items()}
        fp = {}
        for key, val in host.items():
            parts = key.split("/")
            if parts[0] == "fp":
                fp.setdefault(parts[1], {})[parts[2]] = val
        records.append({"event": "numerics_fingerprint", "step": t + 1,
                        "fp": fp})
    return records


def test_fingerprint_bisection_finds_seeded_divergence(devices):
    ref = _run_recording_fps()
    div = _run_recording_fps(perturb_at=5)
    rep = numerics.diff_fingerprint_logs(ref, div)
    assert rep["diverged"], rep
    assert rep["first_divergent_step"] == 5, rep
    assert rep["subtree"] == "head", rep
    assert rep["last_agreeing_step"] == 4
    # identical runs agree everywhere
    rep2 = numerics.diff_fingerprint_logs(ref, _run_recording_fps())
    assert not rep2["diverged"], rep2
    assert rep2["steps_compared"] == 8


# -- parity harness -----------------------------------------------------------


def test_parity_harness_identical_and_perturbed(devices):
    from serverless_learn_tpu.training.train_step import build_trainer

    cfg = _numerics_cfg()
    tr = build_trainer(cfg)
    batch = tr.shard_batch(
        tr.bundle.make_batch(np.random.default_rng(1), cfg.data, 8))
    h = numerics.ParityHarness(tr.step, tr.step, tr.init(), tr.init())
    for _ in range(3):
        h.step(batch)
    rep = h.report()
    assert rep["within_tolerance"], rep
    assert all(c["max_ulp"] == 0 for c in rep["subtrees"].values()), rep

    # candidate starts perturbed -> first step already exceeds
    bad = tr.init()
    bumped = jax.tree_util.tree_map(
        lambda x: np.asarray(jax.device_get(x)), bad.params)
    bumped["dense_0"]["kernel"] = bumped["dense_0"]["kernel"] + 1e-2
    bad = bad.replace(params=jax.tree_util.tree_map(
        lambda hh, p: jax.device_put(hh.astype(p.dtype), p.sharding),
        bumped, bad.params))
    h2 = numerics.ParityHarness(tr.step, tr.step, tr.init(), bad)
    h2.step(batch)
    rep2 = h2.report(rtol=1e-5, atol=1e-6)
    assert not rep2["within_tolerance"]
    assert rep2["first_exceeded"]["subtree"] == "dense_0"


# -- loss-health detectors through the HealthEngine ---------------------------


def _engine(tmp_path=None, **hc):
    from serverless_learn_tpu.telemetry.health import HealthEngine
    from serverless_learn_tpu.telemetry.registry import MetricsRegistry

    sink = []
    eng = HealthEngine(
        registry=MetricsRegistry(),
        config=HealthConfig(numerics_spike_z=4.0, **hc),
        emit=sink.append, clock=time.time,
        flight_dir=str(tmp_path) if tmp_path else None)
    return eng, sink


def test_loss_spike_fires_health_engine_and_flight_dump(tmp_path, devices):
    numerics.clear_steps()
    eng, sink = _engine(tmp_path)
    t = 1_000_000.0
    for i in range(16):
        numerics.note_step({"step": i + 1, "loss": 2.0 - 0.02 * i,
                            "grad_norm": 1.0, "nonfinite": 0})
        eng.sample_once(now=t)
        t += 1.0
    assert not eng.alerts(firing_only=True)
    # a massive spike (> 2x the z threshold) escalates to critical ->
    # the engine writes a flight dump with the firing alert attached
    numerics.note_step({"step": 17, "loss": 500.0, "grad_norm": 1.0,
                        "nonfinite": 0})
    eng.sample_once(now=t)
    firing = eng.alerts(firing_only=True)
    spikes = [a for a in firing if a["alert"] == "numerics.loss_spike"]
    assert spikes and spikes[0]["severity"] == "critical", firing
    assert any(r.get("alert") == "numerics.loss_spike" for r in sink)
    dumps = list(tmp_path.glob("flight-*.json"))
    assert dumps, "critical numerics alert must write a flight dump"
    payload = json.loads(dumps[0].read_text())
    assert payload["reason"] == "alert:numerics.loss_spike"
    numerics.clear_steps()


def test_nonfinite_record_fires_critical_alert(devices):
    numerics.clear_steps()
    eng, sink = _engine()
    t = 1_000_000.0
    numerics.note_step({"step": 3, "loss": float("nan"), "nonfinite": 42,
                        "first": "params:dense_1"})
    eng.sample_once(now=t)
    firing = eng.alerts(firing_only=True)
    nf = [a for a in firing if a["alert"] == "numerics.nonfinite"]
    assert nf and nf[0]["severity"] == "critical"
    assert "dense_1" in nf[0]["message"]
    numerics.clear_steps()


def test_plateau_and_explosion_detectors():
    lh = numerics.LossHealth(plateau_window=10, plateau_min_rel=1e-3,
                             explode_z=6.0, min_samples=4)
    fired = []
    for i in range(30):
        loss = 2.0 - 0.05 * min(i, 10)  # improves then flatlines
        v = lh.update(i + 1, loss, grad_norm=1.0)
        if v["loss_plateau"]:
            fired.append(i + 1)
    assert fired and fired[0] >= 21, fired  # window after the last best
    v = lh.update(31, 1.5, grad_norm=1e6)
    assert v["grad_explosion"] is not None
    assert v["grad_explosion"]["severity"] == "critical"


# -- end-to-end: seeded NaN injection through the real loop -------------------


def _train_cfg(**num_over):
    return ExperimentConfig(
        model="mlp_mnist",
        model_overrides=dict(features=(16, 16), dtype=jnp.float32),
        mesh=MeshConfig(dp=8),
        optimizer=OptimizerConfig(name="sgd", learning_rate=0.05,
                                  momentum=0.0),
        train=TrainConfig(batch_size=8, num_steps=12, dtype="float32",
                          param_dtype="float32", log_every=100),
        data=DataConfig(),
        numerics=NumericsConfig(enabled=True, cadence=4, **num_over))


def test_injected_nan_is_named_with_step_and_layer(devices):
    """The acceptance path: a seeded mid-run NaN in one subtree's
    gradient is detected AT the faulting step (forced off-cadence fetch
    from the already-fetched metrics), provenance names the seeded
    layer, and the record trail carries both — with donate_state=True,
    proving the sweep reads pre-donation values."""
    from serverless_learn_tpu.telemetry.registry import MetricsRegistry
    from serverless_learn_tpu.training.audit import NumericsAuditor
    from serverless_learn_tpu.training.loop import run_training
    from serverless_learn_tpu.training.train_step import build_trainer

    numerics.clear_steps()
    cfg = _train_cfg(inject_nan_step=6, inject_nan_subtree="dense_1")
    assert cfg.train.donate_state  # the hazard under test
    reg = MetricsRegistry()
    events = []
    trainer = build_trainer(cfg)
    auditor = NumericsAuditor(cfg, registry=reg, bundle=trainer.bundle,
                              emit=events.append)
    run_training(cfg, trainer=trainer, auditor=auditor)
    bad = [r for r in events if r["event"] == "numerics_nonfinite"]
    assert bad, events
    assert bad[0]["step"] == 6
    assert bad[0]["provenance"]["first"] == "params:dense_1"
    assert "grad:dense_1" in bad[0]["bad_subtrees"]
    assert auditor.nonfinite_steps[0] == 6
    assert reg.counter("slt_numerics_nonfinite_total").value >= 1
    # the /numerics payload is host floats only (json-serializable: no
    # retained device references anywhere a scrape could reach)
    json.dumps(numerics.endpoint_payload())
    numerics.clear_steps()


def test_provenance_prefers_host_shadow(devices):
    """With a shadow_fn wired (the checkpointer's note_state shadow),
    provenance reads it instead of the live state — the donated-buffer-
    safe path."""
    from serverless_learn_tpu.telemetry.registry import MetricsRegistry
    from serverless_learn_tpu.training.audit import NumericsAuditor
    from serverless_learn_tpu.training.train_step import build_trainer

    numerics.clear_steps()
    cfg = _train_cfg()
    tr = build_trainer(cfg)
    state = tr.init()
    shadow = jax.device_get(state)
    events = []
    auditor = NumericsAuditor(cfg, registry=MetricsRegistry(),
                              bundle=tr.bundle,
                              shadow_fn=lambda: (shadow, 0),
                              emit=events.append)
    auditor._on_nonfinite(5, {"nonfinite_total": 1.0,
                              "grad/dense_1/nonfinite": 1.0},
                          state=None, batch={"image": np.zeros(
                              (2, 28, 28, 1), np.float32)})
    assert auditor.last_provenance["source"] == "host_shadow"
    assert events and events[0]["event"] == "numerics_nonfinite"
    numerics.clear_steps()


# -- cadence + overhead acceptance (slow tier) --------------------------------


def test_numerics_cadence_and_overhead_acceptance(devices):
    """Tiny real train run with numerics enabled: stats present, host
    syncs exactly at the cadence (not per step), and the `numerics`
    ledger phase under 2% of the run's wall-clock."""
    from serverless_learn_tpu.telemetry import goodput
    from serverless_learn_tpu.telemetry.registry import MetricsRegistry
    from serverless_learn_tpu.training.audit import NumericsAuditor
    from serverless_learn_tpu.training.loop import run_training
    from serverless_learn_tpu.training.train_step import build_trainer

    numerics.clear_steps()
    cfg = _train_cfg().override(
        train=TrainConfig(batch_size=8, num_steps=40, dtype="float32",
                          param_dtype="float32", log_every=100))
    reg = MetricsRegistry()
    events = []
    ledger = goodput.PhaseLedger(emit=False)
    prev = goodput.set_ledger(ledger)
    try:
        trainer = build_trainer(cfg)
        auditor = NumericsAuditor(cfg, registry=reg,
                                  bundle=trainer.bundle,
                                  emit=events.append)
        run_training(cfg, trainer=trainer, auditor=auditor)
    finally:
        goodput.set_ledger(prev)
    stats = [r for r in events if r["event"] == "numerics_stats"]
    assert stats, "no numerics_stats records emitted"
    # cadence 4 over 40 steps = 10 fetches, none forced (run is clean)
    assert auditor.fetches == 10
    assert reg.counter("slt_numerics_fetches_total").value == 10
    assert all(r["step"] % 4 == 0 for r in stats)
    assert all(r["nonfinite"] == 0 for r in stats)
    rep = ledger.report()
    num_phase = rep["phases"].get("numerics", {"seconds": 0.0})
    assert num_phase["seconds"] < 0.02 * rep["total_s"], rep
    numerics.clear_steps()


# -- CLI ----------------------------------------------------------------------


def test_cli_numerics_diff_and_selfcheck(tmp_path, capsys):
    from serverless_learn_tpu.cli import main

    tree = _tree(7)
    recs_a = [{"event": "numerics_fingerprint", "step": s,
               "fp": {k: {f: float(v) for f, v in d.items()}
                      for k, d in jax.device_get(
                          numerics.fingerprint(tree)).items()}}
              for s in range(4)]
    recs_b = [json.loads(json.dumps(r)) for r in recs_a]
    recs_b[2]["fp"]["head"]["sum"] += 0.5
    recs_b[3]["fp"]["head"]["sum"] += 0.5
    a, b = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    a.write_text("".join(json.dumps(r) + "\n" for r in recs_a))
    b.write_text("".join(json.dumps(r) + "\n" for r in recs_b))
    rc = main(["numerics", "diff", str(a), str(b), "--compact"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1 and out["diverged"]
    assert out["first_divergent_step"] == 2 and out["subtree"] == "head"

    rc = main(["numerics", "diff", str(a), str(a), "--compact"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0 and not out["diverged"]

    rc = main(["numerics", "--self-check", "--compact"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0 and out["ok"], out


def test_cli_numerics_summary_flags_incidents(tmp_path, capsys):
    from serverless_learn_tpu.cli import main

    log = tmp_path / "ev.jsonl"
    recs = [{"event": "numerics_stats", "step": 4, "grad_norm": 1.5,
             "update_ratio": 0.001, "nonfinite": 0, "subtrees": {}},
            {"event": "numerics_nonfinite", "step": 6,
             "first": "params:dense_1",
             "bad_subtrees": ["grad:dense_1"], "nonfinite": 3}]
    log.write_text("".join(json.dumps(r) + "\n" for r in recs))
    rc = main(["numerics", "summary", str(log), "--compact"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1  # incidents present -> nonzero, scriptable
    assert out["nonfinite_incidents"][0]["first"] == "params:dense_1"
    assert out["grad_norm"]["last"] == 1.5


def test_numerics_config_from_dict():
    cfg = ExperimentConfig.from_dict(
        {"numerics": {"enabled": True, "cadence": 7,
                      "inject_nan_step": 3}})
    assert cfg.numerics.enabled and cfg.numerics.cadence == 7
    assert cfg.numerics.inject_nan_step == 3
    assert not ExperimentConfig.from_dict({}).numerics.enabled
