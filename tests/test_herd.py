"""`slt herd` (round 19): the vmapped many-client DiLoCo harness.

What the tests pin:

* the ISSUE-19 acceptance: 256 vmapped clients with non-IID shards and
  speed skew, a FaultPlan killing >20% of the herd mid-round, quorum-0.8
  participation — byte-identical same-seed reports, the poisoned
  worker's NaN delta quarantined (never reaching the anchor), and
  `slt doctor` naming the quarantined worker + partial participation
  from the events log alone, with membership agreement (real SWIM
  gossip) asserted with training in the loop;
* loss parity of partial (quorum 0.8) vs full participation under
  heterogeneity — the degradation policy's "safe to run degraded" claim;
* the norm-outlier arm of the quarantine gate + readmission;
* late-delta policies (drop vs staleness-discount);
* churn: a killed-and-restarted worker rejoins with fresh inner
  optimizer state and contributes deltas again;
* the `slt chaos herd` CLI incl. `--smoke`.
"""

import dataclasses
import json
import os

import pytest

from serverless_learn_tpu.chaos.plan import FaultPlan
from serverless_learn_tpu.training.herd import (HerdSim, HerdSpec,
                                                parity_specs, run_smoke,
                                                run_wire_ab,
                                                wire_parity_specs)

ACCEPT_SPEC = HerdSpec(
    n_workers=256, rounds=5, inner_steps=2, batch_size=4, features=(16,),
    quorum_fraction=0.8, round_timeout_s=1.0, speed_skew=0.5,
    poison_worker=200, poison_round=2)

# Kill 21% of the herd while round 0's deltas are in flight (round 0
# starts at bootstrap_s=2.0; arrivals land from ~2.05 on).
ACCEPT_PLAN = [{"at": 2.08, "op": "kill", "frac": 0.21}]


def _load_events(path):
    with open(path) as f:
        return [json.loads(l) for l in f if l.strip()]


def test_herd_acceptance_churn_determinism_quarantine(tmp_path):
    """The ISSUE-19 acceptance scenario, end to end."""
    from serverless_learn_tpu.telemetry import doctor

    events = str(tmp_path / "herd-events.jsonl")

    def run(log=None):
        rep = HerdSim(ACCEPT_SPEC, seed=3,
                      plan=FaultPlan.from_obj(ACCEPT_PLAN),
                      events_log=log).run(duration_s=45.0)
        rep.pop("wall_time_s")
        return rep

    rep = run(events)
    assert rep["ok"], rep["violations"]
    herd = rep["herd"]
    # >= 20% of 256 workers killed mid-round, and the run still
    # completed every scheduled round at quorum.
    assert len(rep["killed_live"]) >= 52
    assert herd["rounds_completed"] == 5
    assert herd["committed_step"] == 10
    # real membership agreement WITH training in the loop
    assert rep["converged"], rep["violations"]
    assert rep["dissemination_periods"] <= rep["convergence_bound_periods"]
    # quorum 0.8 closed rounds short of full participation
    assert all(0.5 <= p <= 1.0 for p in herd["participation"])
    assert herd["mean_participation"] < 1.0
    # the poisoned worker was quarantined and the anchor stayed finite
    assert "200" in herd["quarantined"]
    assert herd["quarantined"]["200"]["reason"] == "nonfinite"
    assert 2 in herd["quarantined"]["200"]["rounds"]
    assert herd["anchor_finite"]
    # training learned through all of it
    assert herd["final_eval_loss"] < herd["init_eval_loss"] - 0.2

    # byte-identical same-seed reports (the debuggability contract)
    assert json.dumps(rep, sort_keys=True) == \
        json.dumps(run(), sort_keys=True)

    # doctor, fed ONLY the events log, names the quarantined worker and
    # the partial participation
    verdict = doctor.diagnose([events], bench_history="/nonexistent"
                              )["summary"]["verdict"]
    assert "quarantin" in verdict and "200" in verdict, verdict
    assert "participation" in verdict, verdict
    # and the per-round records score stragglers (slow workers missed
    # quorum repeatedly under speed skew)
    d = doctor.diagnose([events], bench_history="/nonexistent")
    assert any(s["flagged"] for s in d["stragglers"].values())
    # ground truth for every kill is in the same log
    recs = _load_events(events)
    kills = [r for r in recs if r.get("event") == "fault_injected"
             and r.get("op") == "kill"]
    assert kills and len(kills[0]["nodes"]) >= 52


def test_partial_participation_loss_parity():
    """Quorum 0.8 under speed skew must land within tolerance of full
    participation — partial participation degrades wall-clock waits,
    not the model."""
    part_spec, full_spec = parity_specs(256, 0.8)
    rp = HerdSim(part_spec, seed=7).run(duration_s=14.0)
    rf = HerdSim(full_spec, seed=7).run(duration_s=14.0)
    hp, hf = rp["herd"], rf["herd"]
    assert not [v for v in rp["violations"]], rp["violations"]
    assert not [v for v in rf["violations"]], rf["violations"]
    assert hp["rounds_completed"] == part_spec.rounds
    assert hf["rounds_completed"] == full_spec.rounds
    assert hp["mean_participation"] < hf["mean_participation"]
    init = hp["init_eval_loss"]
    assert hf["init_eval_loss"] == init  # same seed => same init
    # both learn, and partial tracks full within 5% of the init scale
    assert hp["final_eval_loss"] < init - 0.25
    assert hf["final_eval_loss"] < init - 0.25
    assert abs(hp["final_eval_loss"] - hf["final_eval_loss"]) \
        < 0.05 * init, (hp["final_eval_loss"], hf["final_eval_loss"])


def test_norm_outlier_quarantined_then_readmitted(tmp_path):
    """A finite but wildly out-of-family delta (scaled 1000x) trips the
    outlier arm of the gate; the worker's next clean round resolves the
    alert (readmission)."""
    events = str(tmp_path / "outlier.jsonl")
    spec = HerdSpec(n_workers=24, rounds=3, inner_steps=2, batch_size=4,
                    features=(16,), round_timeout_s=2.0,
                    scale_worker=5, scale_round=1)
    rep = HerdSim(spec, seed=1, events_log=events).run(duration_s=20.0)
    assert rep["ok"], rep["violations"]
    q = rep["herd"]["quarantined"]
    assert q == {"5": {"rounds": [1], "reason": "norm_outlier"}}
    assert rep["herd"]["anchor_finite"]
    alerts = [r for r in _load_events(events)
              if r.get("alert") == "diloco.delta_quarantined"]
    states = [a["state"] for a in alerts]
    assert "firing" in states and "resolved" in states, alerts


def test_late_delta_policies_drop_vs_discount():
    """Heavy speed skew + a tight quorum strands stragglers past the
    close; 'drop' discards their deltas, 'discount' folds them in as
    stale discounted updates — the two runs must actually diverge."""
    base = HerdSpec(n_workers=16, rounds=3, inner_steps=2, batch_size=4,
                    features=(16,), quorum_fraction=0.5,
                    speed_skew=1.0, round_timeout_s=4.0)
    import dataclasses

    drop = HerdSim(base, seed=2).run(duration_s=25.0)
    disc = HerdSim(dataclasses.replace(base, late_policy="discount"),
                   seed=2).run(duration_s=25.0)
    assert drop["herd"]["late_deltas"]["dropped"] > 0
    assert drop["herd"]["late_deltas"]["discounted"] == 0
    assert disc["herd"]["late_deltas"]["discounted"] > 0
    # the discounted stale updates moved the anchor
    assert drop["herd"]["final_eval_loss"] != disc["herd"]["final_eval_loss"]


def test_restarted_worker_rejoins_and_contributes(tmp_path):
    """Kill one worker mid-run, restart it two rounds later: it must
    post deltas again (with reset inner optimizer state) and the herd
    report must stay clean."""
    events = str(tmp_path / "rejoin.jsonl")
    spec = HerdSpec(n_workers=12, rounds=8, inner_steps=2, batch_size=4,
                    features=(16,), round_timeout_s=2.0,
                    base_step_s=0.2, quorum_fraction=0.8)
    plan = FaultPlan.from_obj([
        {"at": 2.5, "op": "kill", "node": "node-5"},
        {"at": 4.5, "op": "restart", "node": "node-5"}])
    rep = HerdSim(spec, seed=6, plan=plan,
                  events_log=events).run(duration_s=40.0)
    assert rep["ok"], rep["violations"]
    rounds = [r for r in _load_events(events)
              if r.get("event") == "diloco_round"]
    posted_by_round = {r["round"]: r["posted"] for r in rounds}
    gone = [r for r, posted in posted_by_round.items() if 5 not in posted]
    back = [r for r, posted in posted_by_round.items() if 5 in posted]
    assert gone, "worker 5 was never absent despite the kill"
    assert back and max(back) > min(gone), \
        "worker 5 never contributed after its restart"


def test_spec_validation():
    for bad in (dict(n_workers=1), dict(quorum_fraction=0.0),
                dict(quorum_fraction=1.5), dict(late_policy="maybe"),
                dict(rounds=0), dict(wire_dtype="int4"),
                dict(wire_block=0)):
        with pytest.raises(ValueError):
            HerdSpec(**bad).validate()


@pytest.mark.skipif(os.environ.get("SLT_RACECHECK") == "1",
                    reason="3 sequential 256-worker sims are ~10x "
                           "slower under write instrumentation; the "
                           "24-worker quantized herd test exercises "
                           "the same code under the monitor")
def test_wire_ab_parity_at_256_under_churn():
    """ROUND-20 ACCEPTANCE: int8-with-error-feedback vs f32 at 256
    workers with churn (quorum 0.8, mid-round kill): final eval loss
    within 5% of the f32 leg on the init scale, wire bytes >= 3.5x
    smaller, and the no-feedback negative control never beats the
    feedback leg. run_wire_ab performs the checks; re-assert the load-
    bearing ones here so a loosened harness can't silently pass."""
    rep = run_wire_ab(workers=256, seed=3)
    assert rep["ok"], rep["violations"]
    init = rep["init_eval_loss"]
    assert abs(rep["final_eval_loss"]["quant"]
               - rep["final_eval_loss"]["f32"]) < 0.05 * init
    assert rep["bytes"]["ratio"] >= 3.5
    # negative control: feedback either measurably helps, or both gaps
    # sit under the 0.5%-of-init noise floor (256-worker averaging
    # already cancels per-round noise; the bias proof is codec-level)
    assert rep["feedback_verdict"] in ("matters",
                                       "equivalent_below_noise_floor")
    if rep["feedback_verdict"] == "equivalent_below_noise_floor":
        assert rep["parity_gap"]["with_feedback"] < 0.0005 * init
    # both legs actually trained through the churn
    assert rep["final_eval_loss"]["f32"] < init - 0.2
    assert rep["final_eval_loss"]["quant"] < init - 0.2


def test_error_feedback_carry_telescopes():
    """The herd's in-graph error-feedback carry, pinned exactly where
    `--wire-ab`'s one-seed loss gap reads noise (ROADMAP D11): over the
    rounds a worker delivered, what its receivers were sent and what it
    meant differ by no more than the residual it still carries
    (sum(wired - delta) == -residual), so the wire's error does not
    grow with the rounds; a dead round absorbs nothing; and the
    no-feedback control's error does grow. The f32 kernel on the same
    anchor, optimizer state and keys gives the deltas that were meant."""
    import jax
    import numpy as np

    from serverless_learn_tpu.training.herd import _kernels

    n, rounds = 8, 6
    quant, f32 = wire_parity_specs(n, 0.8)
    noef = dataclasses.replace(quant, error_feedback=False)
    k32, kq, kn = _kernels(f32), _kernels(quant), _kernels(noef)
    anchor, _, opt, proj, shifts, key = k32["init"](3)
    tmap = jax.tree_util.tree_map
    zeros = tmap(lambda p: np.zeros((n,) + p.shape, np.float32), anchor)
    scale, reset = np.ones(n, np.float32), np.zeros(n, np.bool_)

    def accumulate(kernels):
        residual, err = zeros, zeros
        for r in range(rounds):
            alive = np.ones(n, np.bool_)
            alive[5] = r != 2          # worker 5 is dead in round 2
            args = (anchor, opt, shifts, proj, key, scale, alive, reset, r)
            meant = k32["inner"](*args, zeros)[0]
            wired, *_, residual = kernels["inner"](*args, residual)
            err = tmap(lambda e, w, m: e + np.where(
                alive.reshape((n,) + (1,) * (w.ndim - 1)),
                np.asarray(w) - np.asarray(m), 0.0), err, wired, meant)
        return err, tmap(np.asarray, residual)

    err, residual = accumulate(kq)
    for e, r in zip(jax.tree_util.tree_leaves(err),
                    jax.tree_util.tree_leaves(residual)):
        np.testing.assert_allclose(e, -r, atol=1e-6)
        assert np.abs(r).max() > 0
    err_noef, residual_noef = accumulate(kn)
    assert not any(np.asarray(r).any()
                   for r in jax.tree_util.tree_leaves(residual_noef))

    def norm(tree):
        return float(np.sqrt(sum((np.asarray(x) ** 2).sum()
                                 for x in jax.tree_util.tree_leaves(tree))))

    assert norm(err_noef) > 1.5 * norm(err)


def test_quantized_herd_deterministic_and_poison_still_quarantined(
        tmp_path):
    """The quantizer under vmap keeps the determinism contract
    (byte-identical same-seed reports), and a poisoned NaN delta — now
    passing THROUGH the codec's NaN-propagating in-graph path — still
    trips the quarantine gate on the dequantized values."""
    events = str(tmp_path / "wire-herd.jsonl")
    spec = HerdSpec(n_workers=24, rounds=3, inner_steps=2, batch_size=4,
                    features=(16,), quorum_fraction=0.8,
                    round_timeout_s=1.5, wire_dtype="int8",
                    poison_worker=21, poison_round=1)

    def run(log=None):
        rep = HerdSim(spec, seed=0, events_log=log).run(duration_s=20.0)
        rep.pop("wall_time_s")
        return rep

    rep = run(events)
    assert rep["ok"], rep["violations"]
    assert json.dumps(rep, sort_keys=True) == \
        json.dumps(run(), sort_keys=True)
    assert "21" in rep["herd"]["quarantined"]
    assert rep["herd"]["quarantined"]["21"]["reason"] == "nonfinite"
    assert rep["herd"]["anchor_finite"]
    wire = rep["herd"]["wire"]
    assert wire["dtype"] == "int8" and wire["error_feedback"]
    assert wire["compression_ratio"] > 3.5
    # dcn_wire telemetry reached the events log; doctor reports the
    # engaged codec (and would name a ~1.0 ratio as misconfiguration)
    recs = _load_events(events)
    wires = [r for r in recs if r.get("event") == "dcn_wire"]
    assert wires and all(r["wire_dtype"] == "int8" for r in wires)
    from serverless_learn_tpu.telemetry import doctor

    verdict = doctor.diagnose([events], bench_history="/nonexistent"
                              )["summary"]["verdict"]
    assert "quantized DCN exchange" in verdict, verdict
    assert "misconfigured" not in verdict, verdict


def test_doctor_names_ratio_one_misconfiguration(tmp_path):
    """An int8-configured consumer whose transfers ship ~1:1 (codec not
    engaging — e.g. every round falling back uncompressed) is named as a
    misconfiguration from the telemetry alone."""
    events = tmp_path / "flat.jsonl"
    with open(events, "w") as f:
        for rnd in range(4):
            f.write(json.dumps({
                "event": "dcn_wire", "consumer": "diloco",
                "direction": "tx", "wire_dtype": "int8",
                "logical_bytes": 1000, "wire_bytes": 990,
                "fallback": "nonfinite", "round": rnd}) + "\n")
    from serverless_learn_tpu.telemetry import doctor

    verdict = doctor.diagnose([str(events)],
                              bench_history="/nonexistent"
                              )["summary"]["verdict"]
    assert "quantized exchange misconfigured for diloco" in verdict
    assert "non-finite fallback" in verdict


def test_wire_parity_specs_shape():
    q, f = wire_parity_specs(64, 0.8, "int8")
    assert q.wire_dtype == "int8" and f.wire_dtype == "float32"
    assert dataclasses.replace(q, wire_dtype="float32") == f


def test_run_smoke_is_self_contained(tmp_path):
    """The CI smoke: determinism + quarantine asserted inside, events
    written for the CLI's doctor half."""
    events = str(tmp_path / "smoke.jsonl")
    rep = run_smoke(workers=24, seed=0, events_log=events)
    assert rep["ok"], rep["violations"]
    assert rep["deterministic"]
    assert "21" in rep["herd"]["quarantined"]  # workers - 3
    assert any(r.get("alert") == "diloco.delta_quarantined"
               for r in _load_events(events))


def test_herd_cli_run_and_smoke(tmp_path, capsys):
    from serverless_learn_tpu.cli import main

    rc = main(["chaos", "herd", "--workers", "16", "--rounds", "2",
               "--inner-steps", "2", "--quorum", "0.75", "--seed", "1",
               "--duration", "20", "--compact"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and out["ok"]
    assert out["herd"]["rounds_completed"] == 2

    rc = main(["chaos", "herd", "--workers", "16", "--quorum", "1.5"])
    assert rc == 2
    assert "bad herd spec" in capsys.readouterr().err

    rc = main(["chaos", "herd", "--smoke", "--workers", "24",
               "--seed", "0", "--compact"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and out["ok"], out.get("violations")
    assert out["deterministic"]
    assert "quarantin" in out["doctor_verdict"]


def test_herd_cli_wire_ab_and_record(tmp_path, capsys):
    from serverless_learn_tpu.cli import main

    history = str(tmp_path / "hist.json")
    rc = main(["chaos", "herd", "--wire-ab", "--workers", "16",
               "--seed", "1", "--record", "--history", history,
               "--compact"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and out["ok"], out.get("violations")
    assert out["bytes"]["ratio"] >= 3.5
    with open(history) as f:
        rows = json.load(f)
    assert {r["wire_dtype"] for r in rows} == {"float32", "int8"}
    assert all(r["metric"] == "herd_diloco_round_wait_ms" for r in rows)
    assert all("dcn_bytes_per_round" in r
               and "diloco_round_wait_s" in r for r in rows)
    # the recorded pair passes the gate (int8 must not regress the pair)
    from serverless_learn_tpu.telemetry.benchgate import run_gate

    assert run_gate(history, metric="herd_diloco")["ok"]

    rc = main(["chaos", "herd", "--wire-ab", "--wire-dtype", "f32"])
    assert rc == 2
    assert "int8|fp8" in capsys.readouterr().err
