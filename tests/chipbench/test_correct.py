"""What decides ``correct``: the control has to come out as not correct,
and so has each fault a cell can have, planted under the timed path with
the rest of a run driven as it is (only the look for a chip is skipped).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import checks, reference, run, weights
from chipbench.cell import load_cell
from chipbench.drivers import serve, train


@pytest.fixture(autouse=True)
def _no_cache(no_compile_cache):
    pass


def _run(tiny_root, name, seed=7):
    return run.run_cell(name, seed=seed, seconds=0.3, trace=False,
                        root=tiny_root, require_chip=False)


# ---- faults under the timed path ---------------------------------------

def _patched_build(monkeypatch, wrap):
    real = train.build

    def build(cell, seed, session=None):
        cfg, trainer, state = real(cell, seed, session)
        trainer.step_fn = wrap(trainer.step_fn)
        return cfg, trainer, state

    monkeypatch.setattr(train, "build", build)


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        tiny_root, monkeypatch):
    def wrap(step):
        def frozen(state, batch):
            # Keep the caller's state (the step donates its argument, so
            # it is given a copy) and only count the step.
            copy = jax.tree_util.tree_map(jnp.copy, state)
            _, metrics = step(copy, batch)
            return type(state)(step=state.step + 1, params=state.params,
                               opt_state=state.opt_state,
                               model_state=state.model_state), metrics
        return frozen

    _patched_build(monkeypatch, wrap)
    line = _run(tiny_root, "tiny-train")
    assert line["correct"] is False
    # By the training measure an unmoved leaf reads 1.
    assert line["checked"]["change_norm_gap"]["value"] == pytest.approx(1.0)
    assert line["checked"]["grad_norm_gap"]["value"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out_is_not_correct(tiny_root, monkeypatch):
    def wrap(step):
        def half(state, batch):
            b = batch["tokens"]
            kept = b[:b.shape[0] // 2]
            return step(state, {"tokens": jnp.concatenate([kept, kept])})
        return half

    _patched_build(monkeypatch, wrap)
    line = _run(tiny_root, "tiny-train")
    assert line["correct"] is False
    failed = [k for k, n in line["checked"].items()
              if n["value"] > n["limit"]]
    assert any(k.startswith("loss") for k in failed) and \
        "grad_norm_gap" in failed


@pytest.mark.parametrize("cell", ["tiny-backlog", "tiny-steady"])
def test_a_token_altered_where_it_is_produced_is_not_correct(
        tiny_root, monkeypatch, cell):
    from serverless_learn_tpu.inference import continuous

    real = continuous._sample_slots

    def altered(logits, *a, **k):
        # Second-best instead of best, for every row at every step.
        best = jnp.argmax(logits, axis=-1)
        lowered = logits.at[jnp.arange(logits.shape[0]), best].set(-jnp.inf)
        return real(lowered, *a, **k)

    monkeypatch.setattr(continuous, "_sample_slots", altered)
    line = _run(tiny_root, cell)
    assert line["correct"] is False
    n = line["checked"]["served_logit_gap"]
    assert n["value"] > n["limit"]


def test_a_reply_of_the_wrong_length_is_not_correct(tiny_root, monkeypatch):
    real = serve._one_request

    def short(conn, item, rec):
        real(conn, item, rec)
        if "new_tokens" in rec and len(rec["new_tokens"]) > 1:
            rec["new_tokens"] = rec["new_tokens"][:-1]

    monkeypatch.setattr(serve, "_one_request", short)
    line = _run(tiny_root, "tiny-backlog")
    assert line["correct"] is False
    assert line["checked"]["replies_wrong_length"]["value"] > 0


# ---- the control: one precision down has to fail -----------------------

def _record(tiny_root, name, seed=5, seconds=0.3):
    cell = load_cell(name, tiny_root)
    tracer = run.Tracer(False, "", {}, run.CompileCounter())
    driver = run.load_driver(cell.kind)
    return cell, driver, driver.run(cell, seed, seconds, tracer)


def _fails(values, limits):
    return not checks.verdict(checks.with_limits(values, limits)["numbers"])


def test_training_control_and_fault_come_out_not_correct(tiny_root):
    cell, driver, record = _record(tiny_root, "tiny-train")
    readings = driver.control_readings(cell, 5, record)
    limits = cell.config["limits"]["train"]
    assert not _fails(readings["program"], limits)
    assert _fails(readings["control_bfloat16"], limits)
    assert _fails(readings["fault_half_batch"], limits)
    # Each fails by a wide margin on a number of its own.
    assert readings["fault_half_batch"]["grad_norm_gap"] > \
        10 * readings["program"]["grad_norm_gap"]


def test_serving_control_comes_out_not_correct(tiny_root):
    """Deterministic: the served tokens are the float32 reference's own
    greedy continuations (so the program's place is taken by a path that
    agrees exactly), 24 requests of 24 tokens. bfloat16, the control of a
    float32 configuration, moves a tiny model's best token at about one
    position in a hundred, so some hundreds of tokens meet a few."""
    cell = load_cell("tiny-backlog", tiny_root)
    arch, sz = cell.arch, cell.sizes
    w = weights.make_weights(arch, sz, weights.seed_u32(5), jnp.float32)
    rng = np.random.default_rng(0)
    finished = []
    for _ in range(24):
        prompt = [int(t) for t in rng.integers(0, sz.vocab, 40)]
        served = []
        for _ in range(24):
            at = reference._served_logits(arch, w, prompt, served + [0], sz,
                                          112, "float32")
            served.append(int(jnp.argmax(at[-1])))
        finished.append({"item": {"prompt": prompt, "max_new_tokens": 24,
                                  "shared_prefix": None},
                         "new_tokens": served})
    cell.traffic["check_requests"] = len(finished)
    record = {"finished": finished, "never_came": 0}
    readings = serve.control_readings(cell, 5, record)
    limits = {"requests_unanswered": 0.0, "replies_wrong_length": 0.0,
              **cell.config["limits"]["serve"]}
    assert readings["program"]["served_logit_gap"] == 0.0
    assert not _fails(readings["program"], limits)
    assert _fails(readings["control_bfloat16"], limits)
    assert readings["control_bfloat16"]["served_logit_gap"] > \
        3 * limits["served_logit_gap"]


def test_a_session_keeps_the_built_system_from_seed_to_seed(tiny_root):
    cell = load_cell("tiny-backlog", tiny_root)
    tracer = run.Tracer(False, "", {}, run.CompileCounter())
    session = {}
    try:
        for seed in (11, 12):
            record = serve.run(cell, seed, 0.2, tracer, session)
            numbers = serve.check(cell, seed, record)["numbers"]
            assert checks.verdict(numbers), numbers
        assert record["counters"]["programs_warmed"] == 0
    finally:
        session["server"].stop()
