"""The training window: ``run_training`` on ``build_trainer(config)``.

Set-up builds ONE trainer and ONE state, drives it through its first
steps (the first compiles; three are followed by the reference) and hands
the same objects to the window: the whole thing is one call of the
program's own loop, fed through its own ``Prefetcher``, with this module's
callback marking where set-up ends and where the window closes.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import checks, reference, traffic, weights

CHECK_STEPS = 3


class _WindowClosed(Exception):
    pass


def _to_host(tree):
    return jax.tree_util.tree_map(
        lambda a: np.asarray(jax.device_get(a)).astype(np.float32), tree)


def _adam_mu(opt_state):
    """The first moment of the one Adam state in an optax chain."""
    found = [s for s in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda s: hasattr(s, "mu")) if hasattr(s, "mu")]
    if len(found) != 1:
        raise RuntimeError(f"expected one Adam state, found {len(found)}")
    return found[0].mu


def build(cell, seed: int, session: dict = None):
    """(config, trainer, state) with the benchmark's seeded weights.
    ``session`` (``control.py``: many seeds in one process) keeps the
    trainer, and with it the compiled step, from one seed to the next."""
    from serverless_learn_tpu.config import ExperimentConfig
    from serverless_learn_tpu.parallel.mesh import make_mesh
    from serverless_learn_tpu.training.optimizer import make_optimizer
    from serverless_learn_tpu.training.partition import prune
    from serverless_learn_tpu.training.train_state import TrainState
    from serverless_learn_tpu.training.train_step import build_trainer

    raw = cell.program_config()
    t = cell.traffic
    raw.setdefault("train", {}).update(
        batch_size=t["sequences_per_step"], num_steps=10 ** 9, seed=0)
    raw.setdefault("data", {}).update(seq_len=t["tokens_per_sequence"])
    cfg = ExperimentConfig.from_dict(raw)
    if session is not None and "trainer" in session:
        trainer = session["trainer"]
    else:
        mesh = make_mesh(cfg.mesh, devices=jax.devices()[:cfg.mesh.size])
        trainer = build_trainer(cfg, mesh=mesh)
        if session is not None:
            session["trainer"] = trainer
    sz = cell.sizes
    dtype = jnp.dtype(cfg.train.param_dtype)
    params = cell.arch.to_program_tree(weights.make_weights(
        cell.arch, sz, weights.seed_u32(seed), dtype))
    abstract = trainer.abstract_state()
    weights.check_tree_matches(params, abstract.params)
    if jax.tree_util.tree_leaves(abstract.model_state):
        raise RuntimeError("the model has state besides its parameters; "
                           "this driver does not make it")
    tx = make_optimizer(cfg.optimizer)
    mask = trainer.bundle.trainable_mask    # None: every leaf is trained
    trainable = params if mask is None else prune(params, mask(params))
    state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                       opt_state=jax.jit(tx.init)(trainable), model_state={})
    state = jax.device_put(state, trainer.state_shardings)
    return cfg, trainer, state


def run(cell, seed: int, seconds: float, tracer, session: dict = None
        ) -> dict:
    from serverless_learn_tpu.telemetry import goodput
    from serverless_learn_tpu.training.loop import run_training

    t = cell.traffic
    sz = cell.sizes
    cfg, trainer, state = build(cell, seed, session)
    trained_of = lambda tree: cell.arch.trained_of_program_tree(tree, sz)
    start = _to_host(trained_of(state.params))
    b1 = cfg.optimizer.b1
    batch_iter = traffic.train_batches(seed, sz.vocab,
                                       t["sequences_per_step"],
                                       t["tokens_per_sequence"])
    fed = []   # the first batches, kept for the reference

    def source():
        for batch in batch_iter:
            if len(fed) < CHECK_STEPS:
                fed.append(batch["tokens"])
            yield batch

    seen = {"losses": [], "steps": 0, "t0": None, "t1": None,
            "wait0": 0.0, "compiles0": 0}
    ledger = goodput.get_ledger()

    def data_wait_s() -> float:
        ph = ledger.snapshot().get("phases", {})
        return float(ph.get("data_wait", {}).get("seconds", 0.0))

    def callback(step, state, stats):
        now = time.perf_counter()
        if step <= CHECK_STEPS:
            seen["losses"].append(float(stats.metrics["loss"]))
        if step == 1:
            mu = trained_of(_adam_mu(state.opt_state))
            seen["grads"] = jax.tree_util.tree_map(
                lambda m: m / (1.0 - b1), _to_host(mu))
        if step == CHECK_STEPS:
            end = _to_host(trained_of(state.params))
            seen["change"] = jax.tree_util.tree_map(
                lambda a, b: a - b, end, start)
            jax.block_until_ready(state.params)
            seen["wait0"] = data_wait_s()
            tracer.window_opens()
            seen["t0"] = time.perf_counter()
            return
        if step > CHECK_STEPS:
            seen["steps"] += 1
            tracer.after_unit(seen["steps"])
            if now - seen["t0"] >= seconds:
                jax.block_until_ready(state.params)
                seen["t1"] = time.perf_counter()
                raise _WindowClosed

    try:
        run_training(cfg, trainer=trainer, state=state, source=source(),
                     step_callback=callback)
    except _WindowClosed:
        pass
    tracer.window_closes()
    elapsed = seen["t1"] - seen["t0"]
    tokens_per_step = t["sequences_per_step"] * t["tokens_per_sequence"]
    record = {
        "attempted": seen["steps"], "failed": 0,
        "t_window_start": seen["t0"], "window_s": elapsed,
        "end_to_end": {
            "train_tokens_per_s": seen["steps"] * tokens_per_step / elapsed},
        "counters": {
            "steps": seen["steps"], "tokens_per_step": tokens_per_step,
            "sequences_per_step": t["sequences_per_step"],
            "tokens_per_sequence": t["tokens_per_sequence"],
            "data_wait_s": data_wait_s() - seen["wait0"],
        },
        "program": {"losses": seen["losses"], "grads": seen["grads"],
                    "change": seen["change"]},
        "fed": fed,
        "opt": {"learning_rate": cfg.optimizer.learning_rate,
                "b1": cfg.optimizer.b1, "b2": cfg.optimizer.b2,
                "eps": 1e-8, "weight_decay": cfg.optimizer.weight_decay},
    }
    del state, trainer
    return record


def follow(cell, seed: int, record: dict, precision: str = "float32",
           batches=None) -> dict:
    """The reference follows the steps the program was fed."""
    arch, sz = cell.arch, cell.sizes
    w = weights.make_weights(arch, sz, weights.seed_u32(seed),
                             jnp.dtype(cell.config["program"]["train"]
                                       ["param_dtype"]))
    losses, grads, change = reference.train_reference(
        arch, w, record["fed"] if batches is None else batches, sz,
        record["opt"], precision)
    return {"losses": losses, "grads": _to_host(grads),
            "change": _to_host(change)}


def check(cell, seed: int, record: dict) -> dict:
    """Runs once the window has closed and the program's state is gone."""
    values, notes = checks.train_values(record["program"],
                                        follow(cell, seed, record))
    return checks.with_limits(values, cell.config["limits"]["train"], notes)


def control_readings(cell, seed: int, record: dict) -> dict:
    """What limits are set from, beside the program's own reading: the
    control (the reference in the next precision down, in the program's
    place) and the fault a training cell can have that needs a run (half
    of the batch left out, planted in the reference in the program's
    place). A state left unchanged reads 1 by this measure, with no run.
    """
    ref = follow(cell, seed, record)
    low = checks.CONTROL_PRECISION[cell.config["program"]["train"]["dtype"]]
    halved = [np.concatenate([b[:len(b) // 2]] * 2) for b in record["fed"]]
    return {
        "program": checks.train_values(record["program"], ref)[0],
        "control_" + low: checks.train_values(
            follow(cell, seed, record, low), ref)[0],
        "fault_half_batch": checks.train_values(
            follow(cell, seed, record, batches=halved), ref)[0],
    }
