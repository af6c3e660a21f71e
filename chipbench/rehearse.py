"""Compile-only rehearsal: the cells' programs at their real sizes, for a
v5e chip that is described and not attached. No chip, no run, no time.

    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py train 16 20
    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py serve 16
    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py reference 16
    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py serve 5 --workload <cell>

``train <depths>``: the train step of a ``train`` cell at each depth;
prints what the TPU compiler says the step needs, which is how a
configuration's depth is picked. ``serve <depth>``: one decode chunk and
one prefill chunk of a serving cell at their largest buckets.
``reference <depth>``: the plain reference of a cell, which runs on the
same chip after the window and has to fit there too. ``--workload`` names
the cell (any cell of ``BENCHMARK.json`` of the right kind); without it
``train`` takes ``mistral7b-lora-train-4k``, ``serve`` takes
``mistral7b-serve-backlog`` and ``reference`` both. The compiler counts
one program: the other programs' buffers
(the window's batches in flight, the profiler) come on top. How a
configuration is put at another depth, and how the engine's programs are
lowered, is its architecture's to say (``arch/<name>.py``: ``at_depth``,
``lower_largest``).

The program asks ``jax.default_backend()`` whether to interpret its Pallas
kernels; here that answer is steered to "tpu" so that the real kernels
are what compiles.
"""

from __future__ import annotations

import argparse
import os
import sys
import time
from unittest import mock

os.environ.setdefault("TPU_LOG_DIR", "disabled")
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

GIB = 2.0 ** 30


def _one_chip():
    from jax.experimental import topologies
    from jax.sharding import Mesh
    import numpy as np

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return topo.devices[0], Mesh(
        np.array(topo.devices[:1]).reshape((1,) * 6),
        ("dp", "fsdp", "ep", "tp", "sp", "pp"))


def _report(name: str, compiled, seconds: float) -> dict:
    m = compiled.memory_analysis()
    need = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    out = {"program": name, "compile_s": round(seconds, 1),
           "arguments_GiB": round(m.argument_size_in_bytes / GIB, 2),
           "outputs_GiB": round(m.output_size_in_bytes / GIB, 2),
           "aliased_GiB": round(m.alias_size_in_bytes / GIB, 2),
           "temporaries_GiB": round(m.temp_size_in_bytes / GIB, 2),
           "needs_GiB": round(need / GIB, 2),
           "flash_kernels": compiled.as_text().count("tpu_custom_call")}
    print(out, flush=True)
    return out


TRAIN_CELL = "mistral7b-lora-train-4k"
SERVE_CELL = "mistral7b-serve-backlog"


def _kind(cell) -> str:
    """``train`` or ``serve``: the traffic's kind up to its first ``_``."""
    return cell.kind.split("_", 1)[0]


def _cell_at(name: str, depth: int, kind: str = None, root: str = _ROOT):
    """The cell ``name`` with its configuration at ``depth``; where
    ``kind`` is given (``train`` or ``serve``) its traffic has to be of
    it."""
    from chipbench.cell import load_cell

    cell = load_cell(name, root)
    if kind is not None and _kind(cell) != kind:
        raise SystemExit(f"workload {name!r} is of kind {cell.kind!r}, "
                         f"not a {kind} cell")
    cell.config = cell.arch.at_depth(cell.config, depth)
    return cell


def rehearse_train(depth: int, workload: str = None,
                   root: str = _ROOT) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from serverless_learn_tpu.config import ExperimentConfig
    from serverless_learn_tpu.training.train_step import build_trainer

    workload = workload or TRAIN_CELL
    cell = _cell_at(workload, depth, "train", root)
    raw = cell.program_config()
    t = cell.traffic
    raw["train"].update(batch_size=t["sequences_per_step"])
    raw.setdefault("data", {}).update(seq_len=t["tokens_per_sequence"])
    cfg = ExperimentConfig.from_dict(raw)
    _, mesh = _one_chip()
    with mock.patch("jax.default_backend", lambda: "tpu"):
        trainer = build_trainer(cfg, mesh=mesh)
        rep = NamedSharding(mesh, P())
        state = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
            trainer.abstract_state())
        batch = {"tokens": jax.ShapeDtypeStruct(
            (t["sequences_per_step"], t["tokens_per_sequence"]), jnp.int32,
            sharding=trainer.batch_shardings["tokens"])}
        t0 = time.perf_counter()
        compiled = trainer.step_fn.lower(state, batch).compile()
    return _report(f"{workload}: train step, {depth} layers", compiled,
                   time.perf_counter() - t0)


def rehearse_serve(depth: int, workload: str = None,
                   root: str = _ROOT) -> list:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from serverless_learn_tpu import cli
    from serverless_learn_tpu.config import ExperimentConfig
    from serverless_learn_tpu.inference.continuous import (
        ContinuousBatchingEngine)

    workload = workload or SERVE_CELL
    cell = _cell_at(workload, depth, "serve", root)
    cfg = cli._serving_config(ExperimentConfig.from_dict(
        cell.program_config()))
    serve = cell.config.get("serve", {})
    dev, mesh = _one_chip()
    one = SingleDeviceSharding(dev)
    from serverless_learn_tpu.training.train_step import build_trainer

    module = build_trainer(cfg, mesh=mesh).bundle.module
    shaped = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)
    params = shaped(jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]))
    # An engine with no device state: only its jit factories are used.
    # (Patched for this one construction only: a test's process goes on
    # to build real engines.)
    Engine = ContinuousBatchingEngine
    with mock.patch.object(Engine, "_init_state", lambda self: {}), \
            mock.patch.object(Engine, "_fingerprint_params",
                              staticmethod(lambda p: None)):
        eng = Engine(module, None, max_slots=serve.get("max_batch", 8),
                     chunk_size=serve.get("chunk_size", 32), kv=cfg.kv)
    eng.stop()
    prefill, decode = cell.arch.reachable_shapes(eng, cell.traffic)
    print({"reachable_prefill_programs": len(prefill),
           "reachable_decode_programs": len(decode),
           "pool_blocks": eng._pool.num_blocks})
    out = []
    for name, lowered in cell.arch.lower_largest(eng, params, cell.traffic,
                                                 one):
        t0 = time.perf_counter()
        out.append(_report(f"{workload}: {name}, {depth} layers",
                           lowered.compile(), time.perf_counter() - t0))
    return out


def rehearse_reference(depth: int, workload: str = None,
                       root: str = _ROOT) -> list:
    """The plain reference's programs at a cell's sizes: it runs on the
    same chip after the window, so it has to fit there too. A ``train``
    cell's: loss and gradients in float32 and in the control's precision;
    a serving cell's: one forward pass over the longest request."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from chipbench import checks, reference, weights

    dev, _ = _one_chip()
    one = SingleDeviceSharding(dev)
    shaped = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)
    out = []
    for name in ([workload] if workload else [TRAIN_CELL, SERVE_CELL]):
        cell = _cell_at(name, depth, root=root)
        arch, sz, t = cell.arch, cell.sizes, cell.traffic
        train = cell.config["program"]["train"]
        w = jax.eval_shape(lambda: weights.make_weights(
            arch, sz, jnp.uint32(0), jnp.dtype(train["param_dtype"])))
        if _kind(cell) == "train":
            frozen, trained = jax.eval_shape(arch.split_trained, w)
            tokens = jax.ShapeDtypeStruct(
                (t["sequences_per_step"], t["tokens_per_sequence"]),
                jnp.int32, sharding=one)
            for precision in ("float32",
                              checks.CONTROL_PRECISION[train["dtype"]]):
                t0 = time.perf_counter()
                c = reference.loss_and_grads.lower(
                    arch, shaped(frozen), shaped(trained), tokens, sz,
                    precision).compile()
                out.append(_report(
                    f"{name}: reference loss+grads ({precision}), "
                    f"{depth} layers", c, time.perf_counter() - t0))
        else:
            pad = t["prompt_tokens"]["max"] + t["output_tokens"]["max"]
            t0 = time.perf_counter()
            c = reference._row_logits.lower(
                arch, shaped(w),
                jax.ShapeDtypeStruct((pad,), jnp.int32, sharding=one),
                sz, "float32").compile()
            out.append(_report(
                f"{name}: reference forward, {pad} tokens, {depth} layers",
                c, time.perf_counter() - t0))
    return out


def main(argv) -> int:
    ap = argparse.ArgumentParser(
        description="Compile-only rehearsal of a cell's programs for a "
                    "described v5e: no chip, no run, no time.")
    ap.add_argument("what", choices=("train", "serve", "reference"))
    ap.add_argument("depths", type=int, nargs="+")
    ap.add_argument("--workload", default=None,
                    help="a cell of BENCHMARK.json (default: "
                         f"{TRAIN_CELL} / {SERVE_CELL})")
    args = ap.parse_args(argv)
    rehearse = {"train": rehearse_train, "serve": rehearse_serve,
                "reference": rehearse_reference}[args.what]
    for depth in args.depths:
        rehearse(depth, args.workload)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
