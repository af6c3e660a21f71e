"""Compile-only rehearsal: the cells' programs at their real sizes, for a
v5e chip that is described and not attached. No chip, no run, no time.

    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py train 16 20
    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py serve 16
    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py reference 16

``train <depths>``: the train step of ``mistral7b-lora-train-4k`` at each
depth; prints what the TPU compiler says the step needs, which is how the
configuration's depth was picked. ``serve <depth>``: one decode chunk and
one prefill chunk of ``mistral7b-serve-backlog`` at their largest
buckets. The compiler counts one program: the other programs' buffers
(the window's batches in flight, the profiler) come on top. How a
configuration is put at another depth, and how the engine's programs are
lowered, is its architecture's to say (``arch/<name>.py``: ``at_depth``,
``lower_largest``).

The program asks ``jax.default_backend()`` whether to interpret its Pallas
kernels; here that answer is steered to "tpu" so that the real kernels
are what compiles.
"""

from __future__ import annotations

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


def _cell_at(name: str, depth: int):
    from chipbench.cell import load_cell

    cell = load_cell(name)
    cell.config = cell.arch.at_depth(cell.config, depth)
    return cell


def rehearse_train(depth: int) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from serverless_learn_tpu.config import ExperimentConfig
    from serverless_learn_tpu.training.train_step import build_trainer

    cell = _cell_at(TRAIN_CELL, depth)
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
    return _report(f"train step, {depth} layers", compiled,
                   time.perf_counter() - t0)


def rehearse_serve(depth: int) -> list:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from serverless_learn_tpu import cli
    from serverless_learn_tpu.config import ExperimentConfig
    from serverless_learn_tpu.inference.continuous import (
        ContinuousBatchingEngine)

    cell = _cell_at(SERVE_CELL, depth)
    cfg = cli._serving_config(ExperimentConfig.from_dict(
        cell.program_config()))
    dev, mesh = _one_chip()
    one = SingleDeviceSharding(dev)
    from serverless_learn_tpu.training.train_step import build_trainer

    module = build_trainer(cfg, mesh=mesh).bundle.module
    shaped = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)
    params = shaped(jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]))
    # An engine with no device state: only its jit factories are used.
    eng = ContinuousBatchingEngine.__new__(ContinuousBatchingEngine)
    ContinuousBatchingEngine._init_state = lambda self: {}
    ContinuousBatchingEngine._fingerprint_params = staticmethod(
        lambda p: None)
    ContinuousBatchingEngine.__init__(eng, module, None, max_slots=8,
                                      chunk_size=32, kv=cfg.kv)
    eng.stop()
    prefill, decode = cell.arch.reachable_shapes(eng, cell.traffic)
    print({"reachable_prefill_programs": len(prefill),
           "reachable_decode_programs": len(decode),
           "pool_blocks": eng._pool.num_blocks})
    out = []
    for name, lowered in cell.arch.lower_largest(eng, params, cell.traffic,
                                                 one):
        t0 = time.perf_counter()
        out.append(_report(f"{name}, {depth} layers", lowered.compile(),
                           time.perf_counter() - t0))
    return out


def rehearse_reference(depth: int) -> list:
    """The plain reference's two programs at the cells' sizes: it runs on
    the same chip after the window, so it has to fit there too."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from chipbench import reference, weights

    dev, _ = _one_chip()
    one = SingleDeviceSharding(dev)
    shaped = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)
    out = []
    cell = _cell_at(TRAIN_CELL, depth)
    arch, sz, t = cell.arch, cell.sizes, cell.traffic
    w = jax.eval_shape(lambda: weights.make_weights(
        arch, sz, jnp.uint32(0), jnp.bfloat16))
    frozen, adapters = jax.eval_shape(arch.split_trained, w)
    tokens = jax.ShapeDtypeStruct(
        (t["sequences_per_step"], t["tokens_per_sequence"]), jnp.int32,
        sharding=one)
    for precision in ("float32", "fp8"):
        t0 = time.perf_counter()
        c = reference.loss_and_grads.lower(
            arch, shaped(frozen), shaped(adapters), tokens, sz,
            precision).compile()
        out.append(_report(f"reference loss+grads ({precision}), "
                           f"{depth} layers", c, time.perf_counter() - t0))
    cell = _cell_at(SERVE_CELL, depth)
    arch, sz, t = cell.arch, cell.sizes, cell.traffic
    w = jax.eval_shape(lambda: weights.make_weights(
        arch, sz, jnp.uint32(0), jnp.bfloat16))
    pad = t["prompt_tokens"]["max"] + t["output_tokens"]["max"]
    t0 = time.perf_counter()
    c = reference._row_logits.lower(
        arch, shaped(w),
        jax.ShapeDtypeStruct((pad,), jnp.int32, sharding=one),
        sz, "float32").compile()
    out.append(_report(f"reference forward, {pad} tokens, {depth} layers",
                       c, time.perf_counter() - t0))
    return out


def main(argv) -> int:
    if len(argv) < 2 or argv[0] not in ("train", "serve", "reference"):
        print(__doc__)
        return 2
    for depth in map(int, argv[1:]):
        {"train": rehearse_train, "serve": rehearse_serve,
         "reference": rehearse_reference}[argv[0]](depth)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
