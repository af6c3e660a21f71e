"""Names the benchmark's readers find programs and kernels by. They are
an interface: a rename here silences a per-layer metric without failing
anything else.

* ``jit_step_fn`` — ``chipbench/metrics/step_ms_p50.py`` takes the train
  step's ``XLA Modules`` events by ``"step_fn" in name``.
* ``jit_chunk_n{nb}w{W}`` — ``chipbench/metrics/decode_roofline.py``
  takes the paged decode chunk's by ``"chunk" in name``.
* ``jit_pre_n{nb}t{T}w{W}`` — the paged prefill program, as the result
  line's ``breakdown`` prints it beside each idle gap. Both carry their
  bucket (PR 38): ``chipbench/program_records.py`` lines the events whose
  names start ``jit_pre_`` / ``jit_chunk_`` up with the ``sched_iter``
  records' ``programs`` entries, which carry the same names
  (``continuous.program_name``), for ``prefill_ms_per_token`` and
  ``prefill_device_share``.
* ``flash_fwd`` / ``flash_bwd_dq`` / ``flash_bwd_dkv`` — the Pallas
  kernels' HLO instructions in ``device_ops`` (``flash_attn_roofline``
  still finds them by their ``tpu_custom_call`` target).
"""

import re

import jax
import jax.numpy as jnp
import pytest

from serverless_learn_tpu.config import (ExperimentConfig, KVCacheConfig,
                                         MeshConfig, TrainConfig)
from serverless_learn_tpu.data.datasets import SyntheticSource
from serverless_learn_tpu.inference.continuous import (
    ContinuousBatchingEngine, program_name)
from serverless_learn_tpu.models.registry import get_model
from serverless_learn_tpu.ops.pallas import flash_attention as fa
from serverless_learn_tpu.parallel.mesh import make_mesh
from serverless_learn_tpu.telemetry import MetricsRegistry
from serverless_learn_tpu.training.train_step import build_trainer


def _module_name(lowered) -> str:
    return re.search(r"module @(\w+)", lowered.as_text()).group(1)


@pytest.fixture(scope="module")
def engine(devices):
    bundle = get_model("llama_tiny", dtype=jnp.float32,
                       param_dtype=jnp.float32, max_seq_len=64)
    params = bundle.module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    eng = ContinuousBatchingEngine(
        bundle.module, params, max_slots=2, chunk_size=2,
        kv=KVCacheConfig(block_size=4, prefill_chunk=4, prefill_budget=8),
        registry=MetricsRegistry())
    yield eng
    eng.stop()


def _lower_train_step():
    cfg = ExperimentConfig(model="mlp_mnist", mesh=MeshConfig(dp=1),
                           train=TrainConfig(batch_size=8))
    tr = build_trainer(cfg, mesh=make_mesh(cfg.mesh,
                                           devices=jax.devices()[:1]))
    batch = next(iter(SyntheticSource(tr.bundle.make_batch, cfg.data, 8)))
    return tr.step_fn.lower(tr.abstract_state(), batch)


def _lower_paged_prefill(eng, nb=1, T=8, W=4):
    st, i32 = eng._state, jnp.int32
    z = lambda dt: jnp.zeros((nb,), dt)
    return eng._paged_prefill_jit(nb, T, W).lower(
        eng.params, st["pages"], st["vecs"], jnp.zeros((nb, W), i32),
        z(i32), jnp.zeros((nb, T), i32), z(i32), z(i32), z(jnp.bool_),
        z(jnp.float32), z(i32), z(i32), z(jnp.uint32), z(i32), z(i32))


def _lower_paged_chunk(eng, nb=1, W=4):
    st = eng._state
    return eng._paged_chunk_jit(nb, W).lower(
        eng.params, st["pages"], st["vecs"],
        jnp.zeros((nb, W), jnp.int32), jnp.zeros((nb,), jnp.int32))


@pytest.mark.parametrize("program,name", [
    ("train_step", "jit_step_fn"), ("paged_prefill", "jit_pre_n1t8w4"),
    ("paged_chunk", "jit_chunk_n1w4")])
def test_program_module_names(engine, program, name):
    lowered = {"train_step": _lower_train_step,
               "paged_prefill": lambda: _lower_paged_prefill(engine),
               "paged_chunk": lambda: _lower_paged_chunk(engine)}[program]()
    assert _module_name(lowered) == name


@pytest.mark.parametrize("kind,key,name", [
    ("pre", (2, 4, 16), "jit_pre_n2t4w16"),
    ("pre", (1, 8, 1), "jit_pre_n1t8w1"),
    ("chunk", (2, 16), "jit_chunk_n2w16"),
    ("chunk", (1, 1), "jit_chunk_n1w1")])
def test_each_bucket_names_its_module(engine, kind, key, name):
    """The module's name is the bucket's, ``program_name`` gives the same
    string, and the readers' substrings and prefixes still find it."""
    lowered = (_lower_paged_prefill(engine, *key) if kind == "pre"
               else _lower_paged_chunk(engine, *key))
    assert _module_name(lowered) == program_name(kind, key) == name
    assert name.startswith(f"jit_{kind}_")
    assert ("chunk" in name) == (kind == "chunk")
    assert "step_fn" not in name


def test_flash_kernels_are_named_apart():
    """Forward, dq and dk/dv each carry their own name into the lowered
    program (on the chip: the custom-call's instruction name)."""
    q = jnp.zeros((1, 2, 128, 64), jnp.float32)

    def loss(q, k, v):
        return fa._flash_core(q, k, v, None, "none", True, 128, 128,
                              True).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, q, q).as_text(debug_info=True)
    for name in (fa.FWD_KERNEL, fa.BWD_DQ_KERNEL, fa.BWD_DKV_KERNEL):
        assert re.search(rf"\b{name}\b", text), name
    assert len({fa.FWD_KERNEL, fa.BWD_DQ_KERNEL, fa.BWD_DKV_KERNEL}) == 3
