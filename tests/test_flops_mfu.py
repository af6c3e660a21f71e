"""FLOPs / MFU accounting (VERDICT round 1 item 6): XLA-cost-model step
FLOPs, peak lookup by device kind, and the ThroughputMeter wiring."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from serverless_learn_tpu.utils.flops import (
    PEAK_TFLOPS_BF16, compiled_step_cost, compiled_step_flops, mfu,
    peak_flops_for_kind, peak_flops_per_chip, peak_hbm_bytes_per_s)
from serverless_learn_tpu.utils.metrics import ThroughputMeter


def test_compiled_flops_matches_analytic_matmul():
    n = 512
    f = jax.jit(lambda a, b: a @ b)
    a = jnp.ones((n, n), jnp.float32)
    flops = compiled_step_flops(f, a, a)
    if flops is None:  # backend without a cost model: nothing to assert
        return
    # XLA counts 2*M*N*K for a matmul.
    assert abs(flops - 2 * n ** 3) / (2 * n ** 3) < 0.05, flops


def test_peak_lookup_off_tpu_is_none():
    class Fake:
        platform = "cpu"
        device_kind = "abacus"

    assert peak_flops_per_chip(Fake()) is None
    assert mfu(1e12, 1.0, device=Fake()) is None


def test_peak_lookup_is_exact_and_unknown_tpu_kind_raises():
    """A prefix match once handed every unknown "TPU v5..." string v5p's
    459 TFLOP/s; the tables match ``device_kind`` exactly, and a device on
    the tpu platform that is not in them is an error."""
    assert peak_flops_for_kind("TPU v5 lite") == 197e12
    assert peak_flops_for_kind("TPU v5") == 459e12
    assert peak_flops_for_kind("TPU v5e") is None
    assert peak_flops_for_kind("TPU v5 litepod") is None

    class Unknown:
        platform = "tpu"
        device_kind = "TPU v5e"

    with pytest.raises(KeyError, match="TPU v5e"):
        peak_flops_per_chip(Unknown())
    with pytest.raises(KeyError, match="TPU v5e"):
        peak_hbm_bytes_per_s(Unknown())
    with pytest.raises(KeyError):
        mfu(1e12, 1.0, device=Unknown())


def test_step_that_cannot_lower_raises():
    def bad(a):
        return a @ jnp.ones((3, 3))  # shape mismatch at trace time

    with pytest.raises(TypeError):
        compiled_step_cost(bad, jnp.ones((2, 2)))


def test_mfu_math():
    class V5e:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    peak = PEAK_TFLOPS_BF16["TPU v5 lite"] * 1e12
    # half the peak for one second on one chip
    assert abs(mfu(peak / 2, 1.0, n_chips=1, device=V5e()) - 0.5) < 1e-9
    # same work over two chips halves utilization again
    assert abs(mfu(peak / 2, 1.0, n_chips=2, device=V5e()) - 0.25) < 1e-9
    assert mfu(None, 1.0) is None
    assert mfu(1.0, 0.0) is None


def test_meter_reports_mfu_fields():
    meter = ThroughputMeter(batch_size=8, n_chips=1, flops_per_step=1e9)
    meter.start()
    for i in range(5):
        meter.record(i, {})
    out = meter.steady_state()
    assert "tflops_per_sec_per_chip" in out
    assert out["tflops_per_sec_per_chip"] > 0
    # mfu present only when the device kind is known (CPU here -> absent)
    if peak_flops_per_chip() is None:
        assert "mfu" not in out


def test_run_training_attaches_flops(devices):
    from serverless_learn_tpu.config import (
        DataConfig, ExperimentConfig, MeshConfig, OptimizerConfig,
        TrainConfig)
    from serverless_learn_tpu.training.loop import run_training

    cfg = ExperimentConfig(
        model="mlp_mnist", mesh=MeshConfig(dp=8),
        optimizer=OptimizerConfig(name="sgd", learning_rate=0.1),
        train=TrainConfig(batch_size=16, num_steps=3, dtype="float32",
                          param_dtype="float32"),
        data=DataConfig())
    _, meter = run_training(cfg)
    if meter.flops_per_step is not None:  # CPU exposes a cost model
        assert meter.flops_per_step > 1e6
        assert meter.steady_state()["tflops_per_sec_per_chip"] > 0
