"""Multi-host bootstrap: elastic membership → JAX process group.

The reference's birth registration (src/worker.cc:117-129) only populated a
list; here the same contract assigns SPMD ranks and forms the
jax.distributed world (serverless_learn_tpu/parallel/multihost.py).
"""

import json
import os
import subprocess
import sys
import threading

import pytest

from serverless_learn_tpu.control.daemons import start_coordinator
from serverless_learn_tpu.parallel.multihost import (
    bootstrap_via_coordinator, free_port)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def coordinator_addr():
    port = free_port()
    proc = start_coordinator(port=port, lease_ttl_ms=5000, sweep_ms=100)
    yield f"127.0.0.1:{port}"
    proc.terminate()
    proc.wait(timeout=5)


def test_rank_assignment_three_hosts(coordinator_addr):
    """Three concurrent bootstraps agree on distinct ranks 0..2 and on
    rank 0's endpoint as the JAX coordinator (fake initialize)."""
    results = {}
    errors = []
    lock = threading.Lock()

    def host(i):
        calls = []

        def fake_init(addr, n, rank):
            calls.append((addr, n, rank))

        try:
            w = bootstrap_via_coordinator(
                coordinator_addr, world_size=3, name=f"h{i}",
                timeout_s=30, _initialize=fake_init)
            with lock:
                results[i] = (w, calls)
        except Exception as e:  # pragma: no cover
            with lock:
                errors.append(e)

    threads = [threading.Thread(target=host, args=(i,)) for i in range(3)]
    [t.start() for t in threads]
    [t.join(timeout=60) for t in threads]
    assert not errors
    assert len(results) == 3
    worlds = [w for w, _ in results.values()]
    try:
        ranks = sorted(w.rank for w in worlds)
        assert ranks == [0, 1, 2]
        assert len({w.jax_coordinator for w in worlds}) == 1, \
            "all hosts must agree on the JAX coordination endpoint"
        rank0 = next(w for w in worlds if w.rank == 0)
        assert rank0.jax_coordinator == rank0.agent.advertise_addr
        for _, calls in results.values():
            assert calls and calls[0][1] == 3
    finally:
        for w in worlds:
            w.shutdown()


def test_world_formation_timeout(coordinator_addr):
    with pytest.raises(TimeoutError):
        bootstrap_via_coordinator(coordinator_addr, world_size=2,
                                  timeout_s=1.0, _initialize=lambda *a: None)


_WORKER_SCRIPT = r"""
import os, sys, json
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("XLA_FLAGS", None)  # 1 device per process
import jax
from serverless_learn_tpu.parallel.multihost import bootstrap_via_coordinator
world = bootstrap_via_coordinator(sys.argv[1], world_size=2,
                                  name=f"proc{os.getpid()}", timeout_s=60)
assert jax.device_count() == 2, jax.device_count()
assert jax.process_count() == 2

from serverless_learn_tpu.config import (
    DataConfig, ExperimentConfig, MeshConfig, OptimizerConfig, TrainConfig)
from serverless_learn_tpu.training.loop import run_training
cfg = ExperimentConfig(
    model="mlp_mnist",
    mesh=MeshConfig(dp=2),
    optimizer=OptimizerConfig(name="sgd", learning_rate=0.1),
    train=TrainConfig(batch_size=16, num_steps=3),
    data=DataConfig(),
)
state, meter = run_training(cfg)
print(json.dumps({"rank": world.rank,
                  "step": int(jax.device_get(state.step)),
                  "loss_param_sum": float(
                      sum(abs(x).sum() for x in
                          jax.tree_util.tree_leaves(
                              jax.device_get(state.params))))}))
world.shutdown()
"""


def test_two_process_training(coordinator_addr, tmp_path):
    """Two real processes, one CPU device each, bootstrap ranks through the
    native coordinator, form a dp=2 global mesh, and take identical
    synchronized training steps."""
    script = tmp_path / "mh_worker.py"
    script.write_text(_WORKER_SCRIPT)
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    procs = [subprocess.Popen(
        [sys.executable, str(script), coordinator_addr],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env,
        cwd=REPO, text=True) for _ in range(2)]
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=240)
        assert p.returncode == 0, f"worker failed:\n{err[-2000:]}"
        outs.append(json.loads(out.strip().splitlines()[-1]))
    assert sorted(o["rank"] for o in outs) == [0, 1]
    assert all(o["step"] == 3 for o in outs)
    # Synchronous DP: after psum'd gradients both replicas hold identical
    # parameters.
    assert abs(outs[0]["loss_param_sum"] - outs[1]["loss_param_sum"]) < 1e-4
