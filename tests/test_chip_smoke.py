"""CPU drive of what PR 21's bring-up added: ``chip_smoke.py`` refuses to
run without a TPU, its leg functions pass at tiny size on the virtual CPU
mesh (kernels interpreted), the compile-cache helper places the cache from
outside, and no parent that starts chip-using children initialises a JAX
backend first."""

import json
import math
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _python(code_or_args, env_extra=None, env_drop=(), timeout=120):
    env = {k: v for k, v in os.environ.items() if k not in env_drop}
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update(env_extra or {})
    args = (["-c", code_or_args] if isinstance(code_or_args, str)
            else list(code_or_args))
    return subprocess.run([sys.executable] + args, cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=timeout)


# -- the script as a whole ----------------------------------------------------


def test_smoke_refuses_the_cpu_and_names_it():
    p = _python([os.path.join(REPO, "chip_smoke.py")],
                env_extra={"JAX_PLATFORMS": "cpu"})
    assert p.returncode not in (0, 1), p.stderr
    assert "'cpu'" in p.stderr and "no leg was run" in p.stderr
    assert '"ok"' not in p.stdout and "LEG" not in p.stdout


def test_plan_covers_the_contract_on_one_and_four_devices():
    one = {name: kw for name, _, kw in chip_smoke._plan(1)}
    assert set(one) == {"train_resnet18", "train_llama1b_lora",
                        "serve_llama1b", "flash_llama1b_heads",
                        "kv_pool_llama1b"}
    assert one["train_resnet18"]["batch_per_chip"] == 8192
    assert one["train_llama1b_lora"]["mesh"] == "dp=1"
    assert "model_overrides.lora_rank=16" in one["train_llama1b_lora"]["sets"]
    assert one["flash_llama1b_heads"] == dict(
        heads=32, kv_heads=8, head_dim=64, causal_len=4096, lengths_len=512)
    four = {name: kw for name, _, kw in chip_smoke._plan(4)}
    assert four["train_resnet18"]["mesh"] == "dp=4"
    assert "train.zero_stage=1" in four["train_resnet18"]["sets"]
    assert four["train_llama1b_lora"]["mesh"] == "dp=1,fsdp=2,tp=2"
    assert four["train_llama1b_lora"]["expect_sharded"] == ["params", "batch"]


# -- the legs, at tiny size ---------------------------------------------------


def test_train_leg_dp_zero1_on_the_cpu_mesh(devices):
    res = chip_smoke.train_leg(
        "mlp_mnist", batch_per_chip=4, steps=3, expect_loss=math.log(10),
        mesh="dp=8", optimizer="sgd", lr=0.1, sets=["train.zero_stage=1"],
        expect_sharded=["opt_state", "batch"])
    assert res["final_step"] == 3 and len(res["losses"]) == 3
    place = res["placement"]
    assert (place["opt_state"]["max_bytes_per_device"]
            < place["opt_state"]["global_bytes"])
    assert (place["params"]["max_bytes_per_device"]
            == place["params"]["global_bytes"])  # dp replicates parameters


def test_train_leg_lora_fsdp_tp_on_the_cpu_mesh(devices):
    res = chip_smoke.train_leg(
        "llama_tiny", batch_per_chip=1, seq_len=32, steps=2,
        expect_loss=math.log(512), mesh="dp=2,fsdp=2,tp=2",
        sets=["model_overrides.lora_rank=4", "train.remat=true"],
        expect_sharded=["params", "batch"])
    assert res["final_step"] == 2
    assert (res["placement"]["params"]["max_bytes_per_device"]
            <= 0.6 * res["placement"]["params"]["global_bytes"])


def test_train_leg_fails_on_a_wrong_loss(devices):
    with pytest.raises(chip_smoke.LegFailed, match="not within 15%"):
        chip_smoke.train_leg("mlp_mnist", batch_per_chip=4, steps=2,
                             expect_loss=math.log(1000), mesh="dp=8")


def test_flash_leg_interpreted(devices):
    res = chip_smoke.flash_leg(heads=4, kv_heads=2, head_dim=16,
                               causal_len=128, lengths_len=128, impl="flash")
    assert res["interpret"] is True  # CPU: the kernels run interpreted
    assert res["causal"]["max_rel_err"] < 4e-2
    assert res["kv_lengths"]["shape"] == [2, 128, 4, 16]


def test_kv_pool_info_matches_arithmetic_on_cpu(devices):
    info = chip_smoke.kv_pool_info("llama_tiny", max_slots=2)
    # llama_tiny: 2 layers x (K, V) x [96 blocks, 16, 2 kv heads, 32] bf16.
    assert info["num_blocks"] == 2 * 32 + 32
    assert info["arithmetic_bytes"] == 2 * 2 * 96 * 16 * 2 * 32 * 2
    assert info["measured_bytes"] is None  # CPU reports no memory_stats


def test_serve_leg_over_the_wire_llama_tiny(tmp_path):
    res = chip_smoke.serve_leg(
        "llama_tiny", vocab=512, long_prompt=70, long_new=190,
        shared_prefix=64, timeout_s=300.0,
        log_path=str(tmp_path / "serve.log"))
    names = [r["name"] for r in res["requests"]]
    assert names == ["short", "long", "prefix_a", "prefix_b", "short_again"]
    assert res["kv"]["prefix_hit_rate_lifetime"] > 0
    assert res["kv"]["blocks_free"] <= res["kv"]["blocks_total"]


def test_serve_leg_fails_on_an_error_reply(monkeypatch):
    """The dispatcher answers a failed compile with {"error": ...} and the
    server exits 0 later; the leg must fail on the reply itself."""
    import socketserver
    import threading

    class Handler(socketserver.StreamRequestHandler):
        def handle(self):
            self.rfile.readline()
            self.wfile.write(json.dumps(
                {"error": "XlaRuntimeError: RESOURCE_EXHAUSTED"}
            ).encode() + b"\n")

    srv = socketserver.ThreadingTCPServer(("127.0.0.1", 0), Handler)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    addr = "127.0.0.1:%d" % srv.server_address[1]

    class FakeServe:
        """Stands in for the ``serve`` child: announces the stub's addr."""
        pid = os.getpid()
        returncode = None
        stdout = [json.dumps({"event": "serving", "addr": addr}) + "\n"]

        def poll(self):
            return None

    monkeypatch.setattr(chip_smoke.subprocess, "Popen",
                        lambda *a, **k: FakeServe())
    monkeypatch.setattr(chip_smoke, "_stop_group", lambda proc: 0)
    try:
        with pytest.raises(chip_smoke.LegFailed, match="RESOURCE_EXHAUSTED"):
            chip_smoke.serve_leg("llama_tiny", vocab=512, long_prompt=70,
                                 long_new=190, shared_prefix=64,
                                 timeout_s=30.0)
    finally:
        srv.shutdown()
        srv.server_close()


# -- a compile cache that can be placed from outside ---------------------------

_CACHE_PROBE = (
    "import os\n"
    "from serverless_learn_tpu.utils.compile_cache import place_compile_cache\n"
    "set_ = place_compile_cache()\n"
    "print(repr(set_), os.environ.get('JAX_COMPILATION_CACHE_DIR'))\n")


def test_cache_helper_sets_nothing_when_the_variable_is_set(tmp_path):
    outside = str(tmp_path / "cc")
    p = _python(_CACHE_PROBE + "import jax\n"
                "print(jax.config.jax_compilation_cache_dir)\n",
                env_extra={"JAX_COMPILATION_CACHE_DIR": outside,
                           "JAX_PLATFORMS": "cpu"})
    assert p.returncode == 0, p.stderr
    first, second = p.stdout.strip().splitlines()
    assert first == f"None {outside}" and second == outside


def test_cache_helper_picks_one_fixed_path_in_the_checkout():
    runs = [_python(_CACHE_PROBE, env_drop=("JAX_COMPILATION_CACHE_DIR",))
            for _ in range(2)]
    outs = [p.stdout.strip() for p in runs]
    want = os.path.join(REPO, ".jax_cache")
    assert outs[0] == outs[1] == f"{want!r} {want}", (outs, runs[0].stderr)
    ignored = open(os.path.join(REPO, ".gitignore")).read().split()
    assert ".jax_cache/" in ignored and "chiprun_out/" in ignored


def test_cache_helper_leaves_a_process_with_jax_alone(monkeypatch):
    """The test suite itself (JAX already imported) stays cacheless, even
    through ``cli.main``."""
    from serverless_learn_tpu import cli
    from serverless_learn_tpu.utils.compile_cache import place_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert place_compile_cache() is None
    assert cli.main(["models"]) == 0
    assert "JAX_COMPILATION_CACHE_DIR" not in os.environ


# -- one process for each chip --------------------------------------------------

_SUPERVISOR_PROBE = r"""
import sys
from serverless_learn_tpu import cli
from serverless_learn_tpu.training import elastic_multihost

seen = {}

class Supervisor:
    def __init__(self, config, store, **kw):
        seen["mesh"] = config.mesh.nontrivial_axes()
        seen["kw"] = sorted(kw)
    def run(self):
        return []

elastic_multihost.ElasticHostSupervisor = Supervisor
rc = cli.main(["worker", "--multihost", "run", "--model", "mlp_mnist",
               "--checkpoint-dir", sys.argv[1], "--min-hosts", "1"])
from jax._src import xla_bridge
print(rc, xla_bridge.backends_are_initialized(), seen["mesh"])
"""


def test_worker_multihost_supervisor_initialises_no_backend(tmp_path):
    p = _python(["-c", _SUPERVISOR_PROBE, str(tmp_path)],
                env_extra={"JAX_PLATFORMS": "cpu"})
    assert p.returncode == 0, p.stderr
    # Exit 0, no backend in the supervisor, and no device count baked into
    # the config it hands its inner trainers (scale_mesh derives dp there).
    assert p.stdout.strip().splitlines()[-1] == "0 False {}"


_ROUTE_PROBE = r"""
import io, os, sys, _thread
from serverless_learn_tpu import cli

class Tap(io.TextIOBase):
    def write(self, s):
        sys.__stdout__.write(s)
        if '"routing"' in s:
            _thread.interrupt_main()  # the router is up: stop the command
        return len(s)
    def flush(self):
        sys.__stdout__.flush()

sys.stdout = Tap()
try:
    cli.main(["route", "--replicas", "127.0.0.1:9", "--port", "0",
              "--metrics-port", "0", "--health"])
except KeyboardInterrupt:
    pass
sys.__stdout__.write("JAX_IMPORTED %s\n" % ("jax" in sys.modules))
sys.__stdout__.flush()
os._exit(0)
"""


def test_route_never_imports_jax():
    p = _python(_ROUTE_PROBE, env_extra={"JAX_PLATFORMS": "cpu"})
    assert p.returncode == 0, p.stderr
    assert '"routing"' in p.stdout
    assert p.stdout.strip().splitlines()[-1] == "JAX_IMPORTED False"
