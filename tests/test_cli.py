"""CLI layer (L4) — successor of the reference's `./master`, `./worker ADDR`,
`./file_server` shell surface (reference src/Makefile:26-35), where the only
CLI argument in the whole system was the worker's address and every interval
change required recompiling (src/serverless_learn.h:5-12)."""

import json
import os
import socket

import pytest

from serverless_learn_tpu.cli import (_config_from_args, _trainer_config,
                                      build_parser, main)


def _parse(argv):
    return build_parser().parse_args(argv)


def test_models_lists_registry(capsys):
    assert main(["models"]) == 0
    out = capsys.readouterr().out.split()
    assert "mlp_mnist" in out
    assert "resnet18_cifar" in out
    assert "llama_tiny" in out


def test_config_from_flags():
    args = _parse(["train", "--model", "llama_tiny", "--mesh", "dp=4,tp=2",
                   "--batch-size", "32", "--steps", "7", "--lr", "0.01",
                   "--optimizer", "sgd", "--seq-len", "64"])
    cfg = _config_from_args(args)
    assert cfg.model == "llama_tiny"
    assert (cfg.mesh.dp, cfg.mesh.tp) == (4, 2)
    assert cfg.train.batch_size == 32
    assert cfg.train.num_steps == 7
    assert cfg.optimizer.name == "sgd"
    assert cfg.optimizer.learning_rate == 0.01
    assert cfg.data.seq_len == 64


def test_config_file_set_and_flag_precedence(tmp_path):
    f = tmp_path / "cfg.json"
    f.write_text(json.dumps({
        "model": "mlp_mnist",
        "mesh": {"dp": 8},
        "train": {"batch_size": 64, "num_steps": 5},
    }))
    # --set overrides the file; dedicated flags override --set.
    args = _parse(["train", "--config", str(f),
                   "--set", "train.num_steps=9",
                   "--set", "train.seed=3",
                   "--batch-size", "16"])
    cfg = _config_from_args(args)
    assert cfg.train.num_steps == 9
    assert cfg.train.seed == 3
    assert cfg.train.batch_size == 16
    assert cfg.mesh.dp == 8


def test_default_mesh_uses_all_devices():
    import jax

    args = _parse(["train", "--model", "mlp_mnist"])
    assert _trainer_config(args).mesh.size == len(jax.devices())
    # Argument parsing alone names no devices (supervisors rely on it).
    assert _config_from_args(args).mesh.size == 1
    named = _parse(["train", "--model", "mlp_mnist", "--mesh", "dp=1"])
    assert _trainer_config(named).mesh.size == 1


def test_bad_set_syntax():
    with pytest.raises(SystemExit):
        _config_from_args(_parse(["train", "--set", "nonsense"]))


def test_train_end_to_end(capsys, tmp_path):
    from serverless_learn_tpu.utils.tracing import get_tracer

    get_tracer().reset()  # the span registry is process-global
    rc = main(["train", "--model", "mlp_mnist", "--mesh", "dp=8",
               "--batch-size", "16", "--steps", "3",
               "--checkpoint-dir", str(tmp_path / "ck"),
               "--checkpoint-every", "2"])
    assert rc == 0
    done = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert done["event"] == "done"
    assert done["final_step"] == 3
    assert done["spans"]["train/step"]["count"] == 3
    # final checkpoint written
    ck_files = [p for _, _, fs in os.walk(tmp_path / "ck") for p in fs]
    assert any("step-" in p for p in ck_files)


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_publish_stats_and_train_from_shard_server(capsys, tmp_path):
    from serverless_learn_tpu.control.daemons import start_shard_server

    port = _free_port()
    proc = start_shard_server(port=port, root=str(tmp_path))
    addr = f"127.0.0.1:{port}"
    try:
        rc = main(["publish", "--shard-server", addr, "--dataset", "cli_ds",
                   "--model", "mlp_mnist", "--num-records", "128",
                   "--records-per-shard", "64"])
        assert rc == 0
        pub = json.loads(capsys.readouterr().out.strip())
        assert pub["num_shards"] == 2

        rc = main(["train", "--model", "mlp_mnist", "--mesh", "dp=8",
                   "--batch-size", "16", "--steps", "3",
                   "--dataset", "cli_ds", "--shard-server", addr])
        assert rc == 0
        done = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert done["final_step"] == 3

        rc = main(["stats", "--addr", addr, "--kind", "shard-server"])
        assert rc == 0
        stats = json.loads(capsys.readouterr().out)
        assert stats["bytes_served"] > 0
        assert stats["rpc"]["rpc/fetch"]["count"] >= 1
    finally:
        proc.terminate()
        proc.wait(timeout=5)
