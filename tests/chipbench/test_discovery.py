"""A later PR adds a cell or a per-layer metric as FILES and manifest
entries; nothing that is there is edited. Drop a dummy traffic mix and a
dummy metric reader in, and run them."""

import json
import os

import pytest

import tiny
from chipbench import run, trace_reduce


@pytest.fixture
def grown_root(tmp_path, no_compile_cache):
    import tiny

    root = tiny.write_tree(str(tmp_path))
    bench = os.path.join(root, "bench")
    with open(os.path.join(bench, "workloads", "dropped-in.json"), "w") as f:
        json.dump({"kind": "train", "sequences_per_step": 4,
                   "tokens_per_sequence": 16, "trace": {"units": 1}}, f)
    with open(os.path.join(bench, "metrics", "dummy_steps.py"), "w") as f:
        f.write("def read(run, entry):\n"
                "    return run['record']['counters']['steps']\n")
    with open(os.path.join(bench, "metrics", "silent.py"), "w") as f:
        f.write("def read(run, entry):\n    return None\n")
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    manifest["workloads"].append(
        {"name": "tiny-dropped-in", "config": "tiny-lora",
         "traffic": "dropped-in", "chips": 1, "why": "added as data"})
    for m in manifest["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("tiny-dropped-in")
    for name in ("dummy_steps.train", "silent.train"):
        manifest["per_layer"].append(
            {"name": name, "unit": "count", "better": "higher",
             "source": "program_counter", "layer": "train loop",
             "moves": "train_tokens_per_s",
             "workloads": ["tiny-dropped-in"]})
    with open(path, "w") as f:
        json.dump(manifest, f)
    return root


def test_a_dropped_in_workload_runs_with_no_edit(grown_root):
    line = run.run_cell("tiny-dropped-in", seed=3, seconds=0.2, trace=False,
                        root=grown_root, require_chip=False)
    assert line["correct"] is True
    assert line["notes"]["tokens_per_step"] == 64
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_a_dropped_in_metric_is_read_and_a_silent_one_left_out(
        grown_root, monkeypatch):
    # The CPU's own trace has no TPU plane: the reduction is given the
    # recorded TPU trace instead, so the traced path runs to its end.
    monkeypatch.setattr(trace_reduce, "find_xplane",
                        lambda d: tiny.FIXTURE_TRACE)
    line = run.run_cell("tiny-dropped-in", seed=3, seconds=0.2, trace=True,
                        root=grown_root, require_chip=False)
    assert line["metrics"]["dummy_steps.train"]["value"] == \
        line["notes"]["steps"]
    assert line["metrics"]["dummy_steps.train"]["unit"] == "count"
    assert "silent.train" not in line["metrics"]
    # Only the cell's own per-layer metrics are asked for.
    assert set(line["metrics"]) == {"dummy_steps.train"}
    assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > 0
    assert len(line["breakdown"]["device_ops"]) <= 10


def test_a_metric_without_a_reader_file_is_refused(grown_root):
    with pytest.raises(SystemExit, match="no reader"):
        run.load_metric_reader("not_there.train",
                               os.path.join(grown_root, "bench"))
