"""The chip tools' control flow at a tiny size on the CPU (the look for a
chip skipped): ``sweep.py`` offers an open-loop mix at several rates from
one set-up; ``control.py`` reads program, control and fault on a few
seeds from one set-up and sums them up as lower and upper readings."""

import json

import pytest

from chipbench import cell as cell_mod
from chipbench import control, run, sweep


@pytest.fixture
def tiny_tools(tiny_root, monkeypatch, no_compile_cache):
    real_load = cell_mod.load_cell
    monkeypatch.setattr(cell_mod, "load_cell",
                        lambda name: real_load(name, tiny_root))
    monkeypatch.setattr(
        run, "device_info",
        lambda chips, require_chip: {"platform": "cpu", "kind": "cpu",
                                     "count": 1})
    for mod in (sweep, control):
        monkeypatch.setattr(mod, "_ROOT", tiny_root)
    return tiny_root


def _json_lines(text):
    return [json.loads(l) for l in text.splitlines() if l.startswith("{")]


def test_sweep_offers_each_rate_from_one_set_up(tiny_tools, monkeypatch,
                                                capsys):
    import os
    import shutil

    # sweep.py reads the traffic file from chipbench/workloads of its root.
    os.makedirs(os.path.join(tiny_tools, "chipbench"), exist_ok=True)
    shutil.copytree(os.path.join(tiny_tools, "bench", "workloads"),
                    os.path.join(tiny_tools, "chipbench", "workloads"),
                    dirs_exist_ok=True)
    assert sweep.main(["--workload", "tiny-backlog", "--traffic", "steady",
                       "--rates", "10,30", "--seconds", "0.4"]) == 0
    rows = _json_lines(capsys.readouterr().out)
    assert [r["rate_per_s"] for r in rows] == [10.0, 30.0]
    assert [r["due"] for r in rows] == [4, 12]
    assert all(r["failed"] == 0 and r["norm_latency_p95"] > 0 for r in rows)
    assert all(r["generator_lateness"]["n"] == r["due"] for r in rows)


def test_control_reads_lower_and_upper_from_one_set_up(tiny_tools, capsys):
    assert control.main(["--workload", "tiny-train", "--seeds", "3",
                         "--control-seeds", "1", "--seconds", "0"]) == 0
    rows = _json_lines(capsys.readouterr().out)
    per_seed, summary = rows[:-1], rows[-1]
    assert len(per_seed) == 3 and len({r["seed"] for r in per_seed}) == 3
    assert set(per_seed[0]["readings"]) == {"program", "control_bfloat16",
                                            "fault_half_batch"}
    assert set(per_seed[1]["readings"]) == {"program"}
    lower, upper = summary["lower"], summary["upper"]
    assert lower["grad_norm_gap"] == max(
        r["readings"]["program"]["grad_norm_gap"] for r in per_seed)
    # The control and the fault separate from the program's own reading.
    assert upper["control_bfloat16.grad_norm_gap"] > 3 * lower["grad_norm_gap"]
    assert upper["fault_half_batch.grad_norm_gap"] > \
        10 * lower["grad_norm_gap"]
