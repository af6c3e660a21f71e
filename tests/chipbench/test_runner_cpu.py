"""The runner end to end at a tiny size on the CPU, once per ``kind``:
the timed path agrees with the plain reference (``correct``), the line has
the contract's keys, and nothing in it passes for a chip's number."""

import json

import pytest

from chipbench import run

CELLS = {"tiny-train": "train_tokens_per_s",
         "tiny-backlog": "serve_tokens_per_s",
         "tiny-steady": "serve_norm_latency_p95"}


@pytest.fixture(scope="module")
def lines(tiny_root):
    out = {}
    mp = pytest.MonkeyPatch()
    mp.setattr(run, "place_caches", lambda root: None)
    try:
        for i, name in enumerate(CELLS):
            out[name] = run.run_cell(name, seed=2 ** 31 + 11 + i,
                                     seconds=0.6, trace=False,
                                     root=tiny_root, require_chip=False)
    finally:
        mp.undo()
    return out


@pytest.mark.parametrize("name", list(CELLS))
def test_tiny_cell_is_correct_against_the_reference(lines, name):
    line = lines[name]
    assert line["correct"] is True, line["checked"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert list(line)[-1] == "checked"
    for n in line["checked"].values():
        assert n["value"] <= n["limit"]


@pytest.mark.parametrize("name", list(CELLS))
def test_line_has_the_contracts_keys_and_names_its_device(lines, name):
    line = json.loads(json.dumps(lines[name]))
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in line
    assert set(line["metrics"]) == {CELLS[name], "setup_s"}
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert line["device"]["platform"] == "cpu"
    # No device-trace number without a chip: busy_s and window_s are not there.
    assert "busy_s" not in line["device"]


@pytest.mark.parametrize("name", list(CELLS))
def test_nothing_compiles_inside_the_window(lines, name):
    assert lines[name]["notes"]["compiles_in_window"] == 0


def test_the_command_refuses_to_run_without_a_chip(tiny_root, monkeypatch,
                                                   capsys):
    monkeypatch.setattr(run, "place_caches", lambda root: None)
    monkeypatch.setattr(run, "_ROOT", tiny_root)
    with pytest.raises(SystemExit) as exc:
        run.run_cell("tiny-train", seed=1, seconds=0.1, trace=False,
                     root=tiny_root)
    assert exc.value.code not in (0, None)
    assert "TPU" in str(exc.value.code)
    assert capsys.readouterr().out == ""


def test_an_unknown_workload_is_refused(tiny_root):
    with pytest.raises(SystemExit):
        run.run_cell("no-such-cell", seed=1, seconds=0.1, trace=False,
                     root=tiny_root, require_chip=False)
