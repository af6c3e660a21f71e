"""``BENCHMARK.json`` against the contract's own limits, and against the
files it names: a manifest outside them is refused before a single run."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTH = re.compile(r"hidden_size|intermediate_size|latent|state_size|_proj|"
                   r"_dim$|_rank$|head_dim|expansion|experts_per_tok")


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(text):
    return (isinstance(text, str) and 1 <= len(text) <= 200
            and "\n" not in text and "\t" not in text)


def test_top_level_keys_and_sizes(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    assert 1 <= len(manifest["command"]) <= 32
    assert all(_line(w) for w in manifest["command"])
    assert 1 <= len(manifest["paths"]) <= 16
    for p in manifest["paths"]:
        assert re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", p)
        assert not p.startswith("/") and ".." not in p.split("/")
        assert os.path.isdir(os.path.join(ROOT, p))
    assert any(manifest["command"][1].startswith(p + "/")
               for p in manifest["paths"])


def test_configs(manifest):
    assert 1 <= len(manifest["configs"]) <= 24
    names = [c["name"] for c in manifest["configs"]]
    files = [c["file"] for c in manifest["configs"]]
    assert len(set(names)) == len(names) and len(set(files)) == len(files)
    used = {w["config"] for w in manifest["workloads"]}
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["why"]) and _line(c["source"])
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
        assert len(c["reduced"]) <= 16
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTH.search(key), key
            assert key in body and key in body["reduced"], key
            assert body[key] != body["published"][key]
        # What the file says it reduced is what the manifest lists.
        assert sorted(body["reduced"]) == sorted(c["reduced"])
        assert body["source"] == c["source"]
        # No width differs from the published Mistral-7B-v0.3 config.
        assert (body["hidden_size"], body["intermediate_size"],
                body["num_attention_heads"], body["num_key_value_heads"],
                body["head_dim"], body["vocab_size"]) == (
                    4096, 14336, 32, 8, 128, 32768)


def test_workloads(manifest):
    cells = manifest["workloads"]
    assert 1 <= len(cells) <= 24
    names = [w["name"] for w in cells]
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(set(names)) == len(names) and len(set(pairs)) == len(pairs)
    configs = {c["name"] for c in manifest["configs"]}
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert _line(w["why"])
        assert os.path.exists(os.path.join(
            ROOT, "chipbench", "workloads", w["traffic"] + ".json"))


def test_metrics(manifest):
    e2e, per = manifest["end_to_end"], manifest["per_layer"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(per) <= 128
    names = [m["name"] for m in e2e + per]
    assert len(set(names)) == len(names)
    cells = {w["name"] for w in manifest["workloads"]}
    assert "setup_s" in {m["name"] for m in e2e}
    reports = {c: set() for c in cells}
    for m in e2e:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        for c in m.get("workloads", cells):
            assert c in cells
            reports[c].add(m["name"])
    layers_of = {c: 0 for c in cells}
    for m in per:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and _line(m["layer"])
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in {x["name"] for x in e2e}
        if m["name"].endswith("_roofline") or "_roofline." in m["name"]:
            assert m["unit"] == "%"
        for c in m.get("workloads", cells):
            assert m["moves"] in reports[c], (m["name"], c)
            layers_of[c] += 1
        stem = m["name"].split(".", 1)[0]
        assert os.path.exists(os.path.join(ROOT, "chipbench", "metrics",
                                           stem + ".py"))
    for c in cells:
        assert "setup_s" in reports[c] and len(reports[c]) >= 2
        assert layers_of[c] >= 1
        mine = [m["name"] for m in per if c in m.get("workloads", cells)]
        # Every cell keeps its three: an mfu, a roofline, an idle share.
        assert any("mfu" in n for n in mine)
        assert any("_roofline" in n for n in mine)
        assert any(n.startswith("device_idle_share") for n in mine)


def test_every_file_under_paths_is_named_from_a_names_characters(manifest):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in manifest["paths"]:
        for d, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), ROOT)
                assert ok.match(rel), rel
