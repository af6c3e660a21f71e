"""``BENCHMARK.json`` against the contract's own limits, and against the
files it names: a manifest outside them is refused before a single run.

Every check is a function of a manifest's root, run on the repo's own
tree and on a tiny tree grown by a second architecture's configuration
(``tiny.grow_moe``: other widths, its own ``published``), so that the
next configuration of any model is held to its OWN source: no width may
be listed in ``reduced``, at top level or inside a nested group, whatever
the model. What this file knows of one model is a pin looked up by
``source``."""

import json
import os
import re

import pytest

import tiny

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTH = re.compile(r"hidden_size|intermediate_size|latent|state_size|_proj|"
                   r"_dim$|_rank$|head_dim|expansion|experts_per_tok")
# Published widths of a source, held exactly in every configuration that
# names it (from memory of the source's config.json; the configuration
# files say so under ``assumed``).
PINNED = {
    "https://huggingface.co/mistralai/Mistral-7B-v0.3/blob/main/config.json":
        {"hidden_size": 4096, "intermediate_size": 14336,
         "num_attention_heads": 32, "num_key_value_heads": 8,
         "head_dim": 128, "vocab_size": 32768},
}


def _manifest(root):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(text):
    return (isinstance(text, str) and 1 <= len(text) <= 200
            and "\n" not in text and "\t" not in text)


def _bench_dir(root, manifest):
    """Where ``cell.load_cell`` looks for workloads/ and metrics/: two
    levels above the first configuration's file."""
    return os.path.dirname(os.path.dirname(os.path.join(
        root, manifest["configs"][0]["file"])))


def check_top_level_keys_and_sizes(root):
    manifest = _manifest(root)
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(root, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    assert 1 <= len(manifest["command"]) <= 32
    assert all(_line(w) for w in manifest["command"])
    assert 1 <= len(manifest["paths"]) <= 16
    for p in manifest["paths"]:
        assert re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", p)
        assert not p.startswith("/") and ".." not in p.split("/")
        assert os.path.isdir(os.path.join(root, p))
    assert any(manifest["command"][1].startswith(p + "/")
               for p in manifest["paths"])


def _no_width_differs(body, published, where):
    """Inside a nested group that ``reduced`` lists, every key that names
    a width equals the published one, however deep."""
    for key, value in body.items():
        if WIDTH.search(key):
            assert key in published and value == published[key], \
                f"{where}.{key}"
        elif isinstance(value, dict):
            _no_width_differs(value, published.get(key, {}),
                              f"{where}.{key}")


def check_configs(root):
    manifest = _manifest(root)
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
        with open(os.path.join(root, c["file"])) as f:
            body = json.load(f)
        for key in c["reduced"]:
            # Never a width, whatever the model.
            assert NAME.match(key) and not WIDTH.search(key), key
            assert key in body and key in body["reduced"], key
            assert body[key] != body["published"][key], key
            if isinstance(body[key], dict):
                _no_width_differs(body[key], body["published"][key], key)
        # What the file says it reduced, and what it says the source
        # published otherwise, is what the manifest lists.
        assert sorted(body["reduced"]) == sorted(c["reduced"])
        assert sorted(body["published"]) == sorted(c["reduced"])
        assert body["source"] == c["source"]
        for key, value in PINNED.get(c["source"], {}).items():
            assert body[key] == value, (c["name"], key)


def check_workloads(root):
    manifest = _manifest(root)
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
            _bench_dir(root, manifest), "workloads", w["traffic"] + ".json"))


def check_metrics(root):
    manifest = _manifest(root)
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
        assert os.path.exists(os.path.join(
            _bench_dir(root, manifest), "metrics", stem + ".py"))
    for c in cells:
        assert "setup_s" in reports[c] and len(reports[c]) >= 2
        assert layers_of[c] >= 1
        mine = [m["name"] for m in per if c in m.get("workloads", cells)]
        # Every cell keeps its three: an mfu, a roofline, an idle share.
        assert any("mfu" in n for n in mine)
        assert any("_roofline" in n for n in mine)
        assert any(n.startswith("device_idle_share") for n in mine)


def check_every_file_under_paths_is_named_from_a_names_characters(root):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in _manifest(root)["paths"]:
        for d, dirs, files in os.walk(os.path.join(root, p)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), root)
                assert ok.match(rel), rel


CHECKS = [check_top_level_keys_and_sizes, check_configs, check_workloads,
          check_metrics,
          check_every_file_under_paths_is_named_from_a_names_characters]
_ids = lambda check: check.__name__[len("check_"):]


@pytest.mark.parametrize("check", CHECKS, ids=_ids)
def test_the_repos_own_manifest(check):
    check(ROOT)


@pytest.fixture(scope="module")
def grown_root(tmp_path_factory):
    return tiny.grow_moe(tiny.write_tree(
        str(tmp_path_factory.mktemp("grown_manifest"))))


@pytest.mark.parametrize("check", CHECKS, ids=_ids)
def test_a_tree_grown_by_another_architectures_configuration(check,
                                                             grown_root):
    """Other widths (64 / 128 / 4 heads of 16 / 256), their own
    ``published``, a nested group whose list is cut: every check passes
    with no edit to this file."""
    check(grown_root)


# ---- planted faults: each has to fail ``check_configs`` ------------------

def _a_width_listed_as_reduced(body, entry):
    body.update(hidden_size=32)
    body["published"]["hidden_size"] = 64
    body["reduced"]["hidden_size"] = "half the published width"
    entry["reduced"] = sorted(body["reduced"])


def _a_width_changed_inside_a_listed_group(body, entry):
    body["router_config"]["router_dim"] = 32


def _a_width_left_out_of_a_listed_group(body, entry):
    del body["published"]["router_config"]["router_dim"]


def _the_pinned_source_with_another_width(body, entry):
    mistral = next(iter(PINNED))
    body.update(source=mistral, hidden_size=2048)
    entry["source"] = mistral


def _a_change_the_manifest_does_not_list(body, entry):
    body.update(vocab_size=128)
    body["published"]["vocab_size"] = 256


def _a_listed_key_that_did_not_change(body, entry):
    body["num_hidden_layers"] = body["published"]["num_hidden_layers"]


FAULTS = [_a_width_listed_as_reduced, _a_width_changed_inside_a_listed_group,
          _a_width_left_out_of_a_listed_group,
          _the_pinned_source_with_another_width,
          _a_change_the_manifest_does_not_list,
          _a_listed_key_that_did_not_change]


@pytest.mark.parametrize("fault", FAULTS,
                         ids=lambda fault: fault.__name__.lstrip("_"))
def test_a_configuration_at_fault_is_refused(fault, tmp_path):
    root = tiny.grow_moe(tiny.write_tree(str(tmp_path)), edit_config=fault)
    with pytest.raises(AssertionError):
        check_configs(root)
    # Nothing else about the tree is at fault.
    for check in CHECKS:
        if check is not check_configs:
            check(root)
