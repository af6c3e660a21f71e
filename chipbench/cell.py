"""A cell as the harness sees it: manifest entry + its data files."""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict          # the configuration file
    traffic_name: str
    traffic: dict         # the workload (traffic mix) file
    manifest: dict        # BENCHMARK.json
    root: str             # directory BENCHMARK.json lies in
    bench_dir: str        # holds configs/, workloads/, metrics/, arch/

    @property
    def kind(self) -> str:
        return self.traffic["kind"]

    @property
    def arch(self):
        """The module of the architecture the configuration names: all
        the harness knows of the model's shape (README, "An
        architecture")."""
        if "arch" not in self.config:
            raise SystemExit(f"configuration {self.config_name!r} names no "
                             f"\"arch\" (a file of {self.bench_dir}/arch/)")
        return load_arch(self.config["arch"], self.bench_dir)

    @property
    def sizes(self):
        return self.arch.sizes(self.config)

    def metrics(self, section: str) -> list:
        """This cell's metrics of ``end_to_end`` or ``per_layer``: those
        with no ``workloads`` key, or that list the cell."""
        return [m for m in self.manifest[section]
                if "workloads" not in m or self.name in m["workloads"]]

    def program_config(self) -> dict:
        """The program's ``ExperimentConfig`` as a dict: the file's
        ``program`` section, with the model's sizes taken from the file's
        published keys by the architecture, so that they are stated once."""
        prog = json.loads(json.dumps(self.config["program"]))
        prog.setdefault("model_overrides", {}).update(
            self.arch.model_overrides(self.config))
        return prog


@functools.lru_cache(maxsize=None)
def load_arch(name: str, bench_dir: str):
    """``<bench_dir>/arch/<name>.py`` as a module, found as a file the way
    ``run.load_metric_reader`` finds a reader, so that a later PR (or a
    test's tree) drops one in. One module object per file: it is a static
    argument of the jitted reference and weights."""
    path = os.path.join(bench_dir, "arch", name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"architecture {name!r} has no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_arch_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod    # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> Cell:
    manifest = _read_json(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in manifest["workloads"] if w["name"] == name),
                 None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; known: "
                         f"{[w['name'] for w in manifest['workloads']]}")
    cfg_entry = next(c for c in manifest["configs"]
                     if c["name"] == entry["config"])
    config = _read_json(os.path.join(root, cfg_entry["file"]))
    bench_dir = os.path.dirname(os.path.dirname(
        os.path.join(root, cfg_entry["file"])))
    traffic = _read_json(os.path.join(bench_dir, "workloads",
                                      entry["traffic"] + ".json"))
    return Cell(name=name, chips=int(entry["chips"]),
                config_name=entry["config"], config=config,
                traffic_name=entry["traffic"], traffic=traffic,
                manifest=manifest, root=root, bench_dir=bench_dir)
