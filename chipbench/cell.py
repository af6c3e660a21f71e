"""A cell as the harness sees it: manifest entry + its data files."""

from __future__ import annotations

import dataclasses
import json
import os

from chipbench.weights import Sizes

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
    bench_dir: str        # holds configs/, workloads/ and metrics/

    @property
    def kind(self) -> str:
        return self.traffic["kind"]

    @property
    def sizes(self) -> Sizes:
        return Sizes.from_config(self.config, self.config.get("lora"))

    def metrics(self, section: str) -> list:
        """This cell's metrics of ``end_to_end`` or ``per_layer``: those
        with no ``workloads`` key, or that list the cell."""
        return [m for m in self.manifest[section]
                if "workloads" not in m or self.name in m["workloads"]]

    def program_config(self) -> dict:
        """The program's ``ExperimentConfig`` as a dict: the file's
        ``program`` section, with the model's sizes taken from the file's
        published keys so that they are stated once."""
        prog = json.loads(json.dumps(self.config["program"]))
        c = self.config
        ov = prog.setdefault("model_overrides", {})
        ov.update({
            "vocab_size": c["vocab_size"], "d_model": c["hidden_size"],
            "n_layers": c["num_hidden_layers"],
            "n_heads": c["num_attention_heads"],
            "n_kv_heads": c["num_key_value_heads"],
            "d_ff": c["intermediate_size"],
            "max_seq_len": c["max_position_embeddings"],
            "rope_theta": c["rope_theta"],
            "tie_embeddings": c["tie_word_embeddings"],
        })
        lora = c.get("lora")
        if lora:
            ov.update({"lora_rank": lora["rank"], "lora_alpha": lora["alpha"]})
        return prog


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
