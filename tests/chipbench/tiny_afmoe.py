"""The tiny benchmark tree (``tiny.write_tree``) grown by a cell of the
AFMoE architecture, ``arch/afmoe.py``: the benchmark's OWN file of it
(``write_tree`` copies ``arch/`` and ``metrics/`` whole), a configuration
with the published keys at a test's sizes, a traffic file and manifest
entries; no edit to a file that was there."""

from __future__ import annotations

import json
import os

from tiny import LENGTHS

CELL = "tiny-afmoe-backlog"
# Two dense layers, then four expert layers of 8 experts (top 2) and a
# shared one; window, window, window, full, window, window; a window of
# 16 over pages of 4, so every prompt here (20 to 72 tokens) and every
# reply's context passes it, and a table window of 16 or 64 pages is
# wider than the 5 to 13 pages a window layer can touch.
AFMOE = {
    "arch": "afmoe", "source": "tests",
    "hidden_size": 64, "intermediate_size": 96, "head_dim": 16,
    "num_hidden_layers": 6,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"]
    + ["sliding_attention"] * 2,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "vocab_size": 256, "max_position_embeddings": 256,
    "rms_norm_eps": 1e-5, "rope_theta": 10000, "tie_word_embeddings": False,
    "sliding_window": 16, "num_dense_layers": 2, "num_experts": 8,
    "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "num_shared_experts": 1, "score_func": "sigmoid", "route_norm": True,
    "route_scale": 2.826, "n_group": 1, "topk_group": 1,
    "mup_enabled": True, "published": {}, "reduced": {},
}


def grow_afmoe(root: str) -> str:
    bench = os.path.join(root, "bench")
    config = dict(
        AFMOE,
        program={"model": "trinity_mini", "mesh": {"dp": 1},
                 "train": {"dtype": "float32", "param_dtype": "float32"},
                 "kv": {"block_size": 4}},
        serve={"max_batch": 4, "chunk_size": 8},
        # Float32 against float32: a served token is the reference's own
        # greedy choice unless two of its logits tie within float32
        # rounding of the two orders of summation.
        limits={"serve": {"served_logit_gap": 2e-5}})
    traffic = dict(LENGTHS, kind="serve_closed", clients=8,
                   warm_in_replies=8, pool=256, pool_seed=13,
                   length_cycle=6, check_requests=6,
                   trace={"seconds": 0.3})
    with open(os.path.join(bench, "configs", "tiny-afmoe-serve.json"),
              "w") as f:
        json.dump(config, f)
    with open(os.path.join(bench, "workloads", "afmoe-backlog.json"),
              "w") as f:
        json.dump(traffic, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    manifest["configs"].append(
        {"name": "tiny-afmoe-serve", "source": "tests", "reduced": [],
         "file": "bench/configs/tiny-afmoe-serve.json",
         "why": "dropped in"})
    manifest["workloads"].append(
        {"name": CELL, "config": "tiny-afmoe-serve",
         "traffic": "afmoe-backlog", "chips": 1,
         "why": "a model with experts and window layers, added as files"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if m["name"] == "serve_tokens_per_s" or m["name"].endswith(".tput"):
            m["workloads"].append(CELL)
    manifest["per_layer"].append(
        {"name": "experts_read_share.tput", "unit": "%", "better": "lower",
         "source": "program_counter", "layer": "tiny",
         "moves": "serve_tokens_per_s", "workloads": [CELL]})
    with open(path, "w") as f:
        json.dump(manifest, f)
    return root
