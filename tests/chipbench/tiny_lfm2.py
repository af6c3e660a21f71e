"""The tiny benchmark tree (``tiny.write_tree``) grown by a cell of the
LFM2-MoE architecture, ``arch/lfm2_moe.py``: the benchmark's OWN file of
it (``write_tree`` copies ``arch/`` and ``metrics/`` whole), a
configuration with the published keys at a test's sizes, a traffic file
and manifest entries; no edit to a file that was there."""

from __future__ import annotations

import json
import os

from tiny import LENGTHS

CELL = "tiny-lfm2-backlog"
# The published pattern's first six layers: conv, conv, attention, conv,
# conv, conv; two dense layers, then four expert layers of 8 experts
# (top 2). Prompts of 20 to 72 tokens in prefill chunks of 32, so most
# prompts link a chunk to the one before it.
LFM2 = {
    "arch": "lfm2_moe", "source": "tests",
    "hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 6,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv",
                    "conv"],
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "vocab_size": 256, "max_position_embeddings": 256, "norm_eps": 1e-5,
    "rope_theta": 1000000, "conv_L_cache": 3, "conv_bias": False,
    "num_dense_layers": 2, "num_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "use_expert_bias": True,
    "norm_topk_prob": True, "routed_scaling_factor": 1.0,
    "model_type": "lfm2_moe", "published": {}, "reduced": {},
}


def grow_lfm2(root: str) -> str:
    bench = os.path.join(root, "bench")
    config = dict(
        LFM2,
        program={"model": "lfm2_8b_a1b", "mesh": {"dp": 1},
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
    with open(os.path.join(bench, "configs", "tiny-lfm2-serve.json"),
              "w") as f:
        json.dump(config, f)
    with open(os.path.join(bench, "workloads", "lfm2-backlog.json"),
              "w") as f:
        json.dump(traffic, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    manifest["configs"].append(
        {"name": "tiny-lfm2-serve", "source": "tests", "reduced": [],
         "file": "bench/configs/tiny-lfm2-serve.json",
         "why": "dropped in"})
    manifest["workloads"].append(
        {"name": CELL, "config": "tiny-lfm2-serve",
         "traffic": "lfm2-backlog", "chips": 1,
         "why": "a model with conv slot state and experts, added as files"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if m["name"] == "serve_tokens_per_s" or m["name"].endswith(".tput"):
            m["workloads"].append(CELL)
    manifest["per_layer"].append(
        {"name": "state_traffic_share.tput", "unit": "%", "better": "lower",
         "source": "program_counter", "layer": "tiny",
         "moves": "serve_tokens_per_s", "workloads": [CELL]})
    with open(path, "w") as f:
        json.dump(manifest, f)
    return root
