"""The tiny benchmark tree (``tiny.write_tree``) grown by a cell of the
hybrid architecture, ``arch/hybrid_ssm.py``: the benchmark's OWN file of
it (``write_tree`` copies ``arch/`` and ``metrics/`` whole), a
configuration with the published keys at a test's sizes, a traffic file
and manifest entries; no edit to a file that was there."""

from __future__ import annotations

import json
import os

from tiny import LENGTHS

CELL = "tiny-hybrid-backlog"
# Mamba, Mamba, attention, Mamba: an attention layer inside the period;
# chunks of 8 tokens, so a 32-token row-chunk crosses three boundaries of
# the chunked scan, and a prompt over 32 tokens one of the engine's.
HYBRID = {
    "arch": "hybrid_ssm", "source": "tests",
    "hidden_size": 64, "intermediate_size": 128,
    "shared_intermediate_size": 128, "num_hidden_layers": 4,
    "layer_types": ["mamba", "mamba", "attention", "mamba"],
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "vocab_size": 256, "max_position_embeddings": 256,
    "rms_norm_eps": 1e-5, "tie_word_embeddings": True,
    "position_embedding_type": "nope", "num_local_experts": 0,
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_d_conv": 4,
    "mamba_chunk_size": 8,
    "embedding_multiplier": 12, "attention_multiplier": 0.015625,
    "residual_multiplier": 0.22, "logits_scaling": 8,
    "published": {}, "reduced": {},
}


def grow_hybrid(root: str) -> str:
    bench = os.path.join(root, "bench")
    config = dict(
        HYBRID,
        program={"model": "granite_4_0_h_micro", "mesh": {"dp": 1},
                 "train": {"dtype": "float32", "param_dtype": "float32"}},
        serve={"max_batch": 4, "chunk_size": 8},
        limits={"serve": {"served_logit_gap": 2e-5}})
    traffic = dict(LENGTHS, kind="serve_closed", clients=8,
                   warm_in_replies=8, pool=256, pool_seed=13,
                   length_cycle=6, check_requests=6,
                   trace={"seconds": 0.3})
    with open(os.path.join(bench, "configs", "tiny-hybrid-serve.json"),
              "w") as f:
        json.dump(config, f)
    with open(os.path.join(bench, "workloads", "hybrid-backlog.json"),
              "w") as f:
        json.dump(traffic, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    manifest["configs"].append(
        {"name": "tiny-hybrid-serve", "source": "tests", "reduced": [],
         "file": "bench/configs/tiny-hybrid-serve.json",
         "why": "dropped in"})
    manifest["workloads"].append(
        {"name": CELL, "config": "tiny-hybrid-serve",
         "traffic": "hybrid-backlog", "chips": 1,
         "why": "a model with slot state, added as files"})
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
