"""A whole tiny benchmark tree for the harness's CPU tests: a manifest,
two configurations of a 2-layer model and three traffic mixes, written
into a temporary root; the readers and the architectures are the real
ones. Also where the
recorded TPU trace lies."""

from __future__ import annotations

import json
import os
import shutil

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "chipbench")

# A small trace recorded on a TPU v5e (PR 25's chip run).
FIXTURE_TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "fixtures", "tpu_v5e_small.xplane.pb")

MODEL = {
    "arch": "dense_gqa",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 256, "rope_theta": 10000.0,
    "max_position_embeddings": 256, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False,
}
SUFFIX_MOVES = {"train": "train_tokens_per_s", "tput": "serve_tokens_per_s",
                "lat": "serve_norm_latency_p95"}
# Every reader the benchmark has, under every suffix it can serve.
PER_LAYER = [
    ("compiles_in_window", "count", ("train", "tput", "lat")),
    ("device_idle_share", "%", ("train", "tput", "lat")),
    ("data_wait_share", "%", ("train",)),
    ("step_ms_p50", "ms", ("train",)),
    ("train_mfu", "%", ("train",)),
    ("flash_attn_roofline", "%", ("train",)),
    ("decode_batch_occupancy", "%", ("tput", "lat")),
    ("serve_mfu", "%", ("tput", "lat")),
    ("decode_roofline", "%", ("tput", "lat")),
    ("ttft_ms_p50", "ms", ("tput", "lat")),
    ("queue_wait_ms_p50", "ms", ("tput", "lat")),
    ("prefix_hit_share", "%", ("tput", "lat")),
]
LENGTHS = {
    "prompt_tokens": {"median": 40, "sigma": 0.5, "min": 20, "max": 72},
    "output_tokens": {"median": 12, "sigma": 0.5, "min": 4, "max": 40},
}


def write_tree(root: str, param_dtype: str = "float32") -> str:
    """Write the tiny tree under ``root`` and return ``root``."""
    bench = os.path.join(root, "bench")
    for sub in ("configs", "workloads"):
        os.makedirs(os.path.join(bench, sub), exist_ok=True)
    for sub in ("metrics", "arch"):
        shutil.copytree(os.path.join(BENCH, sub), os.path.join(bench, sub),
                        dirs_exist_ok=True)
    train_prog = {"model": "llama_tiny", "mesh": {"dp": 1},
                  "train": {"dtype": param_dtype, "param_dtype": param_dtype,
                            "remat": True},
                  "optimizer": {"name": "adamw", "learning_rate": 2e-4},
                  "data": {"prefetch": 2}}
    serve_prog = {"model": "llama_tiny", "mesh": {"dp": 1},
                  "train": {"dtype": param_dtype,
                            "param_dtype": param_dtype},
                  "kv": {"num_blocks": 96}}
    files = {
        "configs/tiny-lora.json": dict(
            MODEL, lora={"rank": 4, "alpha": 4.0}, program=train_prog,
            limits={"train": {"loss1_gap": 1e-4, "loss2_gap": 1e-4,
                              "loss3_gap": 1e-4, "grad_norm_gap": 1e-2,
                              "change_norm_gap": 1e-2}}),
        "configs/tiny-serve.json": dict(
            MODEL, program=serve_prog, serve={"max_batch": 4},
            limits={"serve": {"served_logit_gap": 1e-3}}),
        "workloads/train.json": {
            "kind": "train", "sequences_per_step": 2,
            "tokens_per_sequence": 32, "trace": {"units": 2}},
        "workloads/backlog.json": dict(
            LENGTHS, kind="serve_closed", clients=6, warm_in_replies=6,
            pool=256, pool_seed=7, length_cycle=6, check_requests=3,
            trace={"seconds": 0.5}),
        "workloads/steady.json": dict(
            LENGTHS, kind="serve_open", rate_per_s=20.0, warm_in_s=0.5,
            workers=8, pool=256, pool_seed=7, check_requests=3,
            shared_prefix={"count": 2, "tokens": 16, "share": 0.5},
            trace={"seconds": 0.5}),
    }
    for rel, body in files.items():
        with open(os.path.join(bench, rel), "w") as f:
            json.dump(body, f)
    cells = {"tiny-train": ("tiny-lora", "train", "train_tokens_per_s"),
             "tiny-backlog": ("tiny-serve", "backlog", "serve_tokens_per_s"),
             "tiny-steady": ("tiny-serve", "steady",
                             "serve_norm_latency_p95")}
    by_e2e = {e2e: name for name, (_, _, e2e) in cells.items()}
    e2e_units = {"train_tokens_per_s": "tokens/s",
                 "serve_tokens_per_s": "tokens/s",
                 "serve_norm_latency_p95": "ms/token"}
    per_layer = []
    for stem, unit, suffixes in PER_LAYER:
        for suffix in suffixes:
            moves = SUFFIX_MOVES[suffix]
            per_layer.append({
                "name": f"{stem}.{suffix}", "unit": unit, "better": "higher",
                "source": "program_counter", "layer": "tiny",
                "moves": moves, "workloads": [by_e2e[moves]]})
    manifest = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 1,
        "configs": [{"name": n, "source": "tests", "reduced": [],
                     "file": f"bench/configs/{n}.json", "why": "tiny"}
                    for n in ("tiny-lora", "tiny-serve")],
        "workloads": [{"name": name, "config": c, "traffic": t, "chips": 1,
                       "why": "tiny"} for name, (c, t, _) in cells.items()],
        "end_to_end": [{"name": n, "unit": u, "better": "higher",
                        "bound": 0.1, "source": "host_clock",
                        "workloads": [by_e2e[n]]}
                       for n, u in e2e_units.items()]
        + [{"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1,
            "source": "host_clock"}],
        "per_layer": per_layer,
    }
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root


def seeded_trees(cell, seed: int, dtype: str = "float32") -> tuple:
    """(canonical weights, the program's tree of them) of a cell from a
    seed, as both drivers make them."""
    import jax.numpy as jnp

    from chipbench import weights

    w = weights.make_weights(cell.arch, cell.sizes, weights.seed_u32(seed),
                             jnp.dtype(dtype))
    return w, cell.arch.to_program_tree(w)
