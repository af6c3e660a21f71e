"""A whole tiny benchmark tree for the harness's CPU tests: a manifest,
two configurations of a 2-layer model and three traffic mixes, written
into a temporary root; the readers and the architectures are the real
ones. ``grow_moe`` adds a second architecture to it the way a later PR
would: files and manifest entries alone. Also where the recorded TPU
trace lies."""

from __future__ import annotations

import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "chipbench")

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
    "source": "tests", "published": {}, "reduced": {},
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
    ("flash_fwd_roofline", "%", ("train",)),
    ("flash_bwd_roofline", "%", ("train",)),
    ("decode_batch_occupancy", "%", ("tput", "lat")),
    ("serve_mfu", "%", ("tput", "lat")),
    ("decode_roofline", "%", ("tput", "lat")),
    ("useful_decode_share", "%", ("tput", "lat")),
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


# ``fixtures/moe_topk.py``'s configuration: other widths than any
# configuration of the benchmark's own, held to its own ``published``.
MOE_SOURCE = "tests/chipbench/fixtures/moe_topk.py"
MOE = {
    "arch": "moe_topk", "source": MOE_SOURCE,
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 256, "rope_theta": 10000.0,
    "max_position_embeddings": 256, "rms_norm_eps": 1e-6,
    "num_local_experts": 4, "num_experts_per_tok": 2,
    # A nested group whose list of layers is cut and whose width is not.
    "router_config": {"moe_layers": [0, 1], "router_dim": 64},
    "published": {"num_hidden_layers": 4,
                  "router_config": {"moe_layers": [0, 1, 2, 3],
                                    "router_dim": 64}},
    "reduced": {"num_hidden_layers": "2 of 4: a test's size",
                "router_config": "the layers that are left"},
}
F32 = {"dtype": "float32", "param_dtype": "float32"}
TRAINED_NAMES = ("trained_of_program_tree", "split_trained", "merge_trained",
                 "train_flops_per_token")


def _without(source: str, names: tuple) -> str:
    """A module's text with the top-level functions ``names`` cut out."""
    import ast

    tree = ast.parse(source)
    lines = source.splitlines(keepends=True)
    for node in reversed(tree.body):
        if isinstance(node, ast.FunctionDef) and node.name in names:
            del lines[node.lineno - 1:node.end_lineno]
    return "".join(lines)


def grow_moe(root: str, serving_only: bool = False,
             edit_config=None) -> str:
    """Grow a tree that ``write_tree`` wrote by a second architecture, as
    a later PR would: ``arch/moe_topk.py`` (``fixtures/moe_topk.py``), a
    serving configuration and cell and, unless ``serving_only``, a
    training configuration and cell; manifest entries; no edit to a file
    that was there. ``serving_only`` also cuts the architecture's four
    training names out of its file. ``edit_config(body, entry)`` may
    change a configuration's file and manifest entry before they are
    written (the manifest tests plant their faults with it)."""
    bench = os.path.join(root, "bench")
    fixture = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "fixtures", "moe_topk.py")
    with open(fixture) as f:
        text = f.read()
    with open(os.path.join(bench, "arch", "moe_topk.py"), "w") as f:
        f.write(_without(text, TRAINED_NAMES) if serving_only else text)
    configs = {
        "tiny-moe-serve": dict(
            MOE, program={"model": "moe_tiny", "mesh": {"dp": 1},
                          "train": F32, "kv": {"num_blocks": 96}},
            serve={"max_batch": 4},
            limits={"serve": {"served_logit_gap": 1e-3}}),
        "tiny-moe-train": dict(
            MOE, program={"model": "moe_tiny", "mesh": {"dp": 1},
                          "train": F32, "data": {"prefetch": 2},
                          "optimizer": {"name": "adamw",
                                        "learning_rate": 2e-4}},
            limits={"train": {"loss1_gap": 1e-4, "loss2_gap": 1e-4,
                              "loss3_gap": 1e-4, "grad_norm_gap": 1e-2,
                              "change_norm_gap": 1e-2}}),
    }
    workloads = {
        "moe-backlog": dict(
            LENGTHS, kind="serve_closed", clients=6, warm_in_replies=6,
            pool=256, pool_seed=11, length_cycle=6, check_requests=6,
            trace={"seconds": 0.3}),
        "moe-train": {
            "kind": "train", "sequences_per_step": 2,
            "tokens_per_sequence": 32, "trace": {"units": 1}},
    }
    cells = {"tiny-moe-backlog": ("tiny-moe-serve", "moe-backlog",
                                  ("serve_tokens_per_s", "serve_mfu.tput",
                                   "decode_roofline.tput",
                                   "useful_decode_share.tput",
                                   "device_idle_share.tput")),
             "tiny-moe-train": ("tiny-moe-train", "moe-train",
                                ("train_tokens_per_s", "train_mfu.train",
                                 "flash_attn_roofline.train",
                                 "device_idle_share.train"))}
    if serving_only:
        del cells["tiny-moe-train"]
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    for name, (config, traffic, listed_under) in cells.items():
        body = json.loads(json.dumps(configs[config]))
        entry = {"name": config, "source": MOE_SOURCE,
                 "reduced": sorted(body["reduced"]),
                 "file": f"bench/configs/{config}.json", "why": "dropped in"}
        if edit_config is not None:
            edit_config(body, entry)
        with open(os.path.join(bench, "configs", config + ".json"),
                  "w") as f:
            json.dump(body, f)
        with open(os.path.join(bench, "workloads", traffic + ".json"),
                  "w") as f:
            json.dump(workloads[traffic], f)
        manifest["configs"].append(entry)
        manifest["workloads"].append(
            {"name": name, "config": config, "traffic": traffic, "chips": 1,
             "why": "an architecture added as files"})
        for m in manifest["end_to_end"] + manifest["per_layer"]:
            if m["name"] in listed_under:
                m["workloads"].append(name)
    with open(path, "w") as f:
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
