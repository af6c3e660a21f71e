"""The LFM2-MoE architecture as the benchmark holds it (``arch/lfm2_moe.py``,
``configs/lfm2-8b-a1b-serve.json``, ``workloads/serve-backlog-wide.json``):
its counts against hand counts at the published sizes, the programs the
real mix reaches, a tiny cell end to end on the CPU (``correct`` true),
the experts made over their own buffers, the traced line's readers
through its counts (``state_traffic_share.tput`` reading the conv
mixers' slot state), and the configuration file against the manifest.
The planted faults are ``test_lfm2_moe_faults.py``'s."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_manifest
import tiny
import tiny_lfm2
from chipbench import cell as cell_mod
from chipbench import flops, run, trace_reduce, weights

CELL = tiny_lfm2.CELL
REAL_CELL = "lfm2-8b-a1b-serve-wide"


@pytest.fixture(scope="module")
def arch():
    return cell_mod.load_arch("lfm2_moe", tiny.BENCH)


@pytest.fixture(scope="module")
def published(arch):
    cell = cell_mod.load_cell(REAL_CELL, tiny.ROOT)
    return cell, arch.sizes(cell.config)


@pytest.fixture(scope="module")
def lfm2_root(tmp_path_factory):
    return tiny_lfm2.grow_lfm2(tiny.write_tree(
        str(tmp_path_factory.mktemp("lfm2_bench"))))


@pytest.fixture(autouse=True)
def _no_cache(no_compile_cache):
    pass


def _run(root, trace=False, seed=2 ** 31 + 43):
    return run.run_cell(CELL, seed=seed, seconds=0.3, trace=trace,
                        root=root, require_chip=False)


# ---- counts at the published sizes, by hand -------------------------------

CONV = 2048 * 6144 + 2048 * 2048 + 3 * 2048          # in, out, taps
ATTN = 2 * 2048 * 2048 + 2 * 2048 * 8 * 64 + 2 * 64   # q, o; k, v; norms
DENSE = 3 * 2048 * 7168
EXPERT = 3 * 2048 * 1792
ROUTER = 2048 * 32 + 32                              # and the bias
EMBED = 65_536 * 2048
NORMS = 2 * 2048                                     # a block's two


def test_the_published_models_parameters_by_hand(arch, published):
    """The cut (layers 0-11) and the whole model (24 layers), tied."""
    cell, sz = published
    assert (sz.n_layers, sz.n_conv, sz.n_attention) == (12, 9, 3)
    assert (sz.n_dense, sz.n_moe, sz.head_dim) == (2, 10, 64)
    assert arch.conv_params(sz) == CONV == 16_783_360
    assert arch.attention_params(sz) == ATTN == 10_485_888
    assert arch.dense_params(sz) == DENSE == 44_040_192
    assert 32 * EXPERT + ROUTER == 352_387_104
    assert arch.parameters(sz) == (
        9 * CONV + 3 * ATTN + 2 * DENSE + 10 * (32 * EXPERT + ROUTER)
        + 12 * NORMS + EMBED + 2048) == 3_928_728_256   # 7.86 GB in bf16
    whole = arch.sizes(arch.at_depth(cell.config, 24))
    assert (whole.n_conv, whole.n_attention, whole.n_moe) == (18, 6, 22)
    assert arch.parameters(whole) == (
        18 * CONV + 6 * ATTN + 2 * DENSE + 22 * (32 * EXPERT + ROUTER)
        + 24 * NORMS + EMBED + 2048) == 8_339_930_560   # 8.34 B
    # An untied head would be 8.47 B: the published 8.3 B is the tied.
    assert arch.parameters(whole) + EMBED == pytest.approx(8.474e9,
                                                           rel=1e-3)


def test_the_state_a_row_holds_and_a_tokens_keys_by_hand(arch, published):
    _, sz = published
    # 9 conv layers x 2 carried inputs x 2048 channels, bfloat16.
    assert arch.state_bytes_per_row(sz) == {"conv": 9 * 2 * 2048 * 2} \
        == {"conv": 73_728}
    # 3 attention layers x keys and values x 8 heads of 64, bfloat16.
    assert arch.kv_bytes_per_token(sz) == 3 * 2 * 8 * 64 * 2 == 6144
    # The auto pool: 64 slots x 256 blocks of 16 tokens.
    assert 64 * 256 * 16 * 6144 == pytest.approx(1.61e9, rel=1e-3)


def test_forward_flops_and_the_decode_step_by_hand(arch, published):
    _, sz = published
    fixed = 9 * CONV + 3 * ATTN + 2 * DENSE + 10 * ROUTER + EMBED
    assert arch.fixed_params(sz) == fixed == 405_461_696     # 0.81 GB
    # 4 routed experts of 32, the conv mixers' taps and two gates, and
    # attention's term in the 3 attention layers only.
    fwd = arch.forward_flops_per_token(sz, 1400)
    assert fwd == 2 * (fixed + 10 * 4 * EXPERT) + 3 * 2 * 2 * 2048 * 1400 \
        + 9 * 8 * 2048
    # Uniform routing of 64 rows reads 32 (1 - (28/32)^64) = 31.99
    # experts a layer: every expert, 7.05 GB a step.
    cost = arch.decode_step_cost(sz, rows=64, mean_context=1400)
    per_layer = 32 * (1 - (28 / 32) ** 64)
    assert per_layer == pytest.approx(31.99, abs=0.01)
    assert cost["experts_read"] == pytest.approx(10 * per_layer)
    state = 64 * 2 * 73_728
    kv = 64 * 1400 * 6144
    assert cost["state_bytes"] == state
    assert cost["bytes"] == pytest.approx(
        2 * (fixed + 10 * per_layer * EXPERT) + state + kv)
    assert cost["bytes"] == pytest.approx(8.416e9, rel=1e-3)
    t, bound = flops.least_seconds(cost["flops"], cost["bytes"],
                                   flops.peaks("TPU v5 lite"))
    assert bound == "memory" and t == pytest.approx(10.28e-3, rel=1e-2)
    experts = 2 * 10 * per_layer * EXPERT
    assert experts / cost["bytes"] == pytest.approx(0.84, abs=0.01)
    # Counted from a record: 250 experts a step over 64 steps.
    rec = {"spans": [{"event": "sched_iter", "decode_steps": 32,
                      "experts_read": 32 * 250} for _ in range(2)]}
    counted = arch.decode_step_cost(sz, 64, 1400, rec)
    assert counted["experts_read"] == 250
    assert counted["bytes"] == pytest.approx(
        2 * (fixed + 250 * EXPERT) + state + kv)


# ---- the real cell's files ------------------------------------------------

CATALOG_ROW = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
    "num_key_value_heads": 8, "rope_theta": 1_000_000,
    "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65_536}
PUBLISHED_KINDS = ["conv", "conv", "full_attention", "conv", "conv", "conv",
                   "full_attention", "conv", "conv", "conv",
                   "full_attention", "conv", "conv", "conv",
                   "full_attention", "conv", "conv", "conv",
                   "full_attention", "conv", "conv", "full_attention",
                   "conv", "conv"]


def test_the_configuration_holds_every_key_of_the_catalogs_row(published):
    """The file is the published ``config.json`` (the catalog's row) with
    depth, its layer kinds and the positions alone changed."""
    cell, _ = published
    c = cell.config
    assert {k: c[k] for k in CATALOG_ROW} == CATALOG_ROW
    pub = c["published"]
    assert sorted(c["reduced"]) == sorted(pub) == [
        "layer_types", "max_position_embeddings", "num_hidden_layers"]
    assert (pub["num_hidden_layers"], pub["max_position_embeddings"]) \
        == (24, 128_000)
    assert pub["layer_types"] == PUBLISHED_KINDS
    assert c["layer_types"] == PUBLISHED_KINDS[:12]
    assert c["num_hidden_layers"] == 12 and c["max_position_embeddings"] \
        == 4096
    assert {"sizes", "tie_embedding", "equations", "epsilon", "precision",
            "weights"} <= set(c["assumed"])
    ov = cell.program_config()["model_overrides"]
    assert ov["layer_types"] == ["conv", "conv", "attention"] \
        + ["conv"] * 3 + ["attention"] + ["conv"] * 3 + ["attention",
                                                         "conv"]
    assert (ov["ssm_conv"], ov["moe_d_ff"], ov["n_experts"],
            ov["moe_top_k"]) == (3, 1792, 32, 4)
    assert cell.config["program"]["model"] == "lfm2_8b_a1b"
    assert cell.config["serve"]["max_batch"] == 64


def test_the_real_mix_reaches_a_known_set_of_programs(arch, published):
    """The cell's own traffic through ``reachable_shapes`` with the
    engine's sizes and no engine: every context is past 256 tokens, so a
    decode chunk's table window is 64 or 256 pages; prompts end in a
    chunk of the 8 and 16 buckets at W 64."""
    cell, _ = published

    class Engine:     # what ``reachable_shapes`` reads of an engine
        _ps, prefill_chunk, chunk_size, max_slots = 16, 32, 32, 64
        _max_pages = 4096 // 16

    prefill, decode = arch.reachable_shapes(Engine, cell.traffic)
    nbs = [1, 2, 4, 8, 16, 32, 64]
    assert decode == [(nb, W) for nb in nbs for W in (64, 256)]
    assert {(T, W) for _, T, W in prefill} \
        == {(8, 64), (16, 64), (32, 4), (32, 16), (32, 64), (32, 256)}
    assert len(prefill) == 7 * 6
    pairs = sorted(arch._arch("hybrid_ssm")._length_pairs(cell.traffic))
    assert pairs == [(490, 239), (754, 243), (822, 179), (965, 422),
                     (1137, 510), (1309, 627), (1528, 280), (2487, 281)]
    assert max(p + o for p, o in pairs) == 2768


# ---- the tiny cell, end to end on the CPU ---------------------------------

def test_the_grown_tree_passes_the_manifests_checks(lfm2_root):
    for check in test_manifest.CHECKS:
        check(lfm2_root)


def test_the_tiny_lfm2_cell_is_correct(lfm2_root):
    line = _run(lfm2_root)
    assert line["correct"] is True, line["checked"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["notes"]["tokens_compared"] >= 40, line["notes"]
    assert line["notes"]["compiles_in_window"] == 0
    kv = line["notes"]["kv_stats"]
    assert (kv["moe_layers"], kv["n_experts"]) == (4, 8)
    assert kv["state_slots"] == 4 and kv["prefix_cache"].startswith("off")
    # Five conv layers x 2 carried inputs x 64 channels, float32.
    assert kv["state_bytes_per_slot"] == 5 * 2 * 64 * 4


def test_experts_are_made_over_their_own_buffers(lfm2_root):
    """A routed expert's matrices are the layer's own plus EXPERT_SPREAD
    times its own, made leaf by leaf over the own part's buffer (the part
    is gone after, so no second copy of an expert leaf is held), and the
    canonical tree keeps them in place of the parts; the reference reads
    the same model from the parts (the serving check) as from the made
    matrices."""
    cell = cell_mod.load_cell(CELL, lfm2_root)
    arch, sz = cell.arch, cell.sizes
    make = lambda: weights.make_weights(arch, sz, weights.seed_u32(11),
                                        jnp.dtype("float32"))
    parts, w = make(), make()
    layer = w["layers"][sz.n_dense]
    own = layer["e_gate"]
    arch.to_program_tree(w)
    assert own.is_deleted()
    made = w["layers"][sz.n_dense]
    assert not any(k.endswith("_base") for k in made)
    ref = parts["layers"][sz.n_dense]
    np.testing.assert_array_equal(
        np.asarray(made["e_gate"]),
        np.asarray(ref["e_gate_base"] + ref["e_gate"]))
    flat = np.asarray(made["e_gate"]).reshape(sz.n_experts, -1)
    norms = np.linalg.norm(flat, axis=1)
    cos = flat @ flat.T / np.outer(norms, norms)
    alike = cos[~np.eye(sz.n_experts, dtype=bool)]
    want = 1 / (1 + arch.EXPERT_SPREAD ** 2)
    assert abs(float(np.mean(alike)) - want) < 0.03
    seq = jax.random.randint(jax.random.PRNGKey(3), (1, 40), 0, sz.vocab)
    np.testing.assert_allclose(
        np.asarray(arch.head(parts, arch.trunk(parts, seq, sz), sz)),
        np.asarray(arch.head(w, arch.trunk(w, seq, sz), sz)),
        rtol=1e-6, atol=1e-6)


# ---- the traced line ------------------------------------------------------

def test_the_traced_line_reads_through_the_lfm2_counts(lfm2_root, arch,
                                                      monkeypatch):
    """Every reader the serving cells share on the tiny cell's own record
    (the CPU has no peak and no TPU plane: the run is told it is a v5e
    and given the recorded trace under the decode chunk's name, as
    ``test_hybrid_ssm`` does). ``state_traffic_share.tput`` reads the
    conv mixers' carried inputs, the engine's own bytes over the
    architecture's least bytes; ``decode_roofline.tput`` counts the
    experts the window's records saw."""
    recorded = trace_reduce.reduce_trace(tiny.FIXTURE_TRACE)
    as_chunks = dict(recorded, module_events=[
        (s, e, "jit_chunk") for s, e, _ in recorded["module_events"]])
    chunk_s = trace_reduce.whole_events(as_chunks, "chunk")
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: d)
    monkeypatch.setattr(trace_reduce, "reduce_trace", lambda path: as_chunks)
    monkeypatch.setattr(run, "device_info", lambda chips, require_chip: {
        "platform": "cpu", "kind": "TPU v5 lite", "count": 1})
    line = _run(lfm2_root, trace=True)
    assert line["correct"] is True, line["checked"]
    got = {k: m["value"] for k, m in line["metrics"].items()}
    assert {"serve_mfu.tput", "decode_roofline.tput",
            "useful_decode_share.tput", "device_idle_share.tput",
            "compiles_in_window.tput", "state_traffic_share.tput"} \
        <= set(got)
    assert got.get("prefix_hit_share.tput", 0.0) == 0.0
    cell = cell_mod.load_cell(CELL, lfm2_root)
    sz, c = cell.sizes, line["notes"]
    peak = flops.peaks("TPU v5 lite")
    tokens = c["prompt_tokens_arrived"] + c["output_tokens_arrived"]
    ctx = c["mean_context_arrived"]
    assert got["serve_mfu.tput"] == pytest.approx(
        100.0 * tokens * arch.forward_flops_per_token(sz, ctx / 2)
        / (line["window_s"] * peak["flops_per_s"]))
    held = arch.state_bytes_per_row(sz)["conv"]
    assert c["state_bytes_per_slot"] == 2 * held, \
        "float32 here: the carried inputs are 4 bytes, not 2"
    assert 0.0 < got["state_traffic_share.tput"] < 100.0
    rows = c["decoded_rows"] / c["chunks_run"]
    cost = arch.decode_step_cost(sz, rows, ctx, None)
    t, _ = flops.least_seconds(cost["flops"], cost["bytes"], peak)
    assert got["decode_roofline.tput"] != pytest.approx(
        100.0 * len(chunk_s) * c["chunk_size"] * t / sum(chunk_s))
    assert got["decode_roofline.tput"] > 0


def _planted(published, kv_stats, iters):
    cell, _ = published
    return {"cell": cell, "notes": {},
            "record": {"spans": iters, "window_s": 2.0, "counters": {
                "kv_stats": kv_stats, "chunk_size": 32,
                "mean_context_arrived": 1400.0}}}


def _iter(rows, chunks, experts_a_step=320):
    return {"event": "sched_iter", "decode_rows": rows,
            "decode_steps": 32 * chunks,
            "experts_read": 32 * chunks * experts_a_step}


def test_state_traffic_share_on_a_planted_record(published):
    """The conv mixers' carried inputs, read and written by every live
    row, against the step's least bytes: a hundredth of a percent, since
    the step streams every expert for 64 rows."""
    read = run.load_metric_reader("state_traffic_share.tput", tiny.BENCH)
    per_slot = 73_728
    iters = [_iter(64, 1), _iter(120, 2), _iter(0, 0),
             {"event": "span", "span": "request"}]
    run_ = _planted(published, {"state_bytes_per_slot": per_slot}, iters)
    rows = 184 / 3
    fixed = 405_461_696
    least = (2 * (fixed + 320 * EXPERT) + rows * 2 * per_slot
             + rows * 1400 * 6144)
    assert read(run_, None) == pytest.approx(
        100.0 * rows * 2 * per_slot / least)
    assert run_["notes"]["state_bytes_per_slot"] == per_slot
    full = _planted(published, {"state_bytes_per_slot": per_slot},
                    [_iter(64, 1)])
    assert read(full, None) == pytest.approx(0.112, abs=0.002)


def test_the_configuration_file_and_the_manifest_agree():
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "lfm2-8b-a1b-serve")
    assert entry["file"] == "chipbench/configs/lfm2-8b-a1b-serve.json"
    with open(os.path.join(tiny.ROOT, entry["file"])) as f:
        body = json.load(f)
    assert entry["source"] == body["source"]
    assert entry["reduced"] == sorted(body["reduced"])
    cell = next(w for w in manifest["workloads"] if w["name"] == REAL_CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "serve-backlog-wide"
    assert cell["config"] == "lfm2-8b-a1b-serve"
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert REAL_CELL in e2e["serve_tokens_per_s"]["workloads"]
    # Every serving reader the hybrid cell reports, its state's
    # included; not the experts' share, which reads 99.98 at 64 rows.
    granite = {m["name"] for m in manifest["per_layer"]
               if "granite4h-micro-serve-backlog" in m.get("workloads", [])}
    mine = {m["name"] for m in manifest["per_layer"]
            if REAL_CELL in m.get("workloads", [])}
    assert mine == granite and len(mine) == 18
    assert "experts_read_share.tput" not in mine
