"""The hybrid architecture as the benchmark holds it
(``arch/hybrid_ssm.py``, ``configs/granite-4.0-h-micro-serve.json``,
``metrics/state_traffic_share.py``): its counts against hand counts at
the published sizes, a tiny cell of it end to end on the CPU (``correct``
true, nothing compiled inside the window, every shared reader reading
through its counts), the carried state dropped between two prefill
chunks (not correct), the programs its mix reaches, and the new reader
on a planted record."""

import json
import os

import pytest

import test_manifest
import tiny
import tiny_hybrid
from chipbench import cell as cell_mod
from chipbench import flops, run, trace_reduce

CELL = tiny_hybrid.CELL
REAL_CELL = "granite4h-micro-serve-backlog"


@pytest.fixture(scope="module")
def arch():
    return cell_mod.load_arch("hybrid_ssm", tiny.BENCH)


@pytest.fixture(scope="module")
def published(arch):
    cell = cell_mod.load_cell(REAL_CELL, tiny.ROOT)
    return cell, arch.sizes(cell.config)


@pytest.fixture(scope="module")
def hybrid_root(tmp_path_factory):
    return tiny_hybrid.grow_hybrid(tiny.write_tree(
        str(tmp_path_factory.mktemp("hybrid_bench"))))


@pytest.fixture(autouse=True)
def _no_cache(no_compile_cache):
    pass


def _run(root, trace=False, seed=2 ** 31 + 29):
    return run.run_cell(CELL, seed=seed, seconds=0.3, trace=trace,
                        root=root, require_chip=False)


# ---- counts at the published sizes, by hand -------------------------------

def test_the_published_models_parameters_by_hand(arch, published):
    _, sz = published
    assert (sz.n_layers, sz.n_mamba, sz.n_attention) == (40, 36, 4)
    assert [i for i, k in enumerate(sz.layer_types) if k == "attention"] \
        == [5, 15, 25, 35]
    in_proj = 2048 * (2 * 4096 + 2 * 128 + 64)       # [z | xBC | dt]
    out_proj = 4096 * 2048
    assert in_proj == 17_432_576
    assert arch.mamba_matmul_params(sz) == in_proj + out_proj
    assert arch.mlp_params(sz) == 2048 * 16384 + 8192 * 2048 == 50_331_648
    assert arch.attention_matmul_params(sz) \
        == 2 * 2048 * 2048 + 2 * 2048 * 512 == 10_485_760
    assert arch.head_params(sz) == 100_352 * 2048
    small = 4352 * 4 + 4352 + 3 * 64 + 4096   # conv, dt_bias A_log D, norm
    total = (36 * (in_proj + out_proj + small + 50_331_648)
             + 4 * (10_485_760 + 50_331_648)
             + 40 * 2 * 2048 + 2048 + 100_352 * 2048)
    assert arch.parameters(sz) == total == 3_191_396_096       # 3.19 B
    # What a decode step streams: all of it but the norms and the three
    # per-head vectors, 2 bytes each: 6.38 GB.
    assert arch.weight_bytes(sz) == 2 * (
        total - 36 * (3 * 64 + 4096) - 81 * 2048) == 6_382_151_680


def test_the_state_a_row_holds_and_a_tokens_keys_by_hand(arch, published):
    _, sz = published
    held = arch.state_bytes_per_row(sz)
    assert held["ssm"] == 36 * 64 * 64 * 128 * 4 == 75_497_472   # 75.5 MB
    assert held["conv"] == 36 * 3 * 4352 * 2 == 940_032
    assert arch.kv_bytes_per_token(sz) == 2 * 4 * 8 * 64 * 2 == 8192


def test_forward_flops_and_the_decode_step_by_hand(arch, published):
    _, sz = published
    matmul = (36 * (17_432_576 + 8_388_608 + 50_331_648)
              + 4 * (10_485_760 + 50_331_648) + 205_520_896)
    recurrence = 5 * 64 * 64 * 128 + 2 * 4 * 4352    # a Mamba layer, a token
    fwd = arch.forward_flops_per_token(sz, 300)
    assert fwd == (2 * matmul + 4 * (2 * 2 * 32 * 64 * 300)
                   + 36 * recurrence)
    # The scan and the convolution are 1.5 % of a token's operations;
    # attention's term counts the 4 attention layers only.
    assert 36 * recurrence / fwd == pytest.approx(0.0147, abs=2e-4)
    cost = arch.decode_step_cost(sz, rows=16, mean_context=450)
    state = 16 * 2 * (75_497_472 + 940_032)
    assert cost["state_bytes"] == state
    assert cost["bytes"] == 6_382_151_680 + state + 16 * 450 * 8192
    assert cost["flops"] == 16 * arch.forward_flops_per_token(sz, 450)
    t, bound = flops.least_seconds(cost["flops"], cost["bytes"],
                                   flops.peaks("TPU v5 lite"))
    assert bound == "memory" and t == pytest.approx(10.85e-3, rel=1e-2)
    # The state is 27.5 % of a step's least bytes at 16 rows, and would
    # pass the weights between 41 and 42 rows.
    assert state / cost["bytes"] == pytest.approx(0.275, abs=2e-3)
    per_row = 2 * (75_497_472 + 940_032)
    assert 41 * per_row < 6_382_151_680 < 42 * per_row


# ---- the real cell's files ------------------------------------------------

def test_the_configuration_holds_every_key_of_the_catalogs_row(published):
    """The file is the published ``config.json`` (the model-configs
    guide's catalog row) with ``max_position_embeddings`` alone changed."""
    cell, sz = published
    c = cell.config
    row = {"attention_bias": False, "attention_multiplier": 0.015625,
           "embedding_multiplier": 12, "hidden_act": "silu",
           "hidden_size": 2048, "intermediate_size": 8192,
           "logits_scaling": 8, "mamba_chunk_size": 256,
           "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
           "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
           "mamba_n_heads": 64, "mamba_proj_bias": False,
           "model_type": "granitemoehybrid",
           "normalization_function": "rmsnorm", "num_attention_heads": 32,
           "num_experts_per_tok": 0, "num_hidden_layers": 40,
           "num_key_value_heads": 8, "num_local_experts": 0,
           "position_embedding_type": "nope", "residual_multiplier": 0.22,
           "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
           "shared_intermediate_size": 8192, "tie_word_embeddings": True,
           "vocab_size": 100352}
    assert {k: c[k] for k in row} == row
    assert len(c["layer_types"]) == 40 and c["layer_types"].count(
        "attention") == 4
    assert c["published"] == {"max_position_embeddings": 131072}
    assert sorted(c["reduced"]) == ["max_position_embeddings"]
    ov = cell.program_config()["model_overrides"]
    assert ov["position"] == "none" and ov["rms_norm_eps"] == 1e-5
    assert ov["tie_embeddings"] is True and ov["max_seq_len"] == 8192


def test_the_real_mix_reaches_a_known_set_of_programs(arch, published):
    """The cell's own traffic file through ``reachable_shapes``, with the
    engine's sizes and no engine: every program a request of the mix can
    form, and no more than the dense decoder's way of counting gives."""
    cell, _ = published

    class Engine:     # what ``reachable_shapes`` reads of an engine
        _ps, prefill_chunk, chunk_size, max_slots = 16, 32, 32, 16
        _max_pages = 8192 // 16

    prefill, decode = arch.reachable_shapes(Engine, cell.traffic)
    assert {nb for nb, _, _ in prefill} == {1, 2, 4, 8, 16}
    # The shortest prompt (92) and its first chunk fill 8 pages: no
    # decode window of 4.
    assert {W for _, W in decode} == {16, 64}
    # A whole 32-token chunk at any depth of the prompt; a last partial
    # chunk in the 16 bucket only where a prompt of the mix ends in one
    # (141 = 4 x 32 + 13, at 9 pages); the other seven end in the 32
    # bucket. Beside a whole chunk such a row makes a (32, 16) program.
    assert {(T, W) for _, T, W in prefill} \
        == {(16, 16), (32, 4), (32, 16), (32, 64)}
    assert len(prefill) == 5 * 4 and len(decode) == 5 * 2
    lengths = arch._length_pairs(cell.traffic)
    assert len(lengths) == 8
    assert max(p + o for p, o in lengths) <= 1024


# ---- the tiny cell, end to end on the CPU ---------------------------------

def test_the_grown_tree_passes_the_manifests_checks(hybrid_root):
    for check in test_manifest.CHECKS:
        check(hybrid_root)


def test_the_tiny_hybrid_cell_is_correct(hybrid_root):
    line = _run(hybrid_root)
    assert line["correct"] is True, line["checked"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["notes"]["tokens_compared"] >= 40, line["notes"]
    assert line["notes"]["compiles_in_window"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    kv = line["notes"]["kv_stats"]
    assert kv["state_slots"] == 4 and kv["prefix_cache"].startswith("off")
    assert kv["state_bytes_per_slot"] == 3 * (8 * 16 * 16 * 4
                                              + 3 * 160 * 4)


def test_a_carry_dropped_between_prefill_chunks_is_not_correct(
        hybrid_root, monkeypatch):
    """The fault: every prefill row-chunk starts from a zero state, so a
    prompt of more than one chunk forgets its first. Prompts here are 20
    to 72 tokens in chunks of 32."""
    from serverless_learn_tpu.inference import kvcache

    real = kvcache.take_slots

    def forgetful(tree, names, ids, fresh=None):
        if fresh is not None:
            fresh = fresh | True
        return real(tree, names, ids, fresh)

    monkeypatch.setattr(kvcache, "take_slots", forgetful)
    line = _run(hybrid_root)
    assert line["correct"] is False
    n = line["checked"]["served_logit_gap"]
    assert n["value"] > 10 * n["limit"]
    assert line["checked"]["replies_wrong_length"]["value"] == 0


def test_the_traced_line_reads_through_the_hybrid_counts(
        hybrid_root, arch, monkeypatch):
    """Every reader the serving cells share, and the new one, on the tiny
    cell's own record. (The CPU has no published peak and its trace no
    TPU plane: the run is told it is a v5e and given the recorded trace,
    its one program under the decode chunk's name, as
    ``test_arch_dropped_in`` does. What the numbers were computed FROM is
    what is asserted.)"""
    recorded = trace_reduce.reduce_trace(tiny.FIXTURE_TRACE)
    as_chunks = dict(recorded, module_events=[
        (s, e, "jit_chunk") for s, e, _ in recorded["module_events"]])
    chunk_s = trace_reduce.whole_events(as_chunks, "chunk")
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: d)
    monkeypatch.setattr(trace_reduce, "reduce_trace", lambda path: as_chunks)
    monkeypatch.setattr(run, "device_info", lambda chips, require_chip: {
        "platform": "cpu", "kind": "TPU v5 lite", "count": 1})
    line = _run(hybrid_root, trace=True)
    assert line["correct"] is True, line["checked"]
    got = {k: m["value"] for k, m in line["metrics"].items()}
    assert {"serve_mfu.tput", "decode_roofline.tput",
            "useful_decode_share.tput", "device_idle_share.tput",
            "decode_batch_occupancy.tput", "compiles_in_window.tput",
            "ttft_ms_p50.tput", "queue_wait_ms_p50.tput",
            "state_traffic_share.tput"} <= set(got)
    # No trie: every prompt token was computed, none served from a page.
    assert got.get("prefix_hit_share.tput", 0.0) == 0.0
    cell = cell_mod.load_cell(CELL, hybrid_root)
    sz, c = cell.sizes, line["notes"]
    peak = flops.peaks("TPU v5 lite")
    tokens = c["prompt_tokens_arrived"] + c["output_tokens_arrived"]
    ctx = c["mean_context_arrived"]
    assert got["serve_mfu.tput"] == pytest.approx(
        100.0 * tokens * arch.forward_flops_per_token(sz, ctx / 2)
        / (line["window_s"] * peak["flops_per_s"]))
    rows = c["decoded_rows"] / c["chunks_run"]
    cost = arch.decode_step_cost(sz, rows, ctx, None)
    t, _ = flops.least_seconds(cost["flops"], cost["bytes"], peak)
    assert got["decode_roofline.tput"] == pytest.approx(
        100.0 * len(chunk_s) * c["chunk_size"] * t / sum(chunk_s))
    # The new reader: the engine's own bytes per slot over the
    # architecture's least bytes; both count the same state.
    assert c["state_bytes_per_slot"] == c["kv_stats"]["state_bytes_per_slot"]
    held = arch.state_bytes_per_row(sz)
    assert held["ssm"] + held["conv"] * 2 == c["state_bytes_per_slot"], \
        "float32 here: the carried inputs are 4 bytes, not 2"
    assert 0.0 < got["state_traffic_share.tput"] < 100.0


# ---- the new reader on a planted record -----------------------------------

def _planted(arch, published, kv_stats, iters):
    cell, _ = published
    return {"cell": cell, "notes": {},
            "record": {"spans": iters, "window_s": 2.0, "counters": {
                "kv_stats": kv_stats, "chunk_size": 32,
                "mean_context_arrived": 450.0}}}


def _iter(rows, chunks):
    return {"event": "sched_iter", "decode_rows": rows,
            "decode_steps": 32 * chunks}


def test_state_traffic_share_on_a_planted_record(arch, published):
    read = run.load_metric_reader("state_traffic_share.tput", tiny.BENCH)
    per_slot = 75_497_472 + 940_032
    # Three chunks of 16, 16 and 12 live rows, and an iteration that
    # decoded nothing: 44 / 3 rows a step.
    iters = [_iter(16, 1), _iter(28, 2), _iter(0, 0),
             {"event": "span", "span": "request"}]
    run_ = _planted(arch, published, {"state_bytes_per_slot": per_slot},
                    iters)
    rows = 44 / 3
    least = 6_382_151_680 + rows * 2 * per_slot + rows * 450 * 8192
    assert read(run_, None) == pytest.approx(
        100.0 * rows * 2 * per_slot / least)
    assert run_["notes"]["state_bytes_per_slot"] == per_slot
    # At 16 rows it is the 27.5 % of the hand count above.
    full = _planted(arch, published, {"state_bytes_per_slot": per_slot},
                    [_iter(16, 1)])
    assert read(full, None) == pytest.approx(27.5, abs=0.2)


@pytest.mark.parametrize("kv_stats", [
    {}, None, {"state_bytes_per_slot": 0}, {"paged": True}],
    ids=["no_stats", "none", "no_slot_state", "a_parents_stats"])
def test_state_traffic_share_is_silent_without_slot_state(
        arch, published, kv_stats):
    """A model whose every layer attends, or a program from before the
    engine knew slot state (the parent commit): nothing, and no raise."""
    read = run.load_metric_reader("state_traffic_share.tput", tiny.BENCH)
    assert read(_planted(arch, published, kv_stats, [_iter(16, 1)]),
                None) is None


def test_state_traffic_share_is_silent_without_a_decode_chunk(
        arch, published):
    read = run.load_metric_reader("state_traffic_share.tput", tiny.BENCH)
    assert read(_planted(arch, published, {"state_bytes_per_slot": 7},
                         [_iter(0, 0)]), None) is None
    assert read(_planted(arch, published, {"state_bytes_per_slot": 7}, []),
                None) is None
