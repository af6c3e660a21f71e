"""The AFMoE architecture as the benchmark holds it (``arch/afmoe.py``,
``configs/trinity-mini-serve.json``, ``metrics/experts_read_share.py``):
its counts against hand counts at the published sizes, the programs the
real mix reaches, a tiny cell end to end on the CPU (``correct`` true,
the program's logits against the reference's through prefill and
decode), planted faults that ``correct`` has to catch, and the new
reader on the tiny cell's own record and on planted ones. The planted
faults are ``test_afmoe_faults.py``'s."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_manifest
import tiny
import tiny_afmoe
from chipbench import cell as cell_mod
from chipbench import flops, run, trace_reduce, weights

CELL = tiny_afmoe.CELL
REAL_CELL = "trinity-mini-serve-long-in"


@pytest.fixture(scope="module")
def arch():
    return cell_mod.load_arch("afmoe", tiny.BENCH)


@pytest.fixture(scope="module")
def published(arch):
    cell = cell_mod.load_cell(REAL_CELL, tiny.ROOT)
    return cell, arch.sizes(cell.config)


@pytest.fixture(scope="module")
def afmoe_root(tmp_path_factory):
    return tiny_afmoe.grow_afmoe(tiny.write_tree(
        str(tmp_path_factory.mktemp("afmoe_bench"))))


@pytest.fixture(autouse=True)
def _no_cache(no_compile_cache):
    pass


def _run(root, trace=False, seed=2 ** 31 + 41):
    return run.run_cell(CELL, seed=seed, seconds=0.3, trace=trace,
                        root=root, require_chip=False)


# ---- counts at the published sizes, by hand -------------------------------

ATTN = 3 * 2048 * 32 * 128 + 2 * 2048 * 4 * 128     # q, gate, o; k, v
DENSE = 3 * 2048 * 6144
ROUTER, SHARED, EXPERT = 2048 * 128, 3 * 2048 * 1024, 3 * 2048 * 1024
HEAD = 2048 * 200_192


def test_the_published_models_parameters_by_hand(arch, published):
    _, sz = published
    assert (sz.n_layers, sz.n_dense, sz.n_moe) == (6, 2, 4)
    assert [sz.windowed(i) for i in range(6)] == [True] * 3 + [False] \
        + [True] * 2
    assert arch.attention_params(sz) == ATTN == 27_262_976
    assert arch.expert_params(sz) == EXPERT == 6_291_456
    assert arch.moe_fixed_params(sz) == ROUTER + SHARED
    fixed = 6 * ATTN + 2 * DENSE + 4 * (ROUTER + SHARED) + HEAD
    assert arch.fixed_params(sz) == fixed == 675_282_944
    projections = fixed + 4 * 128 * EXPERT + HEAD     # and the embedding
    assert projections == 4_306_501_632
    norms = 6 * (4 * 2048 + 2 * 128) + 2048
    assert arch.parameters(sz) == projections + norms + 4 * 128 \
        == 4_306_554_880                              # 8.61 GB in bf16
    assert arch.kv_bytes_per_token(sz) == 2 * 6 * 4 * 128 * 2 == 12_288


def test_forward_flops_and_the_decode_step_by_hand(arch, published):
    _, sz = published
    # 8 routed and the shared expert, not 128; window layers see at most
    # 2,048 keys, the full layer all 3,000.
    fwd = arch.forward_flops_per_token(sz, 3000)
    attn = 2 * 2 * 32 * 128 * (5 * 2048 + 3000)
    assert fwd == 2 * (675_282_944 + 4 * 8 * EXPERT) + attn
    # Uniform routing of 32 rows reads 128 (1 - (120/128)^32) = 111.8
    # experts a layer: the step's least bytes are some 7.8 GB.
    cost = arch.decode_step_cost(sz, rows=32, mean_context=2300)
    per_layer = 128 * (1 - (120 / 128) ** 32)
    assert cost["experts_read"] == pytest.approx(4 * per_layer)
    kv = 32 * 2048 * (5 * 2048 + 2300)
    assert cost["bytes"] == pytest.approx(
        2 * (675_282_944 + 4 * per_layer * EXPERT) + kv)
    assert cost["bytes"] == pytest.approx(7.81e9, rel=2e-3)
    t, bound = flops.least_seconds(cost["flops"], cost["bytes"],
                                   flops.peaks("TPU v5 lite"))
    assert bound == "memory" and t == pytest.approx(9.53e-3, rel=1e-2)
    # The experts are three quarters of those bytes.
    experts = 2 * 4 * per_layer * EXPERT
    assert experts / cost["bytes"] == pytest.approx(0.72, abs=0.01)
    # Counted from a record: 300 experts a step over 64 steps.
    rec = {"spans": [{"event": "sched_iter", "decode_steps": 32,
                      "experts_read": 32 * 300} for _ in range(2)]}
    counted = arch.decode_step_cost(sz, 32, 2300, rec)
    assert counted["experts_read"] == 300
    assert counted["bytes"] == pytest.approx(
        2 * (675_282_944 + 300 * EXPERT) + kv)


# ---- the real cell's files ------------------------------------------------

CATALOG_ROW = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144,
    "load_balance_coeff": 0.001, "model_type": "afmoe",
    "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1,
    "num_attention_heads": 32, "num_dense_layers": 2,
    "num_expert_groups": 1, "num_experts": 128, "num_experts_per_tok": 8,
    "num_key_value_heads": 4, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.826,
    "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
    "vocab_size": 200_192}


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
        == (32, 131_072)
    assert pub["layer_types"] == (["sliding_attention"] * 3
                                  + ["full_attention"]) * 8
    assert c["layer_types"] == pub["layer_types"][:6]
    assert c["num_hidden_layers"] == 6 and c["max_position_embeddings"] \
        == 4096
    ov = cell.program_config()["model_overrides"]
    assert ov["layer_types"] == ["window_rope"] * 3 + ["full_nope"] \
        + ["window_rope"] * 2
    assert ov["embedding_multiplier"] == pytest.approx(2048 ** 0.5)
    assert ov["head_dim_override"] == 128 and ov["moe_d_ff"] == 1024


def test_the_real_mix_reaches_a_known_set_of_programs(arch, published):
    """The cell's own traffic through ``reachable_shapes`` with the
    engine's sizes and no engine. Every prompt of the mix is over 1,024
    tokens, so every decode chunk has a table window of 256 pages; two
    prompts end in a chunk of the 16 bucket, deep in the prompt."""
    cell, _ = published

    class Engine:     # what ``reachable_shapes`` reads of an engine
        _ps, prefill_chunk, chunk_size, max_slots = 16, 32, 32, 32
        _max_pages = 4096 // 16

    prefill, decode = arch.reachable_shapes(Engine, cell.traffic)
    nbs = [1, 2, 4, 8, 16, 32]
    assert decode == [(nb, 256) for nb in nbs]
    assert {(T, W) for _, T, W in prefill} \
        == {(16, 256), (32, 4), (32, 16), (32, 64), (32, 256)}
    assert len(prefill) == 6 * 5
    pairs = sorted(arch._hybrid()._length_pairs(cell.traffic))
    assert len(pairs) == 8 and max(p + o for p, o in pairs) == 3772
    # Half the prompts pass the window; the longest request, which the
    # check always takes, crosses it by more than 512.
    assert sum(p > 2048 for p, _ in pairs) == 4
    assert max(p + o for p, o in pairs) > 2048 + 512


# ---- the tiny cell, end to end on the CPU ---------------------------------

def test_the_grown_tree_passes_the_manifests_checks(afmoe_root):
    for check in test_manifest.CHECKS:
        check(afmoe_root)


def test_the_tiny_afmoe_cell_is_correct(afmoe_root):
    line = _run(afmoe_root)
    assert line["correct"] is True, line["checked"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["notes"]["tokens_compared"] >= 40, line["notes"]
    assert line["notes"]["compiles_in_window"] == 0
    kv = line["notes"]["kv_stats"]
    assert (kv["moe_layers"], kv["n_experts"]) == (4, 8)
    assert kv["prefix_cache"] == "on"


def test_prefill_then_decode_logits_match_the_reference(afmoe_root):
    """The program's own logits, not its tokens: a 44-token prompt in
    chunks of 32 through the paged pool, then 28 decode steps, against
    one float32 forward of the reference over the whole sequence (72
    positions, four and a half windows). Both are float32; the bound is
    rounding of two orders of summation."""
    from serverless_learn_tpu.inference import kvcache
    from serverless_learn_tpu.inference.generate import init_cache
    from serverless_learn_tpu.models.registry import get_model

    cell = cell_mod.load_cell(CELL, afmoe_root)
    arch, sz = cell.arch, cell.sizes
    w, prog = tiny.seeded_trees(cell, 5)
    ov = cell.program_config()["model_overrides"]
    module = get_model("trinity_mini", dtype=jnp.float32,
                       param_dtype=jnp.float32, **ov).module
    seq = jax.random.randint(jax.random.PRNGKey(9), (1, 72), 0, sz.vocab)
    pmod = kvcache.paged_module(module, 4, 64)
    pages, _ = kvcache.split_cache(init_cache(pmod, 1))
    tbl = jnp.arange(64, dtype=jnp.int32)[None]

    @jax.jit
    def step(pages, toks, start):
        cache = kvcache.with_tables(pages, tbl, start[None])
        n = toks.shape[1]
        logits, upd = pmod.apply(
            {"params": prog, "cache": cache}, toks, extend=n > 1,
            decode=n == 1, mutable=["cache"])
        return kvcache.split_cache(upd["cache"])[0], logits[0]

    got = []
    for start, n in [(0, 32), (32, 12)] + [(p, 1) for p in range(44, 72)]:
        pages, logits = step(pages, seq[:, start:start + n],
                             jnp.int32(start))
        got.append(logits)
    ref = arch.head(w, arch.trunk(w, seq, sz), sz)[0]
    np.testing.assert_allclose(np.concatenate(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_experts_are_seeded_alike_and_made_once(afmoe_root):
    """A routed expert's matrices are the layer's own plus EXPERT_SPREAD
    times its own: two experts of a layer are alike to 1 / (1 + s^2), and
    a matrix is N(0, 1/fan_in) as a whole. ``to_program_tree`` makes each
    once and leaves it in the canonical tree in place of its parts; the
    reference reads the same model from the parts (the serving check)
    as from the made matrices."""
    cell = cell_mod.load_cell(CELL, afmoe_root)
    arch, sz = cell.arch, cell.sizes
    w, _ = tiny.seeded_trees(cell, 11)
    parts = weights.make_weights(arch, sz, weights.seed_u32(11),
                                 jnp.dtype("float32"))
    layer = parts["layers"][sz.n_dense]
    assert set(layer) >= {"e_gate_base", "e_up_base", "e_down_base"}
    made = w["layers"][sz.n_dense]
    assert not any(k.endswith("_base") for k in made)
    gate = np.asarray(made["e_gate"])
    np.testing.assert_array_equal(
        gate, np.asarray(layer["e_gate_base"] + layer["e_gate"]))
    flat = gate.reshape(sz.n_experts, -1)
    norms = np.linalg.norm(flat, axis=1)
    cos = flat @ flat.T / np.outer(norms, norms)
    alike = cos[~np.eye(sz.n_experts, dtype=bool)]
    want = 1 / (1 + arch.EXPERT_SPREAD ** 2)
    assert abs(float(np.mean(alike)) - want) < 0.02
    assert float(np.std(gate)) == pytest.approx(sz.d_model ** -0.5,
                                                rel=0.05)
    seq = jax.random.randint(jax.random.PRNGKey(3), (1, 40), 0, sz.vocab)
    np.testing.assert_allclose(
        np.asarray(arch.head(parts, arch.trunk(parts, seq, sz), sz)),
        np.asarray(arch.head(w, arch.trunk(w, seq, sz), sz)),
        rtol=1e-6, atol=1e-6)


# ---- the traced line, and the new reader ----------------------------------

def test_the_traced_line_reads_through_the_afmoe_counts(afmoe_root, arch,
                                                        monkeypatch):
    """Every reader the serving cells share, and the new one, on the tiny
    cell's own record (the CPU has no peak and no TPU plane: the run is
    told it is a v5e and given the recorded trace under the decode
    chunk's name, as ``test_hybrid_ssm`` does)."""
    recorded = trace_reduce.reduce_trace(tiny.FIXTURE_TRACE)
    as_chunks = dict(recorded, module_events=[
        (s, e, "jit_chunk") for s, e, _ in recorded["module_events"]])
    chunk_s = trace_reduce.whole_events(as_chunks, "chunk")
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: d)
    monkeypatch.setattr(trace_reduce, "reduce_trace", lambda path: as_chunks)
    monkeypatch.setattr(run, "device_info", lambda chips, require_chip: {
        "platform": "cpu", "kind": "TPU v5 lite", "count": 1})
    line = _run(afmoe_root, trace=True)
    assert line["correct"] is True, line["checked"]
    got = {k: m["value"] for k, m in line["metrics"].items()}
    assert {"serve_mfu.tput", "decode_roofline.tput",
            "experts_read_share.tput", "device_idle_share.tput",
            "compiles_in_window.tput"} <= set(got)
    cell = cell_mod.load_cell(CELL, afmoe_root)
    sz, c = cell.sizes, line["notes"]
    # Every decode step of a reply here is past the window of 16.
    assert c["decode_past_window_share"] == 100.0
    # One to four rows a step, two experts each of eight: some of the
    # experts, never more than all.
    assert 25.0 <= got["experts_read_share.tput"] <= 100.0
    peak = flops.peaks("TPU v5 lite")
    rows = c["decoded_rows"] / c["chunks_run"]
    cost = arch.decode_step_cost(sz, rows, c["mean_context_arrived"],
                                 None)
    assert cost["experts_read"] == pytest.approx(
        4 * 8 * (1 - (6 / 8) ** rows))
    t, _ = flops.least_seconds(cost["flops"], cost["bytes"], peak)
    # The reader counts the experts the window's records saw instead.
    assert got["decode_roofline.tput"] != pytest.approx(
        100.0 * len(chunk_s) * c["chunk_size"] * t / sum(chunk_s))
    assert got["decode_roofline.tput"] > 0


def _planted(published, kv_stats, iters, finished=()):
    cell, _ = published
    return {"cell": cell, "notes": {},
            "record": {"spans": iters, "window_s": 2.0,
                       "finished": list(finished),
                       "counters": {"kv_stats": kv_stats, "chunk_size": 32,
                                    "mean_context_arrived": 2300.0}}}


def _iter(steps, experts):
    return {"event": "sched_iter", "decode_steps": steps,
            "experts_read": experts, "decode_rows": 32}


def _reply(prompt, new):
    return {"item": {"prompt": [0] * prompt}, "new_tokens": [0] * new}


def test_experts_read_share_on_a_planted_record(published):
    read = run.load_metric_reader("experts_read_share.tput", tiny.BENCH)
    kv = {"moe_layers": 4, "n_experts": 128}
    # 64 steps that read 400 experts a step over the 4 layers, 32 that
    # read 512 (all of them), and an iteration that decoded nothing.
    iters = [_iter(64, 64 * 400), _iter(32, 32 * 512), _iter(0, 0),
             {"event": "span", "span": "request"}]
    # A 1,900-token prompt replying 301 tokens: 300 decode steps, the
    # last 152 past 2,048; a 2,500-token one: all 100 of its steps.
    run_ = _planted(published, kv, iters,
                    [_reply(1900, 301), _reply(2500, 101)])
    assert read(run_, None) == pytest.approx(
        100.0 * (64 * 400 + 32 * 512) / (96 * 512))
    assert run_["notes"]["decode_past_window_share"] == pytest.approx(
        100.0 * (152 + 100) / 400)


@pytest.mark.parametrize("kv_stats,iters", [
    ({}, [_iter(32, 32 * 400)]),
    ({"moe_layers": 0, "n_experts": 0}, [_iter(32, 0)]),
    ({"moe_layers": 4, "n_experts": 128}, [_iter(0, 0)]),
    ({"moe_layers": 4, "n_experts": 128},
     [{"event": "sched_iter", "decode_steps": 32, "decode_rows": 32}])],
    ids=["a_parents_stats", "no_expert_layer", "no_decode_step",
         "records_without_the_counter"])
def test_experts_read_share_is_silent_with_nothing_to_read(
        published, kv_stats, iters):
    read = run.load_metric_reader("experts_read_share.tput", tiny.BENCH)
    assert read(_planted(published, kv_stats, iters), None) is None


def test_a_model_without_experts_reads_nothing_and_counts_nothing():
    """The dense model's engine: no expert layer in its model info, and
    its chunk returns its tokens alone (the program it had)."""
    from serverless_learn_tpu.inference.continuous import (
        ContinuousBatchingEngine)
    from serverless_learn_tpu.models.registry import get_model

    module = get_model("llama_tiny", dtype=jnp.float32,
                       param_dtype=jnp.float32, max_seq_len=64).module
    params = module.init(jax.random.PRNGKey(0),
                         jnp.zeros((1, 8), jnp.int32))["params"]
    eng = ContinuousBatchingEngine(module, params, max_slots=2,
                                   chunk_size=4)
    try:
        r = eng.submit([1, 2, 3], 6, 0.0, 0, None, 0)
        assert len(r["new_tokens"]) == 6
        kv = eng.kv_stats()
        assert (kv["moe_layers"], kv["n_experts"]) == (0, 0)
        assert eng.experts_read_total == 0
        st = eng._init_state()
        out = eng._paged_chunk_jit(1, 4)(
            params, st["pages"], st["vecs"],
            jnp.full((1, 4), eng._pool.sentinel, jnp.int32),
            jnp.full((1,), 2, jnp.int32))
        assert isinstance(out[2], jax.Array) and out[2].shape == (1, 4)
    finally:
        eng.stop()


def test_the_configuration_file_and_the_manifest_agree():
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "trinity-mini-serve")
    cell = next(w for w in manifest["workloads"] if w["name"] == REAL_CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "serve-backlog-long-in"
    per = {m["name"]: m for m in manifest["per_layer"]}
    assert per["experts_read_share.tput"]["workloads"] == [REAL_CELL]
    assert per["experts_read_share.tput"]["layer"] == "experts"
    # Every serving reader the hybrid cell reports but its state's.
    granite = {m["name"] for m in manifest["per_layer"]
               if "granite4h-micro-serve-backlog" in m.get("workloads", [])}
    mine = {m["name"] for m in manifest["per_layer"]
            if REAL_CELL in m.get("workloads", [])}
    assert mine == granite - {"state_traffic_share.tput"} \
        | {"experts_read_share.tput"}
    assert entry["reduced"] == sorted(entry["reduced"])
