"""A later PR adds an ARCHITECTURE as files too: ``arch/<name>.py``, a
configuration that names it, a traffic file and manifest entries; nothing
of ``chipbench/`` is edited. Dropped in here: ``fixtures/moe_topk.py``,
the registry's ``moe_tiny`` family (4 experts, top-2, no token dropped),
with its own leaves, its own plain reference and its own counts. Its
cells run end to end on the CPU, the readers that every model's cells
share (``serve_mfu``, ``decode_roofline``, ``train_mfu``) read through its
counts, and an expert left out of the program's tree is not correct."""

import json
import os

import pytest

import tiny
from chipbench import cell as cell_mod
from chipbench import flops, run, trace_reduce

MOE = tiny.MOE
# The interface of ``chipbench/README.md``, "An architecture": what every
# architecture defines, and what one with a ``train`` cell defines too.
EVERY_ARCH = ("sizes", "model_overrides", "leaf_shapes", "to_program_tree",
              "trunk", "head", "forward_flops_per_token", "decode_step_cost",
              "reachable_shapes", "warm")
TRAINED_ARCH = tiny.TRAINED_NAMES


@pytest.fixture(scope="module")
def moe_root(tmp_path_factory):
    """The tiny tree, grown by files and manifest entries alone."""
    return tiny.grow_moe(tiny.write_tree(
        str(tmp_path_factory.mktemp("moe_bench"))))


@pytest.fixture(scope="module")
def serving_only_root(tmp_path_factory):
    """The same with no training cell, and the architecture's file
    without its four training names."""
    return tiny.grow_moe(tiny.write_tree(
        str(tmp_path_factory.mktemp("moe_serving_only"))),
        serving_only=True)


@pytest.fixture(autouse=True)
def _no_cache(no_compile_cache):
    pass


def _run(root, name, trace=False, seed=2 ** 31 + 5):
    return run.run_cell(name, seed=seed, seconds=0.3, trace=trace,
                        root=root, require_chip=False)


def _archs(root):
    bench = os.path.join(root, "bench")
    return (cell_mod.load_arch("moe_topk", bench),
            cell_mod.load_arch("dense_gqa", bench))


def test_the_dropped_in_serving_cell_is_correct(moe_root):
    line = _run(moe_root, "tiny-moe-backlog")
    assert line["correct"] is True, line["checked"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["notes"]["tokens_compared"] >= 40, line["notes"]
    assert line["notes"]["compiles_in_window"] == 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}


def test_the_shared_readers_read_through_the_dropped_in_counts(
        moe_root, monkeypatch):
    """``serve_mfu.tput`` and ``decode_roofline.tput`` have one reader
    each for every model; what they divide is the cell's architecture's
    count. (The CPU has no published peak and its trace no TPU plane: the
    run is told it is a v5e and given the recorded trace, its one program
    under the decode chunk's name. The values mean nothing and go
    nowhere; what they were computed FROM is what is asserted.)"""
    recorded = trace_reduce.reduce_trace(tiny.FIXTURE_TRACE)
    as_chunks = dict(recorded, module_events=[
        (s, e, "jit_chunk") for s, e, _ in recorded["module_events"]])
    chunk_s = trace_reduce.whole_events(as_chunks, "chunk")
    assert len(chunk_s) == 2
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: d)
    monkeypatch.setattr(trace_reduce, "reduce_trace", lambda path: as_chunks)
    monkeypatch.setattr(run, "device_info", lambda chips, require_chip: {
        "platform": "cpu", "kind": "TPU v5 lite", "count": 1})
    line = _run(moe_root, "tiny-moe-backlog", trace=True)
    assert line["correct"] is True, line["checked"]
    assert set(line["metrics"]) == {
        "serve_mfu.tput", "decode_roofline.tput", "useful_decode_share.tput",
        "device_idle_share.tput"}
    assert line["notes"]["decode_chunks_whole"] == 2
    moe, dense = _archs(moe_root)
    sz = moe.sizes(MOE)
    c, peak = line["notes"], flops.peaks("TPU v5 lite")
    tokens = c["prompt_tokens_arrived"] + c["output_tokens_arrived"]
    ctx = c["mean_context_arrived"]

    def mfu(arch, sizes):
        return (100.0 * tokens * arch.forward_flops_per_token(sizes, ctx / 2)
                / (line["window_s"] * peak["flops_per_s"]))

    def roofline(arch, sizes):
        cost = arch.decode_step_cost(sizes, c["decoded_rows"]
                                     / c["chunks_run"], ctx, None)
        t, _ = flops.least_seconds(cost["flops"], cost["bytes"], peak)
        return 100.0 * len(chunk_s) * c["chunk_size"] * t / sum(chunk_s)

    got = {k: m["value"] for k, m in line["metrics"].items()}
    assert got["serve_mfu.tput"] == pytest.approx(mfu(moe, sz))
    assert got["decode_roofline.tput"] == pytest.approx(roofline(moe, sz))
    # A dense decoder of the same widths multiplies ONE such MLP per
    # layer where a token here uses two experts and a step touches up to
    # four: its counts give other numbers for the same window.
    as_dense = dense.sizes(MOE)
    assert mfu(dense, as_dense) < 0.8 * got["serve_mfu.tput"]
    assert roofline(dense, as_dense) < 0.8 * got["decode_roofline.tput"]


def test_the_dropped_in_counts_by_hand(moe_root):
    moe, _ = _archs(moe_root)
    sz = moe.sizes(MOE)
    attn = 64 * 64 + 2 * 64 * 32 + 64 * 64
    expert, router, head = 3 * 64 * 128, 64 * 4, 64 * 256
    # A token uses 2 of the 4 experts; it sees 10 keys in each layer.
    assert moe.forward_flops_per_token(sz, 10) == (
        2 * (2 * (attn + router + 2 * expert) + head)
        + 2 * (2 * 2 * 4 * 16 * 10))
    # One row touches 2 experts, many rows all 4: never more.
    assert moe.experts_touched(sz, 1) == pytest.approx(2.0)
    assert 3.9 < moe.experts_touched(sz, 8) < 4.0
    one = moe.decode_step_cost(sz, 1, 100)
    assert one["bytes"] == pytest.approx(
        2 * (2 * (attn + router + 2 * expert) + head)
        + 100 * (2 * 2 * 2 * 16 * 2))
    assert moe.train_flops_per_token(sz, 32) == \
        3 * moe.forward_flops_per_token(sz, 16)


def test_an_expert_left_out_of_the_programs_tree_is_not_correct(
        moe_root, monkeypatch):
    moe, _ = _archs(moe_root)
    real = moe.to_program_tree

    def without_the_last_expert(w):
        tree = real(w)
        for name, layer in tree.items():
            if name.startswith("layer_"):
                down = layer["moe"]["expert_down"]
                layer["moe"]["expert_down"] = down.at[-1].set(0.0)
        return tree

    monkeypatch.setattr(moe, "to_program_tree", without_the_last_expert)
    line = _run(moe_root, "tiny-moe-backlog")
    assert line["correct"] is False
    n = line["checked"]["served_logit_gap"]
    assert n["value"] > 10 * n["limit"]


def test_the_dropped_in_training_cell_is_correct(moe_root):
    """Every leaf is trained (no adapters, no mask): the same driver, the
    reference following the same three steps through this architecture's
    blocks. The load-balance loss is weighted by nought."""
    line = _run(moe_root, "tiny-moe-train")
    assert line["correct"] is True, line["checked"]
    assert set(line["checked"]) == {"loss1_gap", "loss2_gap", "loss3_gap",
                                    "grad_norm_gap", "change_norm_gap"}
    # Two layers' ten leaves and the three top leaves were compared.
    assert line["notes"]["grad_leaf"] is not None
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_a_configuration_names_its_architecture(moe_root):
    cell = cell_mod.load_cell("tiny-moe-backlog", moe_root)
    assert cell.arch.__file__.endswith(os.path.join("arch", "moe_topk.py"))
    assert cell.sizes.n_experts == 4 and cell.sizes.vocab == 256
    assert cell.program_config()["model_overrides"]["n_experts"] == 4
    cell.config.pop("arch")
    with pytest.raises(SystemExit, match="names no"):
        cell.arch
    cell.config["arch"] = "not_there"
    with pytest.raises(SystemExit, match="no file"):
        cell.sizes


def _architectures_of_train_cells(root) -> set:
    """The ``arch`` of every configuration that a ``train`` cell names."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        names = [w["name"] for w in json.load(f)["workloads"]]
    cells = [cell_mod.load_cell(name, root) for name in names]
    return {c.config["arch"] for c in cells if c.kind == "train"}


def _missing(arch, has_train_cell: bool) -> list:
    """``EVERY_ARCH`` is asked of every file of ``arch/``, ``TRAINED_ARCH``
    only of one that a configuration of a ``train`` cell names."""
    asked = EVERY_ARCH + (TRAINED_ARCH if has_train_cell else ())
    return [n for n in asked if not hasattr(arch, n)]


@pytest.mark.parametrize("name", sorted(
    f[:-3] for f in os.listdir(os.path.join(tiny.BENCH, "arch"))
    if f.endswith(".py")))
def test_every_architecture_of_the_benchmark_defines_the_interface(name):
    trained = _architectures_of_train_cells(tiny.ROOT)
    assert "dense_gqa" in trained      # the LoRA cell: its names are asked
    assert not _missing(cell_mod.load_arch(name, tiny.BENCH),
                        name in trained)


def test_the_dropped_in_architecture_defines_the_interface(moe_root):
    assert "moe_topk" in _architectures_of_train_cells(moe_root)
    moe, _ = _archs(moe_root)
    assert not _missing(moe, True)


def test_a_serving_only_architecture_needs_no_training_names(
        serving_only_root):
    """The fixture with its four training names cut out and no ``train``
    cell: the interface asks nothing more of it, and it serves its cell.
    Were a ``train`` cell to name it, exactly those four would be
    missing."""
    moe, _ = _archs(serving_only_root)
    assert "moe_topk" not in _architectures_of_train_cells(serving_only_root)
    assert not _missing(moe, False)
    assert sorted(_missing(moe, True)) == sorted(TRAINED_ARCH)
    line = _run(serving_only_root, "tiny-moe-backlog")
    assert line["correct"] is True, line["checked"]
    assert line["failed"] == 0 and line["notes"]["tokens_compared"] >= 40
