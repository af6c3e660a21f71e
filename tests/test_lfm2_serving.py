"""A model with gated short-convolution layers and experts behind the
continuous engine: ``lfm2_moe_tiny`` (conv, conv, attention, conv, conv,
conv; two dense layers, then four layers of 8 experts, top 2), whose
slots hold each conv layer's carried inputs beside the paged pool, and
whose decode chunk also counts the experts its rows read.

CPU, float32. The plain reference is the benchmark's
(``chipbench/arch/lfm2_moe.py``: the convolution over the whole sequence,
every expert on every token, nothing of the program imported), at this
test's sizes, on the benchmark's own seeded weights. Both sides compute
in float32; a bound of 1e-4 on logits is the rounding of two orders of
summation, and a served token is the reference's greedy choice when its
reference logit lies within that bound of the best.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import cell as cell_mod
from chipbench import weights
from serverless_learn_tpu.config import KVCacheConfig
from serverless_learn_tpu.inference import kvcache
from serverless_learn_tpu.inference.continuous import ContinuousBatchingEngine
from serverless_learn_tpu.inference.generate import generate, init_cache
from serverless_learn_tpu.inference.speculative import speculative_generate
from serverless_learn_tpu.models.registry import get_model
from serverless_learn_tpu.models.transformer import slot_leaves
from serverless_learn_tpu.telemetry import MetricsRegistry
from test_hybrid_serving import _by_hand

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "chipbench")
VOCAB = 256
TOL = 1e-4      # float32 against float32: two orders of summation


@pytest.fixture(scope="module")
def arch():
    return cell_mod.load_arch("lfm2_moe", BENCH)


@pytest.fixture(scope="module")
def tiny_config():
    """The published configuration's keys at ``lfm2_moe_tiny``'s sizes."""
    with open(os.path.join(BENCH, "configs",
                           "lfm2-8b-a1b-serve.json")) as f:
        published = json.load(f)
    return dict(
        published, hidden_size=64, intermediate_size=96,
        num_hidden_layers=6, layer_types=published["layer_types"][:6],
        num_attention_heads=4, num_key_value_heads=2, num_experts=8,
        num_experts_per_tok=2, moe_intermediate_size=32, vocab_size=VOCAB,
        max_position_embeddings=64)


@pytest.fixture(scope="module")
def lfm2(devices, arch, tiny_config):
    """(module, the program's tree of seeded weights, canonical weights,
    sizes): the registry's ``lfm2_moe_tiny`` and the benchmark's own
    weights, so that program and reference hold the same values."""
    sz = arch.sizes(tiny_config)
    module = get_model("lfm2_moe_tiny", dtype=jnp.float32,
                       param_dtype=jnp.float32, max_seq_len=64).module
    cfg = module.cfg
    for field, value in arch.model_overrides(tiny_config).items():
        assert getattr(cfg, field) == (tuple(value) if isinstance(
            value, list) else value), field
    w = weights.make_weights(arch, sz, weights.seed_u32(7), jnp.float32)
    return module, arch.to_program_tree(w), w, sz


def _engine(module, params, max_slots=2, **kw):
    kv = kw.pop("kv", None) or KVCacheConfig(block_size=4, prefill_chunk=4)
    return ContinuousBatchingEngine(
        module, params, max_slots=max_slots, chunk_size=4, kv=kv,
        registry=MetricsRegistry(), **kw)


def _prompts(n, lo=5, hi=19, seed=0):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(0, VOCAB, int(length))]
            for length in rng.integers(lo, hi, n)]


def _reference_logits(arch, w, sz, tokens):
    return np.asarray(arch.head(w, arch.trunk(
        w, jnp.asarray([tokens], jnp.int32), sz), sz)[0])


def _solo(module, params, prompt, n):
    toks = generate(module, params, jnp.asarray([prompt], jnp.int32), n)
    return [int(t) for t in jax.device_get(toks)[0][len(prompt):]]


def _greedy_gap(arch, w, sz, prompt, served):
    ref = _reference_logits(arch, w, sz, prompt + served)
    at = ref[len(prompt) - 1:-1]
    return at.max(-1) - at[np.arange(len(served)), served]


def test_chunked_prefill_then_decode_gives_the_references_logits(
        lfm2, arch):
    """The serving twin of the module: a prompt fed in three uneven
    pieces (right-padded, with their lengths) through the paged pool and
    the slot leaf, then token by token, against ONE full forward of the
    reference."""
    module, params, w, sz = lfm2
    tokens = _prompts(1, 30, 31)[0]
    ref = _reference_logits(arch, w, sz, tokens)
    paged = kvcache.paged_module(module, 4, 16)
    pages, ci = kvcache.split_cache(init_cache(paged, 1))
    tbl = jnp.asarray(kvcache.sequential_table(1, 16, 16))

    def feed(piece, **mode):
        nonlocal pages, ci
        logits, upd = paged.apply(
            {"params": params,
             "cache": kvcache.with_tables(pages, tbl, ci)},
            jnp.asarray(piece), mutable=["cache", "moe_route"], **mode)
        pages, ci = kvcache.split_cache(upd["cache"])
        return np.asarray(logits[0])

    got, lo = [], 0
    for hi in (7, 8, 21):
        piece = np.zeros((1, 16), np.int32)
        piece[0, :hi - lo] = tokens[lo:hi]
        got.append(feed(piece, extend=True,
                        seq_lengths=jnp.asarray([hi - lo]))[:hi - lo])
        lo = hi
    for t in range(21, 30):
        got.append(feed([[tokens[t]]], decode=True))
    np.testing.assert_allclose(np.concatenate(got), ref, rtol=TOL, atol=TOL)


def test_linked_rows_of_one_program_agree_with_one_long_row(lfm2):
    """Three rows of one prefill program hold consecutive chunks of one
    prompt (the middle one two tokens, exactly the inputs the convolution
    carries), each LINKED to the row before it: their logits are those of
    the whole prompt fed as one row."""
    module, params, _, _ = lfm2
    tokens = _prompts(1, 21, 22, seed=4)[0]
    cuts = [(0, 9), (9, 11), (11, 21)]
    paged = kvcache.paged_module(module, 4, 16)
    tbl1 = jnp.asarray(kvcache.sequential_table(1, 16, 16))

    def run(rows, starts, lens, tbl, link):
        pages, _ = kvcache.split_cache(init_cache(paged, len(rows)))
        width = max(len(r) for r in rows)
        toks = np.zeros((len(rows), width), np.int32)
        for i, r in enumerate(rows):
            toks[i, :len(r)] = r
        logits, _ = paged.apply(
            {"params": params, "cache": kvcache.with_tables(
                pages, tbl, jnp.asarray(starts, jnp.int32))},
            jnp.asarray(toks), extend=True, mutable=["cache", "moe_route"],
            seq_lengths=jnp.asarray(lens), link=link)
        return [np.asarray(logits[i, :n]) for i, n in enumerate(lens)]

    whole = run([tokens], [0], [21], tbl1, None)[0]
    parts = run([tokens[a:b] for a, b in cuts], [a for a, _ in cuts],
                [b - a for a, b in cuts], jnp.tile(tbl1, (3, 1)),
                jnp.asarray([False, True, True]))
    np.testing.assert_allclose(np.concatenate(parts), whole, rtol=TOL,
                               atol=TOL)
    # Unlinked, the later rows forget the convolution's inputs before
    # them: the first logits of each differ.
    unlinked = run([tokens[a:b] for a, b in cuts], [a for a, _ in cuts],
                   [b - a for a, b in cuts], jnp.tile(tbl1, (3, 1)),
                   jnp.asarray([False, False, False]))
    assert np.abs(unlinked[2][0] - whole[11]).max() > 100 * TOL


def test_the_engines_tokens_are_the_references_and_solo_generates(
        lfm2, arch):
    """Six requests over two slots, prompts of one to five prefill
    chunks: every served token is the reference's own greedy choice at
    its position, the reply is what solo ``generate`` gives, and the
    engine counted the experts of its decode steps and holds the conv
    layers' carried inputs per slot."""
    module, params, w, sz = lfm2
    eng = _engine(module, params)
    try:
        for prompt in _prompts(6):
            served = eng.submit(prompt, 9, 0.0, 0, None, 0)["new_tokens"]
            assert served == _solo(module, params, prompt, 9)
            assert _greedy_gap(arch, w, sz, prompt, served).max() <= TOL
        st = eng.kv_stats()
        assert (st["moe_layers"], st["n_experts"]) == (4, 8)
        assert eng.experts_read_total > 0
        # Five conv layers x 2 carried inputs x 64 channels, float32.
        assert st["state_bytes_per_slot"] == 5 * 2 * 64 * 4
        assert st["state_slots"] == 2 and eng._trie is None
    finally:
        eng.stop()


@pytest.mark.parametrize("chunk", [4, 2])
def test_slots_link_chunks_in_a_program_and_across_programs(lfm2, arch,
                                                            chunk):
    """Four slots: the first program holds two chunks or more of each of
    two prompts, and a prompt's chunks straddle it and the next. At a
    chunk of 2 a row passes on every input it holds to the convolution
    of the next. Every reply is solo ``generate``'s and the reference's
    greedy choice, and the decode chunks counted their experts."""
    module, params, w, sz = lfm2
    prompts = [p[:n] for p, n in zip(_prompts(3, 20, 21, seed=9),
                                     (2 * chunk, 3 * chunk - 1, 6))]
    eng, sent, _, records = _by_hand(
        module, params, [(p, 5) for p in prompts], max_slots=4,
        kv=KVCacheConfig(block_size=4, prefill_chunk=chunk))
    pre = [e for r in records for e in r["programs"]
           if e["prog"].startswith("jit_pre_")]
    assert [e["rows"] for e in pre[:2]] == [4, 1 + -(-6 // chunk)]
    assert sum(r["experts_read"] for r in records) \
        == eng.experts_read_total > 0
    for r, prompt in zip(sent, prompts):
        served = r.result["new_tokens"]
        assert served == _solo(module, params, prompt, 5)
        assert _greedy_gap(arch, w, sz, prompt, served).max() <= TOL


def test_a_slots_second_request_gets_what_a_fresh_engine_gives(lfm2):
    """One slot: the second request finds the first one's carried inputs
    in the slot's rows, and starts from zero all the same."""
    module, params, _, _ = lfm2
    first, second = _prompts(2, seed=3)
    eng = _engine(module, params, max_slots=1)
    try:
        eng.submit(first, 10, 0.0, 0, None, 0)
        reused = eng.submit(second, 10, 0.0, 0, None, 0)["new_tokens"]
        assert eng.state_resets_total == 2
    finally:
        eng.stop()
    fresh = _engine(module, params, max_slots=1)
    try:
        assert reused == fresh.submit(second, 10, 0.0, 0, None,
                                      0)["new_tokens"]
    finally:
        fresh.stop()
    assert reused == _solo(module, params, second, 10)


def test_a_chunk_shorter_than_the_convolutions_carry_is_refused(lfm2):
    module, params, _, _ = lfm2
    with pytest.raises(ValueError, match="2 convolution inputs"):
        _engine(module, params, kv=KVCacheConfig(block_size=4,
                                                 prefill_chunk=1))


def test_speculative_decoding_refuses_a_model_with_slot_state(lfm2):
    """A rejected draft rolls the cache index back; a convolution's
    carried inputs have consumed the rejected tokens and cannot follow."""
    module, params, _, _ = lfm2
    assert slot_leaves(module.cfg) == ("conv_state",)
    with pytest.raises(NotImplementedError, match="state per slot"):
        speculative_generate(module, params, module, params,
                             jnp.asarray([[1, 2, 3]], jnp.int32), 4, K=2)


# The parameter trees of the tiny families that have no conv layer, as
# the tree before the conv kind existed built them: leaves, parameters,
# and a digest of every leaf's path, shape and dtype.
TREES = {"llama_tiny": (21, 623_232, "9aa565d889945201"),
         "granite_hybrid_tiny": (50, 211_816, "7dff33a92d7b6113"),
         "afmoe_tiny": (107, 392_992, "10f792d2e552aab1"),
         "moe_tiny": (23, 1_017_472, "bfcc73fa292b4ada")}


@pytest.mark.parametrize("name", sorted(TREES))
def test_a_model_without_a_conv_layer_builds_the_tree_it_built(name):
    import hashlib
    import math

    module = get_model(name).module
    abstract = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    flat = jax.tree_util.tree_flatten_with_path(abstract)[0]
    lines = sorted(f"{jax.tree_util.keystr(p)}:{tuple(a.shape)}:"
                   f"{jnp.dtype(a.dtype).name}" for p, a in flat)
    digest = hashlib.sha256("\n".join(lines).encode()).hexdigest()[:16]
    assert (len(lines), sum(math.prod(a.shape) for _, a in flat),
            digest) == TREES[name]
    assert slot_leaves(module.cfg) == (
        ("ssm_state", "conv_state") if "mamba" in module.cfg.layer_types
        else ())


def test_the_grouped_product_tiles_the_cells_expert_shapes():
    """LFM2-8B-A1B's experts (hidden 2,048, width 1,792 = 14 x 128): a
    decode step of 64 rows has 256 (token, expert) rows, a prefill
    program of 64 rows of 32 tokens 8,192 (top 4). The kernel takes
    them: k whole, n the widest divisor of whole lanes that a 4 MiB
    block of the weights allows (896 of 1,792; 1,024 of 2,048 at k
    1,792)."""
    from serverless_learn_tpu.ops.pallas import moe_gmm

    for m in (256, 8192):
        assert moe_gmm.tiling(m, 2048, 1792) == (128, 2048, 896)
        assert moe_gmm.tiling(m, 1792, 2048) == (128, 1792, 1024)
    for k, n in ((2048, 1792), (1792, 2048)):
        _, tk, tn = moe_gmm.tiling(256, k, n)
        assert tk * tn * 2 <= moe_gmm.MAX_RHS_BLOCK_BYTES
        lhs = jax.ShapeDtypeStruct((256, k), jnp.bfloat16)
        rhs = jax.ShapeDtypeStruct((32, k, n), jnp.bfloat16)
        assert moe_gmm.untileable_reason(lhs, rhs) is None


@pytest.mark.parametrize("slots, cap", [(8, 64), (32, 64), (64, 128)])
def test_a_server_takes_two_connections_a_slot(slots, cap):
    """A closed loop of two clients a slot (the 64-slot cell's 128) is
    served: the connection cap is twice the engine's slots, at least 64,
    and the first connection past it is refused with a typed reply."""
    import socket

    from serverless_learn_tpu.fleet.testing import StubEngine, StubModule
    from serverless_learn_tpu.inference.server import GenerationServer

    srv = GenerationServer(StubModule(), params=None, engine=StubEngine(),
                           max_batch=slots, registry=MetricsRegistry())
    srv.start()
    conns = []
    try:
        assert srv.max_connections == cap
        host, port = srv.addr.rsplit(":", 1)
        for _ in range(cap + 1):
            conns.append(socket.create_connection((host, int(port)), 10))
        last = conns.pop().makefile("rwb")
        assert b"connection capacity" in last.readline()
        f = conns[-1].makefile("rwb")
        f.write(json.dumps({"prompt": [1, 2, 3],
                            "max_new_tokens": 2}).encode() + b"\n")
        f.flush()
        assert len(json.loads(f.readline())["new_tokens"]) == 2
    finally:
        for c in conns:
            c.close()
        srv.stop()
