"""The Llama-shaped dense decoder: RMSNorm, grouped-query attention with
rotary positions, SwiGLU, untied head; LoRA on the query and value
projections where the configuration has a ``lora`` section.

Everything the harness knows about this architecture is in this file
(``chipbench/README.md``, "An architecture", lists the names a module of
``arch/`` defines): its sizes and how the published ``config.json`` keys
give them, the program's ``model_overrides``, the leaves and their names
in the program's tree, the plain reference's blocks, the operations and
bytes a token or a step needs, and the engine programs a traffic mix can
reach, with their signatures.

The plain reference here follows the published description with no
kernel, no cache and no batching tricks, every matrix product through the
``mm`` it is given (``reference.f32_matmul``: float32 at ``highest``). It
imports nothing of the program. Two departures, both to match what the
program runs (listed under ``assumed`` in the configuration files): rotary
pairs are interleaved (x[2i], x[2i+1]) where the published code rotates
halves (the same function up to a fixed permutation of each head's
columns, which seeded random weights absorb), and the norm's epsilon is
the program's 1e-6.

Counts read no compiled cost analysis: XLA's own count includes
recomputation (an HFU), and the yardstick must not move when the program
is refactored. Counts are multiply-adds times two.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from chipbench.reference import F32, f32_matmul, rms_norm, rope


# ---- sizes, and the program's settings that follow from them -----------

@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab: int
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    rope_theta: float
    rms_eps: float
    lora_rank: int = 0
    lora_alpha: float = 16.0


def sizes(config: dict) -> Sizes:
    """``config`` is the configuration file: the published ``config.json``
    keys at its top level, ``lora`` if any."""
    lora = config.get("lora") or {}
    return Sizes(
        vocab=int(config["vocab_size"]),
        d_model=int(config["hidden_size"]),
        n_layers=int(config["num_hidden_layers"]),
        n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]),
        d_ff=int(config["intermediate_size"]),
        rope_theta=float(config["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]),
        lora_rank=int(lora.get("rank", 0)),
        lora_alpha=float(lora.get("alpha", 16.0)),
    )


def model_overrides(config: dict) -> dict:
    """The program's ``model_overrides`` (``TransformerConfig`` fields)
    from the file's published keys, so that sizes are stated once."""
    c = config
    ov = {
        "vocab_size": c["vocab_size"], "d_model": c["hidden_size"],
        "n_layers": c["num_hidden_layers"],
        "n_heads": c["num_attention_heads"],
        "n_kv_heads": c["num_key_value_heads"],
        "d_ff": c["intermediate_size"],
        "max_seq_len": c["max_position_embeddings"],
        "rope_theta": c["rope_theta"],
        "tie_embeddings": c["tie_word_embeddings"],
    }
    lora = c.get("lora")
    if lora:
        ov.update({"lora_rank": lora["rank"], "lora_alpha": lora["alpha"]})
    return ov


def at_depth(config: dict, depth: int) -> dict:
    """The configuration at another depth (``rehearse.py`` picks a depth
    by what compiles and fits)."""
    return dict(config, num_hidden_layers=depth)


# ---- the leaves, and their names in the program's tree -----------------

def _layer_leaves(sz: Sizes) -> dict:
    """Per-layer leaf -> (shape, standard deviation; None = a norm scale).
    Projections are N(0, 1/fan_in)."""
    d, H, K, D, F, r = (sz.d_model, sz.n_heads, sz.n_kv_heads, sz.head_dim,
                        sz.d_ff, sz.lora_rank)
    shapes = {
        "norm_attn": ((d,), None), "norm_mlp": ((d,), None),
        "wq": ((d, H, D), d ** -0.5), "wk": ((d, K, D), d ** -0.5),
        "wv": ((d, K, D), d ** -0.5), "wo": ((H, D, d), (H * D) ** -0.5),
        "w_gate": ((d, F), d ** -0.5), "w_up": ((d, F), d ** -0.5),
        "w_down": ((F, d), F ** -0.5),
    }
    if r > 0:
        # Both adapter factors are non-zero: the cell stands for a step in
        # the middle of a fine-tune, where every adapter leaf has a
        # gradient (a zero B, as at step 0, leaves A's gradient at nought).
        # B at 0.02 makes the adapter's term some 8% of the frozen one's.
        shapes.update({
            "q_a": ((d, r), d ** -0.5), "q_b": ((r, H, D), 0.02),
            "v_a": ((d, r), d ** -0.5), "v_b": ((r, K, D), 0.02),
        })
    return shapes


def leaf_shapes(sz: Sizes) -> dict:
    """{"top": {leaf: (shape, std)}, "layers": [{leaf: (shape, std)}]}:
    what ``weights.make_weights`` draws, in the order it folds its keys.
    The embedding is N(0, 1)."""
    return {
        "top": {"embed": ((sz.vocab, sz.d_model), 1.0),
                "head": ((sz.d_model, sz.vocab), sz.d_model ** -0.5),
                "norm_f": ((sz.d_model,), None)},
        "layers": [_layer_leaves(sz)] * sz.n_layers,
    }


ADAPTER_LEAVES = ("q_a", "q_b", "v_a", "v_b")


def to_program_tree(w: dict) -> dict:
    """Canonical weights -> the flax parameter tree of the program's
    ``Transformer`` (names are load-bearing there)."""
    out = {"embedder": {"embedding": w["embed"]},
           "lm_head": {"kernel": w["head"]},
           "norm_f": {"scale": w["norm_f"]}}
    for i, lw in enumerate(w["layers"]):
        attn = {"q_proj": {"kernel": lw["wq"]},
                "k_proj": {"kernel": lw["wk"]},
                "v_proj": {"kernel": lw["wv"]},
                "o_proj": {"kernel": lw["wo"]}}
        if "q_a" in lw:
            attn["q_lora"] = {"lora_a": {"kernel": lw["q_a"]},
                              "lora_b": {"kernel": lw["q_b"]}}
            attn["v_lora"] = {"lora_a": {"kernel": lw["v_a"]},
                              "lora_b": {"kernel": lw["v_b"]}}
        out[f"layer_{i}"] = {
            "attn": attn,
            "mlp": {"gate_proj": {"kernel": lw["w_gate"]},
                    "up_proj": {"kernel": lw["w_up"]},
                    "down_proj": {"kernel": lw["w_down"]}},
            "norm_attn": {"scale": lw["norm_attn"]},
            "norm_mlp": {"scale": lw["norm_mlp"]},
        }
    return out


def trained_of_program_tree(params: dict, sz: Sizes) -> list:
    """The trained (adapter) leaves of a program parameter (or
    same-shaped moment) tree, back in canonical names: one dict per
    layer."""
    out = []
    for i in range(sz.n_layers):
        attn = params[f"layer_{i}"]["attn"]
        out.append({
            "q_a": attn["q_lora"]["lora_a"]["kernel"],
            "q_b": attn["q_lora"]["lora_b"]["kernel"],
            "v_a": attn["v_lora"]["lora_a"]["kernel"],
            "v_b": attn["v_lora"]["lora_b"]["kernel"],
        })
    return out


def split_trained(w: dict):
    """(frozen weights, adapters) with adapters as float32 leaves."""
    frozen = dict(w, layers=[{k: v for k, v in lw.items()
                              if k not in ADAPTER_LEAVES}
                             for lw in w["layers"]])
    adapters = [{k: lw[k].astype(F32) for k in ADAPTER_LEAVES}
                for lw in w["layers"]]
    return frozen, adapters


def merge_trained(frozen, adapters):
    return dict(frozen, layers=[{**lw, **ad} for lw, ad in
                                zip(frozen["layers"], adapters)])


# ---- the plain reference's blocks --------------------------------------

def _attention_one(q, k, v, mm):
    """q [B, T, G, D] (the G query heads of one KV head), k, v [B, T, D]."""
    T, D = q.shape[1], q.shape[3]
    s = mm(q, k, "btgd,bsd->bgts") * (D ** -0.5)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return mm(p, v, "bgts,bsd->btgd")


def layer(x, lw, sz: Sizes, mm):
    """One decoder block: x [B, T, d] float32."""
    B, T = x.shape[:2]
    H, K, D = sz.n_heads, sz.n_kv_heads, sz.head_dim
    h = rms_norm(x, lw["norm_attn"], sz.rms_eps)
    q = mm(h, lw["wq"], "btd,dhk->bthk")
    k = mm(h, lw["wk"], "btd,dhk->bthk")
    v = mm(h, lw["wv"], "btd,dhk->bthk")
    if sz.lora_rank > 0:
        s = sz.lora_alpha / sz.lora_rank
        q = q + s * mm(mm(h, lw["q_a"], "btd,dr->btr"), lw["q_b"],
                       "btr,rhk->bthk")
        v = v + s * mm(mm(h, lw["v_a"], "btd,dr->btr"), lw["v_b"],
                       "btr,rhk->bthk")
    q, k = rope(q, sz.rope_theta), rope(k, sz.rope_theta)
    G = H // K
    qg = q.reshape(B, T, K, G, D).transpose(2, 0, 1, 3, 4)  # [K,B,T,G,D]
    kg, vg = k.transpose(2, 0, 1, 3), v.transpose(2, 0, 1, 3)  # [K,B,T,D]
    # One KV head at a time: the [G, T, T] scores of all heads at once
    # would not fit beside the weights at T = 4096. (The loop closes over
    # no weight: XLA hoists what a loop does not change, and every
    # layer's float32 copy at once does not fit.)
    # Checkpointed: a backward pass recomputes one head's scores
    # instead of keeping every head's.
    one_head = jax.checkpoint(lambda a: _attention_one(a[0], a[1], a[2], mm))
    og = lax.map(one_head, (qg, kg, vg))                    # [K,B,T,G,D]
    o = og.transpose(1, 2, 0, 3, 4).reshape(B, T, H, D)
    x = x + mm(o, lw["wo"], "bthk,hkd->btd")
    h = rms_norm(x, lw["norm_mlp"], sz.rms_eps)
    gate = jax.nn.silu(mm(h, lw["w_gate"], "btd,df->btf"))
    up = mm(h, lw["w_up"], "btd,df->btf")
    return x + mm(gate * up, lw["w_down"], "btf,fd->btd")


def trunk(w: dict, tokens, sz: Sizes, mm=f32_matmul, remat=False):
    """tokens [B, T] -> the last block's output [B, T, d] float32."""
    one_layer = partial(layer, sz=sz, mm=mm)
    if remat:
        one_layer = jax.checkpoint(one_layer)
    x = w["embed"][tokens].astype(F32)
    for lw in w["layers"]:
        x = one_layer(x, lw)
    return x


def head(w: dict, x, sz: Sizes, mm=f32_matmul):
    """x [..., T, d] -> logits [..., T, V] float32."""
    return mm(rms_norm(x, w["norm_f"], sz.rms_eps), w["head"],
              "...td,dv->...tv")


# ---- operations and bytes from shapes ----------------------------------

def layer_matmul_params(sz: Sizes) -> int:
    """Weights of one block that a token multiplies (norm scales apart)."""
    d, H, K, D, F = sz.d_model, sz.n_heads, sz.n_kv_heads, sz.head_dim, sz.d_ff
    return d * H * D + 2 * d * K * D + H * D * d + 3 * d * F


def layer_adapter_params(sz: Sizes) -> int:
    d, H, K, D, r = (sz.d_model, sz.n_heads, sz.n_kv_heads, sz.head_dim,
                     sz.lora_rank)
    return (d * r + r * H * D) + (d * r + r * K * D) if r else 0


def head_params(sz: Sizes) -> int:
    return sz.d_model * sz.vocab


def weight_bytes(sz: Sizes, bytes_per_param: int = 2) -> int:
    """Bytes a decode step has to stream: every block and the head (the
    embedding contributes one row per token)."""
    return bytes_per_param * (sz.n_layers * layer_matmul_params(sz)
                              + head_params(sz))


def attention_flops(sz: Sizes, n_query: int, n_keys: float) -> float:
    """QK^T and PV of one layer: ``n_query`` queries, each over ``n_keys``
    keys (the mean number it may see, T/2 under a causal mask)."""
    return 2 * 2 * sz.n_heads * sz.head_dim * n_query * n_keys


def forward_flops_per_token(sz: Sizes, mean_keys: float) -> float:
    """One token through every block and the head, seeing ``mean_keys``
    keys in each attention layer."""
    mm = sz.n_layers * (layer_matmul_params(sz) + layer_adapter_params(sz))
    return (2 * (mm + head_params(sz))
            + sz.n_layers * attention_flops(sz, 1, mean_keys))


def train_flops_per_token(sz: Sizes, seq_len: int) -> float:
    """What one LoRA step *requires* per token: the forward pass, the
    backward pass for activations through every frozen product (as much
    again), attention's backward (four products for the forward's two),
    and both gradients of the adapters. No gradient of a frozen weight and
    no recomputation: a step that recomputes does more than this, and its
    utilisation by this count is the lower for it."""
    frozen = 2 * (sz.n_layers * layer_matmul_params(sz) + head_params(sz))
    adapters = 2 * sz.n_layers * layer_adapter_params(sz)
    attn = sz.n_layers * attention_flops(sz, 1, seq_len / 2)
    return 2 * frozen + 3 * adapters + 3 * attn


def flash_attention_cost(sz: Sizes, batch: int, seq_len: int,
                         bytes_per_el: int = 2) -> dict:
    """Causal flash attention over ``batch`` sequences, ONE layer, forward
    and backward together. FLOPs: the forward's two products over the
    causal half, the backward's five (it recomputes the scores) over the
    same. Bytes: the least traffic, each operand once: forward reads
    q, k, v and writes o; backward reads q, k, v, o, do and writes
    dq, dk, dv."""
    H, K, D = sz.n_heads, sz.n_kv_heads, sz.head_dim
    causal = batch * seq_len * (seq_len / 2)
    per_product = 2 * H * D * causal
    q_el = batch * seq_len * H * D
    kv_el = batch * seq_len * K * D
    fwd_bytes = bytes_per_el * (2 * q_el + 2 * kv_el)
    bwd_bytes = bytes_per_el * (4 * q_el + 4 * kv_el)
    return {"fwd_flops": 2 * per_product, "bwd_flops": 5 * per_product,
            "fwd_bytes": fwd_bytes, "bwd_bytes": bwd_bytes}


def kv_bytes_per_token(sz: Sizes, bytes_per_el: int = 2) -> int:
    return 2 * sz.n_layers * sz.n_kv_heads * sz.head_dim * bytes_per_el


def decode_step_cost(sz: Sizes, rows: float, mean_context: float,
                     record: dict = None) -> dict:
    """One decode step over ``rows`` live sequences: every weight once,
    each row's keys and values once. ``record`` (the run's record) is not
    needed: a dense step reads the same whatever the rows hold."""
    flops = rows * forward_flops_per_token(sz, mean_context)
    nbytes = weight_bytes(sz) + rows * mean_context * kv_bytes_per_token(sz)
    return {"flops": flops, "bytes": nbytes}


# ---- the engine's programs: which a mix reaches, and their signatures --
# The paged pool of keys and values is this architecture's one kind of
# state in the engine; a second kind changes these signatures.

def reachable_shapes(engine, mix_params: dict) -> tuple:
    """The (nb, T, W) prefill and (nb, W) decode buckets that requests of
    this mix can reach, by the engine's own bucket functions."""
    from serverless_learn_tpu.inference.batching import _bucket
    from serverless_learn_tpu.inference.continuous import _wbucket
    from serverless_learn_tpu.inference.kvcache import pages_for

    ps, chunk = engine._ps, engine.prefill_chunk
    p, o = mix_params["prompt_tokens"], mix_params["output_tokens"]
    nbs = sorted({_bucket(n, floor=1)
                  for n in range(1, engine.max_slots + 1)})
    t_cap = _bucket(chunk, floor=1)
    pre_t = sorted({min(_bucket(t, floor=8), t_cap)
                    for t in range(1, min(chunk, p["max"]) + 1)})
    first = pages_for(min(chunk, p["min"]), ps)
    pre_w = sorted({min(_wbucket(n), engine._max_pages)
                    for n in range(first, pages_for(p["max"], ps) + 1)})
    lo = pages_for(p["min"] + min(engine.chunk_size, o["min"]), ps)
    hi = pages_for(p["max"] + o["max"], ps)
    dec_w = sorted({min(_wbucket(n), engine._max_pages)
                    for n in range(lo, hi + 1)})
    return ([(nb, T, W) for nb in nbs for T in pre_t for W in pre_w],
            [(nb, W) for nb in nbs for W in dec_w])


def warm(engine, mix_params: dict) -> int:
    """Run every reachable program once, on the engine's OWN pool: all
    table entries and slot ids are sentinels, so every write drops. The
    engine's ``warm_shapes`` does the same on a second, throwaway pool,
    which at this size does not fit beside the first (PERF.md)."""
    sent, M = engine._pool.sentinel, engine.max_slots
    prefill, decode = reachable_shapes(engine, mix_params)
    st = engine._state
    for nb, W in decode:
        pad = jnp.full((nb,), M, jnp.int32)
        st["pages"], st["vecs"], toks = engine._paged_chunk_jit(nb, W)(
            engine.params, st["pages"], st["vecs"],
            jnp.full((nb, W), sent, jnp.int32), pad)
    for nb, T, W in prefill:
        pad = jnp.full((nb,), M, jnp.int32)
        z = lambda dt: jnp.zeros((nb,), dt)
        st["pages"], st["vecs"], toks = engine._paged_prefill_jit(nb, T, W)(
            engine.params, st["pages"], st["vecs"],
            jnp.full((nb, W), sent, jnp.int32), z(jnp.int32),
            jnp.zeros((nb, T), jnp.int32), z(jnp.int32), pad,
            z(jnp.bool_), z(jnp.float32), z(jnp.int32),
            jnp.full((nb,), -1, jnp.int32), z(jnp.uint32),
            jnp.full((nb,), sent, jnp.int32),
            jnp.full((nb,), sent, jnp.int32))
    jax.block_until_ready(toks)
    return len(prefill) + len(decode)


def lower_largest(engine, params, mix_params: dict, sharding) -> list:
    """[(name, lowered program)]: the decode chunk and the prefill chunk
    at the largest buckets the mix reaches, lowered on shapes alone for
    ``rehearse.py`` (``engine`` has no device state; ``params`` are
    shapes)."""
    from serverless_learn_tpu.inference import kvcache
    from serverless_learn_tpu.inference.generate import init_cache

    M = engine.max_slots
    state = jax.eval_shape(lambda: {
        "pages": kvcache.split_cache(init_cache(engine._pmod, M))[0],
        "vecs": {"next_tok": jnp.zeros((M,), jnp.int32),
                 "pos": jnp.zeros((M,), jnp.int32),
                 "done": jnp.ones((M,), jnp.bool_),
                 "temp": jnp.zeros((M,), jnp.float32),
                 "topk": jnp.zeros((M,), jnp.int32),
                 "eos": jnp.zeros((M,), jnp.int32),
                 "seed": jnp.zeros((M,), jnp.uint32),
                 "ci": jnp.zeros((M,), jnp.int32)}})
    state = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        state)
    prefill, decode = reachable_shapes(engine, mix_params)
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    nb, W = decode[-1]
    out = [(f"decode chunk nb={nb} W={W}", engine._paged_chunk_jit(
        nb, W).lower(params, state["pages"], state["vecs"],
                     s((nb, W), jnp.int32), s((nb,), jnp.int32)))]
    nb, T, W = prefill[-1]
    i32 = lambda: s((nb,), jnp.int32)
    out.append((f"prefill chunk nb={nb} T={T} W={W}",
                engine._paged_prefill_jit(nb, T, W).lower(
        params, state["pages"], state["vecs"], s((nb, W), jnp.int32), i32(),
        s((nb, T), jnp.int32), i32(), i32(), s((nb,), jnp.bool_),
        s((nb,), jnp.float32), i32(), i32(), s((nb,), jnp.uint32), i32(),
        i32())))
    return out
