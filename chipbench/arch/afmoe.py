"""The AFMoE decoder of arcee-ai/Trinity-Mini (``model_type`` afmoe):
attention layers of two kinds (``layer_types``: ``sliding_attention``
sees the last ``sliding_window`` keys and rotates, ``full_attention``
sees every earlier key with no positional term), each with RMSNorm of q
and k over head_dim and a sigmoid gate on its output; the first
``num_dense_layers`` layers a SwiGLU MLP, the rest ``num_experts``
routed SwiGLU experts (top ``num_experts_per_tok`` by sigmoid score
plus a selection bias, the chosen scores renormalised and times
``route_scale``) beside ``num_shared_experts`` shared ones; an RMSNorm
before and after each sublayer; the embedding times sqrt(hidden_size)
(``mup_enabled``); an untied head.

Everything the harness knows about this architecture is in this file
(``chipbench/README.md``, "An architecture"). A serving architecture: no
``train`` cell names it, so it defines none of the four training names.

The plain reference follows the equations of the afmoe modelling code as
remembered (no network here; the configuration file lists them under
``assumed.equations``), float32, every product through the ``mm`` it is
given. It imports nothing of the program. Its experts are computed
DENSELY: every expert for every token, weighted by a gate that is zero
off the token's top k, so it drops no token by construction and shares
nothing with the program's sort and grouped product. A window is a band
mask over the whole sequence. Blocks keep it on one chip beside the
weights: attention one KV head at a time, the experts sixteen at a
time. Departures from the source, all to the same function: the
source's fused projections are separate leaves here, and rotary pairs
are interleaved (x[2i], x[2i+1]) where the source rotates halves (a
fixed permutation of each head's columns, which seeded weights absorb).

Counts read no compiled cost analysis and are multiply-adds times two.
A decode step reads the experts its rows were routed to, and the
engine's ``sched_iter`` records count them (``experts_read``);
``decode_step_cost`` reads that count from the run's record. The
engine's programs and their signatures are the hybrid decoder's
(``hybrid_ssm.py``: one paged pool, sentinel pages and slots, the
buckets the mix's own lengths form); this model keeps no slot state, so
its warm-up and lowering are that file's.
"""

from __future__ import annotations

import dataclasses
import math
import os
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from chipbench.reference import F32, f32_matmul, rms_norm, rope

# The program's name of each published attention kind.
KIND_OF = {"sliding_attention": "window_rope", "full_attention": "full_nope"}
EXPERT_BLOCK = 16      # experts the reference computes at a time
# A routed expert's matrices are the layer's own plus EXPERT_SPREAD times
# the expert's own (both seeded; ``expert_matrix``). With every expert
# drawn alone, the bfloat16 program and the float32 reference choose
# another 8th expert for some tokens of every request (the scores near a
# token's 8th choice lie closer than bfloat16 rounds them), each such
# choice moves the logits by a fifth to a half of their spread, and the
# served tokens' gap read the fp8 control's (PERF.md section 6).
# Alike experts make a choice between neighbours cost what rounding
# costs; the choice itself, the gates, the shared expert and the window
# still move the logits as far as they did.
EXPERT_SPREAD = 0.2


# ---- sizes, and the program's settings that follow from them -----------

@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab: int
    d_model: int
    n_layers: int
    layer_types: tuple        # "sliding_attention" | "full_attention"
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int                 # the dense layers' MLP
    n_dense: int
    n_experts: int
    top_k: int
    expert_d_ff: int
    shared_d_ff: int          # all shared experts together
    window: int
    rope_theta: float
    rms_eps: float
    route_scale: float
    route_norm: bool
    embedding_multiplier: float

    @property
    def n_moe(self) -> int:
        return self.n_layers - self.n_dense

    def windowed(self, i: int) -> bool:
        return self.layer_types[i] == "sliding_attention"


def sizes(config: dict) -> Sizes:
    """``config`` is the configuration file: the published ``config.json``
    keys at its top level; its ``layer_types`` has one entry a layer."""
    c = config
    n = int(c["num_hidden_layers"])
    if c["score_func"] != "sigmoid" or c["n_group"] != 1 \
            or c["topk_group"] != 1:
        raise ValueError("this architecture routes by sigmoid score with "
                         "no group limit")
    if len(c["layer_types"]) < n:
        raise ValueError("layer_types names fewer layers than "
                         "num_hidden_layers")
    d = int(c["hidden_size"])
    return Sizes(
        vocab=int(c["vocab_size"]), d_model=d, n_layers=n,
        layer_types=tuple(c["layer_types"][:n]),
        n_heads=int(c["num_attention_heads"]),
        n_kv_heads=int(c["num_key_value_heads"]),
        head_dim=int(c["head_dim"]), d_ff=int(c["intermediate_size"]),
        n_dense=min(int(c["num_dense_layers"]), n),
        n_experts=int(c["num_experts"]),
        top_k=int(c["num_experts_per_tok"]),
        expert_d_ff=int(c["moe_intermediate_size"]),
        shared_d_ff=int(c["num_shared_experts"])
        * int(c["moe_intermediate_size"]),
        window=int(c["sliding_window"]), rope_theta=float(c["rope_theta"]),
        rms_eps=float(c["rms_norm_eps"]),
        route_scale=float(c["route_scale"]),
        route_norm=bool(c["route_norm"]),
        embedding_multiplier=math.sqrt(d) if c["mup_enabled"] else 1.0)


def model_overrides(config: dict) -> dict:
    """The program's ``model_overrides`` (``TransformerConfig`` fields)
    from the file's published keys, so that sizes are stated once. The
    family's other terms (q/k norms, the output gate, the norms after
    each sublayer, sigmoid routing with a selection bias) are the
    registry model's own."""
    sz = sizes(config)
    return {
        "vocab_size": sz.vocab, "d_model": sz.d_model,
        "n_layers": sz.n_layers,
        "layer_types": [KIND_OF[k] for k in sz.layer_types],
        "sliding_window": sz.window,
        "n_heads": sz.n_heads, "n_kv_heads": sz.n_kv_heads,
        "head_dim_override": sz.head_dim, "d_ff": sz.d_ff,
        "max_seq_len": config["max_position_embeddings"],
        "rope_theta": sz.rope_theta, "rms_norm_eps": sz.rms_eps,
        "tie_embeddings": config["tie_word_embeddings"],
        "n_experts": sz.n_experts, "moe_top_k": sz.top_k,
        "moe_d_ff": sz.expert_d_ff, "moe_shared_d_ff": sz.shared_d_ff,
        "moe_dense_layers": sz.n_dense,
        "moe_route_scale": sz.route_scale, "moe_route_norm": sz.route_norm,
        "embedding_multiplier": sz.embedding_multiplier,
    }


def at_depth(config: dict, depth: int) -> dict:
    """The configuration at another depth: the first ``depth`` layers of
    the published pattern."""
    kinds = config.get("published", {}).get("layer_types") \
        or config["layer_types"]
    return dict(config, num_hidden_layers=depth,
                layer_types=list(kinds[:depth]))


# ---- the leaves, and their names in the program's tree -----------------

def bias_std(sz: Sizes) -> float:
    """The expert bias only steers which experts are chosen. Sigmoid
    scores of N(0, 1) router logits lie about 0.01 apart around the 8th
    of 128 (0.09 around the 2nd of 8): a bias of 1 / experts is about
    that spacing, so it changes some of a token's choices and not most,
    and a program that dropped it would serve other experts."""
    return 1.0 / sz.n_experts


def _layer_leaves(sz: Sizes, i: int) -> dict:
    """Per-layer leaf -> (shape, standard deviation; None = a norm
    scale). Projections are N(0, 1/fan_in); a routed expert's matrix is
    one drawn for the layer plus one of its own (``EXPERT_SPREAD``),
    N(0, 1/fan_in) together."""
    d, H, K, D = sz.d_model, sz.n_heads, sz.n_kv_heads, sz.head_dim
    out = {"norm_attn": ((d,), None),
           "wq": ((d, H, D), d ** -0.5), "wk": ((d, K, D), d ** -0.5),
           "wv": ((d, K, D), d ** -0.5), "wg": ((d, H, D), d ** -0.5),
           "q_norm": ((D,), None), "k_norm": ((D,), None),
           "wo": ((H, D, d), (H * D) ** -0.5),
           "norm_attn_out": ((d,), None), "norm_mlp": ((d,), None),
           "norm_mlp_out": ((d,), None)}
    if i < sz.n_dense:
        F = sz.d_ff
        out.update({"w_gate": ((d, F), d ** -0.5), "w_up": ((d, F), d ** -0.5),
                    "w_down": ((F, d), F ** -0.5)})
        return out
    E, F, S = sz.n_experts, sz.expert_d_ff, sz.shared_d_ff
    c = (1.0 + EXPERT_SPREAD ** 2) ** -0.5
    out.update({"router": ((d, E), d ** -0.5),
                "expert_bias": ((E,), bias_std(sz))})
    for name, shape, fan_in in (("e_gate", (d, F), d), ("e_up", (d, F), d),
                                ("e_down", (F, d), F)):
        out[name + "_base"] = (shape, c * fan_in ** -0.5)
        out[name] = ((E,) + shape, EXPERT_SPREAD * c * fan_in ** -0.5)
    out.update({"s_gate": ((d, S), d ** -0.5), "s_up": ((d, S), d ** -0.5),
                "s_down": ((S, d), S ** -0.5)})
    return out


@jax.jit
def expert_matrix(base, own):
    """The experts' matrices: the layer's ``base`` [..] plus each one's
    own [E, ..], added in float32 and rounded once to ``own``'s dtype.
    The program's tree and the reference take the same values from it."""
    return (base.astype(F32) + own.astype(F32)).astype(own.dtype)


def leaf_shapes(sz: Sizes) -> dict:
    """{"top": {leaf: (shape, std)}, "layers": [{leaf: (shape, std)}]}:
    what ``weights.make_weights`` draws. The embedding is N(0, 1/d), so
    that times sqrt(d) it enters the first block at about 1."""
    d = sz.d_model
    return {"top": {"embed": ((sz.vocab, d), d ** -0.5),
                    "head": ((d, sz.vocab), d ** -0.5),
                    "norm_f": ((d,), None)},
            "layers": [_layer_leaves(sz, i) for i in range(sz.n_layers)]}


def to_program_tree(w: dict) -> dict:
    """Canonical weights -> the flax parameter tree of the program's
    ``Transformer`` (names are load-bearing there).

    The experts' matrices are made here (``expert_matrix``), and ``w``
    keeps them in place of the parts they were made from: the parts are
    dropped as each matrix is made, so that the weights are held once
    (at 8.6 GB of weights, a second copy of the experts would not fit the
    chip), and ``w`` stays the same model for the reference."""
    for lw in w["layers"]:
        for name in ("e_gate", "e_up", "e_down"):
            if name + "_base" in lw:
                lw[name] = expert_matrix(lw.pop(name + "_base"), lw[name])
    out = {"embedder": {"embedding": w["embed"]},
           "lm_head": {"kernel": w["head"]},
           "norm_f": {"scale": w["norm_f"]}}
    mlp = lambda g, u, dn: {"gate_proj": {"kernel": g},
                            "up_proj": {"kernel": u},
                            "down_proj": {"kernel": dn}}
    for i, lw in enumerate(w["layers"]):
        layer = {
            "attn": {"q_proj": {"kernel": lw["wq"]},
                     "k_proj": {"kernel": lw["wk"]},
                     "v_proj": {"kernel": lw["wv"]},
                     "gate_proj": {"kernel": lw["wg"]},
                     "q_norm": {"scale": lw["q_norm"]},
                     "k_norm": {"scale": lw["k_norm"]},
                     "o_proj": {"kernel": lw["wo"]}},
            "norm_attn": {"scale": lw["norm_attn"]},
            "norm_attn_out": {"scale": lw["norm_attn_out"]},
            "norm_mlp": {"scale": lw["norm_mlp"]},
            "norm_mlp_out": {"scale": lw["norm_mlp_out"]}}
        if "w_gate" in lw:
            layer["mlp"] = mlp(lw["w_gate"], lw["w_up"], lw["w_down"])
        else:
            layer["moe"] = {"router": lw["router"],
                            "expert_bias": lw["expert_bias"],
                            "expert_gate": lw["e_gate"],
                            "expert_up": lw["e_up"],
                            "expert_down": lw["e_down"],
                            "shared": mlp(lw["s_gate"], lw["s_up"],
                                          lw["s_down"])}
        out[f"layer_{i}"] = layer
    return out


# ---- the plain reference's blocks --------------------------------------

def _attention_one(q, k, v, window, mm):
    """q [B, T, G, D] (the G query heads of one KV head), k, v [B, T, D]:
    softmax(q k^T / sqrt(D) under the causal band) v."""
    T, D = q.shape[1], q.shape[3]
    s = mm(q, k, "btgd,bsd->bgts") * (D ** -0.5)
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    seen = (j <= i) & (j > i - window) if window else j <= i
    p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
    return mm(p, v, "bgts,bsd->btgd")


def attention(h, lw, sz: Sizes, windowed: bool, mm):
    """The gated attention sublayer: q and k RMS-normed over head_dim,
    rotated on a window layer only, attention under the causal band (or
    the whole causal triangle), the output times sigmoid(h W_g), W_o."""
    B, T = h.shape[:2]
    H, K, D = sz.n_heads, sz.n_kv_heads, sz.head_dim
    q = rms_norm(mm(h, lw["wq"], "btd,dhk->bthk"), lw["q_norm"], sz.rms_eps)
    k = rms_norm(mm(h, lw["wk"], "btd,dhk->bthk"), lw["k_norm"], sz.rms_eps)
    v = mm(h, lw["wv"], "btd,dhk->bthk")
    if windowed:
        q, k = rope(q, sz.rope_theta), rope(k, sz.rope_theta)
    G = H // K
    qg = q.reshape(B, T, K, G, D).transpose(2, 0, 1, 3, 4)   # [K,B,T,G,D]
    kg, vg = k.transpose(2, 0, 1, 3), v.transpose(2, 0, 1, 3)
    window = sz.window if windowed else 0
    # One KV head at a time: every head's [T, T] scores at once would not
    # fit beside the weights at T = 4096.
    og = lax.map(lambda a: _attention_one(a[0], a[1], a[2], window, mm),
                 (qg, kg, vg))
    o = og.transpose(1, 2, 0, 3, 4).reshape(B, T, H, D)
    o = o * jax.nn.sigmoid(mm(h, lw["wg"], "btd,dhk->bthk"))
    return mm(o, lw["wo"], "bthk,hkd->btd")


def swiglu(h, g, u, dn, mm):
    return mm(jax.nn.silu(mm(h, g, "btd,df->btf")) * mm(h, u, "btd,df->btf"),
              dn, "btf,fd->btd")


def route(h, lw, sz: Sizes, mm):
    """[B, T, E] gate: zero off each token's top k (chosen by sigmoid
    score plus the bias), there the score, renormalised over the k and
    times ``route_scale``."""
    scores = jax.nn.sigmoid(mm(h, lw["router"], "btd,de->bte"))
    _, idx = lax.top_k(scores + lw["expert_bias"].astype(F32), sz.top_k)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    if sz.route_norm:
        chosen = chosen / jnp.sum(chosen, -1, keepdims=True)
    on = jax.nn.one_hot(idx, sz.n_experts, dtype=F32)        # [B,T,K,E]
    return jnp.einsum("btk,btke->bte", chosen * sz.route_scale, on)


def experts(h, lw, sz: Sizes, mm):
    """Every expert on every token, each weighted by its gate (zero off
    the top k), summed; ``EXPERT_BLOCK`` experts at a time. Plus the
    shared experts, on every token."""
    gate = route(h, lw, sz, mm)
    eb = math.gcd(sz.n_experts, EXPERT_BLOCK)
    n = sz.n_experts // eb
    blocks = lambda w: w.reshape((n, eb) + w.shape[1:])
    gate_b = jnp.moveaxis(gate.reshape(gate.shape[:2] + (n, eb)),
                          2, 0)                                # [n,B,T,e]
    names = ("e_gate", "e_up", "e_down")
    bases = [lw.get(name + "_base") for name in names]

    def block(y, a):
        g = a[0]
        wg, wu, wd = (own if base is None else expert_matrix(base, own)
                      for base, own in zip(bases, a[1:]))
        hid = (jax.nn.silu(mm(h, wg, "btd,edf->btef"))
               * mm(h, wu, "btd,edf->btef")) * g[..., None]
        return y + mm(hid, wd, "btef,efd->btd"), None

    y, _ = lax.scan(block, jnp.zeros(h.shape, F32),
                    (gate_b, *(blocks(lw[name]) for name in names)))
    return y + swiglu(h, lw["s_gate"], lw["s_up"], lw["s_down"], mm)


def layer(x, lw, sz: Sizes, i: int, mm):
    """One block: x <- x + N(attention(N(x))); x <- x + N(mlp(N(x))),
    four RMSNorms of their own. x [B, T, d] float32."""
    h = rms_norm(x, lw["norm_attn"], sz.rms_eps)
    a = attention(h, lw, sz, sz.windowed(i), mm)
    x = x + rms_norm(a, lw["norm_attn_out"], sz.rms_eps)
    h = rms_norm(x, lw["norm_mlp"], sz.rms_eps)
    y = (swiglu(h, lw["w_gate"], lw["w_up"], lw["w_down"], mm)
         if "w_gate" in lw else experts(h, lw, sz, mm))
    return x + rms_norm(y, lw["norm_mlp_out"], sz.rms_eps)


def trunk(w: dict, tokens, sz: Sizes, mm=f32_matmul, remat=False):
    """tokens [B, T] -> the last block's output [B, T, d] float32."""
    x = sz.embedding_multiplier * w["embed"][tokens].astype(F32)
    for i, lw in enumerate(w["layers"]):
        one = partial(layer, sz=sz, i=i, mm=mm)
        x = (jax.checkpoint(one) if remat else one)(x, lw)
    return x


def head(w: dict, x, sz: Sizes, mm=f32_matmul):
    """x [..., T, d] -> logits [..., T, V] float32 (the untied head)."""
    return mm(rms_norm(x, w["norm_f"], sz.rms_eps), w["head"],
              "...td,dv->...tv")


# ---- operations and bytes from shapes ----------------------------------

def attention_params(sz: Sizes) -> int:
    """q, k, v, the output gate and o of one layer."""
    d, H, K, D = sz.d_model, sz.n_heads, sz.n_kv_heads, sz.head_dim
    return 3 * d * H * D + 2 * d * K * D


def expert_params(sz: Sizes) -> int:
    """One routed expert."""
    return 3 * sz.d_model * sz.expert_d_ff


def moe_fixed_params(sz: Sizes) -> int:
    """What a MoE layer reads whatever its tokens' routing: the router
    and the shared experts."""
    return sz.d_model * sz.n_experts + 3 * sz.d_model * sz.shared_d_ff


def head_params(sz: Sizes) -> int:
    return sz.d_model * sz.vocab


def fixed_params(sz: Sizes) -> int:
    """Every weight a decode step reads whatever the routing: attention
    of every layer, the dense MLPs, routers and shared experts, the
    head."""
    return (sz.n_layers * attention_params(sz)
            + sz.n_dense * 3 * sz.d_model * sz.d_ff
            + sz.n_moe * moe_fixed_params(sz) + head_params(sz))


def parameters(sz: Sizes) -> int:
    """All parameters: the above, every routed expert, the expert bias,
    the norms (four a block of d, two of head_dim) and the embedding."""
    return (fixed_params(sz)
            + sz.n_moe * (sz.n_experts * expert_params(sz) + sz.n_experts)
            + sz.n_layers * (4 * sz.d_model + 2 * sz.head_dim)
            + sz.d_model + sz.vocab * sz.d_model)


def attention_flops(sz: Sizes, n_query: int, n_keys: float) -> float:
    """QK^T and PV of one attention layer."""
    return 2 * 2 * sz.n_heads * sz.head_dim * n_query * n_keys


def forward_flops_per_token(sz: Sizes, mean_keys: float) -> float:
    """One token through every block and the head: the top-k routed
    experts and the shared ones (not all experts), a window layer seeing
    at most ``window`` of the ``mean_keys`` keys."""
    routed = sz.n_moe * sz.top_k * expert_params(sz)
    windowed = sum(sz.windowed(i) for i in range(sz.n_layers))
    keys = (windowed * min(mean_keys, sz.window)
            + (sz.n_layers - windowed) * mean_keys)
    return 2 * (fixed_params(sz) + routed) + attention_flops(sz, 1, keys)


def kv_bytes_per_token(sz: Sizes, bytes_per_el: int = 2) -> int:
    """Keys and values of one token, all layers."""
    return 2 * sz.n_layers * sz.n_kv_heads * sz.head_dim * bytes_per_el


def experts_per_step(sz: Sizes, rows: float, record: dict = None) -> float:
    """Distinct experts a decode step reads, summed over the expert
    layers: the window's ``experts_read`` over its decode steps (the
    engine's ``sched_iter`` records); without them, what uniform routing
    of ``rows`` rows gives."""
    recs = [r for r in (record or {}).get("spans", [])
            if r.get("event") == "sched_iter" and "experts_read" in r]
    steps = sum(r["decode_steps"] for r in recs)
    if steps:
        return sum(r["experts_read"] for r in recs) / steps
    E, k = sz.n_experts, sz.top_k
    return sz.n_moe * E * (1.0 - (1.0 - k / E) ** rows)


def decode_step_cost(sz: Sizes, rows: float, mean_context: float,
                     record: dict = None) -> dict:
    """One decode step over ``rows`` live sequences: the weights every
    step reads once, the experts its rows were routed to once, and each
    row's keys and values, at most ``window`` positions on a window
    layer."""
    windowed = sum(sz.windowed(i) for i in range(sz.n_layers))
    per_layer = kv_bytes_per_token(sz) // sz.n_layers
    kv = rows * per_layer * (windowed * min(mean_context, sz.window)
                             + (sz.n_layers - windowed) * mean_context)
    read = experts_per_step(sz, rows, record)
    return {"flops": rows * forward_flops_per_token(sz, mean_context),
            "bytes": 2 * (fixed_params(sz) + read * expert_params(sz)) + kv,
            "experts_read": read}


# ---- the engine's programs: which a mix reaches, and their signatures --

def _hybrid():
    from chipbench.cell import load_arch

    return load_arch("hybrid_ssm", os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))


def reachable_shapes(engine, mix_params: dict) -> tuple:
    """The (nb, T, W) prefill and (nb, W) decode programs this mix's own
    lengths can form."""
    return _hybrid().reachable_shapes(engine, mix_params)


def warm(engine, mix_params: dict) -> int:
    """Run every reachable program once on the engine's own state, every
    write dropped."""
    return _hybrid().warm(engine, mix_params)


def lower_largest(engine, params, mix_params: dict, sharding) -> list:
    """The largest decode and prefill programs, lowered on shapes."""
    return _hybrid().lower_largest(engine, params, mix_params, sharding)
