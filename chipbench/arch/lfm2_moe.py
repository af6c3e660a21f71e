"""The LFM2-MoE decoder of LiquidAI/LFM2-8B-A1B (``model_type``
lfm2_moe): mixers of two kinds (``layer_types``: ``conv``, a gated short
convolution of ``conv_L_cache`` taps, and ``full_attention``, grouped
query attention with RMSNorm of q and k over head_dim before rotary
positions); the first ``num_dense_layers`` layers a SwiGLU MLP, the rest
``num_experts`` routed SwiGLU experts (top ``num_experts_per_tok`` by
sigmoid score plus a selection bias, the chosen scores renormalised and
times ``routed_scaling_factor``) and no shared expert; an RMSNorm before
each sublayer and one after the last block; a head tied to the
embedding.

Everything the harness knows about this architecture is in this file
(``chipbench/README.md``, "An architecture"). A serving architecture: no
``train`` cell names it, so it defines none of the four training names.

The plain reference follows the equations of the lfm2_moe modelling code
as remembered (no network here; the configuration file lists them under
``assumed.equations``), float32, every product through the ``mm`` it is
given. It imports nothing of the program. The convolution runs over the
whole sequence with zeros before it (no cache, no carried inputs); the
experts are computed DENSELY, every expert for every token under a gate
that is zero off the token's top k, ``EXPERT_BLOCK`` experts at a time,
so that the reference fits the chip beside the bf16 weights; attention
one KV head at a time. Departures from the source, all to the same
function: the convolution's taps are ``[taps, channels]`` (tap-major)
where the source stores ``[channels, 1, taps]``; the input projection's
three parts are one leaf in the source's order ``[B | C | x]``; the
experts' and the dense MLP's fused or separate projections are three
leaves; rotary pairs are interleaved (x[2i], x[2i+1]) where the source
rotates halves (a fixed permutation of each head's columns, q/k norm
scales included, which seeded weights absorb).

Counts read no compiled cost analysis and are multiply-adds times two. A
decode step reads the experts its rows were routed to (the engine's
``sched_iter`` records count them, ``experts_read``: ``afmoe.py``'s
``experts_per_step``) and each live row's carried convolution inputs,
read and written. The engine's programs and their signatures are the
hybrid decoder's (``hybrid_ssm.py``: one paged pool and slot leaves,
sentinel pages and slots, the buckets the mix's own lengths form).
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

# The program's kind of each published mixer.
KIND_OF = {"conv": "conv", "full_attention": "attention"}
EXPERT_BLOCK = 8       # experts the reference computes at a time
# The embedding's standard deviation. The table is the head too (tied),
# and nothing multiplies it on its way in: at N(0, 1) a token's own
# embedding, which the residual stream still carries at the last block,
# scores against itself far above every other word, and a reply repeats
# its prompt's last token whatever the layers compute (``hybrid_ssm.py``
# ``EMBED_STD`` has the same reason). At 1/16 the embedding enters the
# stream at a sixteenth of a residual branch; the own-token logit lies
# some two standard deviations up after the twelve blocks (PERF.md
# section 6, PR 42): a candidate, and the context decides.
EMBED_STD = 1.0 / 16
# A routed expert's matrices are the layer's own plus EXPERT_SPREAD times
# the expert's own (both seeded; ``expert_matrix``), as ``afmoe.py``
# makes them: with every expert drawn alone, a bfloat16 program and the
# float32 reference choose another 4th expert for some tokens (scores
# near a token's 4th choice lie closer than bfloat16 rounds them), and
# each such choice moves the logits as far as the fp8 control does. The
# value is set from the readings of a bfloat16 stand-in (PERF.md section
# 6, PR 42).
EXPERT_SPREAD = 0.2


# ---- sizes, and the program's settings that follow from them -----------

@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab: int
    d_model: int
    n_layers: int
    layer_types: tuple        # "conv" | "full_attention", one a layer
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int                 # the dense layers' MLP
    n_dense: int
    n_experts: int
    top_k: int
    expert_d_ff: int
    conv_taps: int
    rope_theta: float
    rms_eps: float
    route_scale: float
    route_norm: bool

    @property
    def n_moe(self) -> int:
        return self.n_layers - self.n_dense

    @property
    def n_conv(self) -> int:
        return sum(k == "conv" for k in self.layer_types)

    @property
    def n_attention(self) -> int:
        return self.n_layers - self.n_conv


def sizes(config: dict) -> Sizes:
    """``config`` is the configuration file: the published ``config.json``
    keys at its top level; its ``layer_types`` has one entry a layer."""
    c = config
    n = int(c["num_hidden_layers"])
    if c["conv_bias"] or not c["use_expert_bias"]:
        raise ValueError("this architecture's convolution has no bias and "
                         "its experts are chosen with a selection bias")
    if len(c["layer_types"]) < n:
        raise ValueError("layer_types names fewer layers than "
                         "num_hidden_layers")
    d, H = int(c["hidden_size"]), int(c["num_attention_heads"])
    return Sizes(
        vocab=int(c["vocab_size"]), d_model=d, n_layers=n,
        layer_types=tuple(c["layer_types"][:n]), n_heads=H,
        n_kv_heads=int(c["num_key_value_heads"]), head_dim=d // H,
        d_ff=int(c["intermediate_size"]),
        n_dense=min(int(c["num_dense_layers"]), n),
        n_experts=int(c["num_experts"]),
        top_k=int(c["num_experts_per_tok"]),
        expert_d_ff=int(c["moe_intermediate_size"]),
        conv_taps=int(c["conv_L_cache"]),
        rope_theta=float(c["rope_theta"]), rms_eps=float(c["norm_eps"]),
        route_scale=float(c["routed_scaling_factor"]),
        route_norm=bool(c["norm_topk_prob"]))


def model_overrides(config: dict) -> dict:
    """The program's ``model_overrides`` (``TransformerConfig`` fields)
    from the file's published keys, so that sizes are stated once. The
    family's other terms (q/k norms, rotary positions, sigmoid routing
    with a selection bias, the tied head) are the registry model's own."""
    sz = sizes(config)
    return {
        "vocab_size": sz.vocab, "d_model": sz.d_model,
        "n_layers": sz.n_layers,
        "layer_types": [KIND_OF[k] for k in sz.layer_types],
        "n_heads": sz.n_heads, "n_kv_heads": sz.n_kv_heads,
        "d_ff": sz.d_ff, "max_seq_len": config["max_position_embeddings"],
        "rope_theta": sz.rope_theta, "rms_norm_eps": sz.rms_eps,
        "ssm_conv": sz.conv_taps,
        "n_experts": sz.n_experts, "moe_top_k": sz.top_k,
        "moe_d_ff": sz.expert_d_ff, "moe_dense_layers": sz.n_dense,
        "moe_route_scale": sz.route_scale, "moe_route_norm": sz.route_norm,
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
    scores of N(0, 1) router logits lie about 0.03 apart around the 4th
    of 32: a bias of 1 / experts is about that spacing, so it changes
    some of a token's choices and not most, and a program that dropped it
    would serve other experts."""
    return 1.0 / sz.n_experts


def _layer_leaves(sz: Sizes, i: int) -> dict:
    """Per-layer leaf -> (shape, standard deviation; None = a norm
    scale). Projections are N(0, 1/fan_in); a routed expert's matrix is
    one drawn for the layer plus one of its own (``EXPERT_SPREAD``),
    N(0, 1/fan_in) together."""
    d, H, K, D = sz.d_model, sz.n_heads, sz.n_kv_heads, sz.head_dim
    if sz.layer_types[i] == "conv":
        L = sz.conv_taps
        mixer = {"w_in": ((d, 3 * d), d ** -0.5),
                 "conv_w": ((L, d), L ** -0.5),
                 "w_out": ((d, d), d ** -0.5)}
    else:
        mixer = {"wq": ((d, H, D), d ** -0.5), "wk": ((d, K, D), d ** -0.5),
                 "wv": ((d, K, D), d ** -0.5),
                 "q_norm": ((D,), None), "k_norm": ((D,), None),
                 "wo": ((H, D, d), (H * D) ** -0.5)}
    out = {"norm_mix": ((d,), None), **mixer, "norm_mlp": ((d,), None)}
    if i < sz.n_dense:
        F = sz.d_ff
        out.update({"w_gate": ((d, F), d ** -0.5), "w_up": ((d, F), d ** -0.5),
                    "w_down": ((F, d), F ** -0.5)})
        return out
    E, F = sz.n_experts, sz.expert_d_ff
    c = (1.0 + EXPERT_SPREAD ** 2) ** -0.5
    out.update({"router": ((d, E), d ** -0.5),
                "expert_bias": ((E,), bias_std(sz))})
    for name, shape, fan_in in (("e_gate", (d, F), d), ("e_up", (d, F), d),
                                ("e_down", (F, d), F)):
        out[name + "_base"] = (shape, c * fan_in ** -0.5)
        out[name] = ((E,) + shape, EXPERT_SPREAD * c * fan_in ** -0.5)
    return out


def leaf_shapes(sz: Sizes) -> dict:
    """{"top": {leaf: (shape, std)}, "layers": [{leaf: (shape, std)}]}:
    what ``weights.make_weights`` draws. The embedding is the head too
    (tied)."""
    return {"top": {"embed": ((sz.vocab, sz.d_model), EMBED_STD),
                    "norm_f": ((sz.d_model,), None)},
            "layers": [_layer_leaves(sz, i) for i in range(sz.n_layers)]}


def _sum_parts(base, own):
    return (base.astype(F32) + own.astype(F32)).astype(own.dtype)


# The experts' matrices: the layer's ``base`` [..] plus each one's own
# [E, ..], added in float32 and rounded once to ``own``'s dtype. The
# program's tree and the reference take the same values from it; the
# program's is written over ``own``'s buffer, so that no second copy of
# an expert leaf exists at any time and the weights are made within what
# serving holds.
expert_matrix = jax.jit(_sum_parts)
_expert_matrix_in_place = jax.jit(_sum_parts, donate_argnums=(1,))


def to_program_tree(w: dict) -> dict:
    """Canonical weights -> the flax parameter tree of the program's
    ``Transformer`` (names are load-bearing there).

    The experts' matrices are made here leaf by leaf, each over its own
    part's buffer, and ``w`` keeps them in place of the parts they were
    made from, so that it stays the same model for the reference."""
    for lw in w["layers"]:
        for name in ("e_gate", "e_up", "e_down"):
            if name + "_base" in lw:
                lw[name] = _expert_matrix_in_place(lw.pop(name + "_base"),
                                                   lw[name])
    out = {"embedder": {"embedding": w["embed"]},
           "norm_f": {"scale": w["norm_f"]}}
    for i, lw in enumerate(w["layers"]):
        layer = {"norm_attn": {"scale": lw["norm_mix"]},
                 "norm_mlp": {"scale": lw["norm_mlp"]}}
        if "w_in" in lw:
            layer["conv"] = {"in_proj": {"kernel": lw["w_in"]},
                             "conv_kernel": lw["conv_w"],
                             "out_proj": {"kernel": lw["w_out"]}}
        else:
            layer["attn"] = {"q_proj": {"kernel": lw["wq"]},
                             "k_proj": {"kernel": lw["wk"]},
                             "v_proj": {"kernel": lw["wv"]},
                             "q_norm": {"scale": lw["q_norm"]},
                             "k_norm": {"scale": lw["k_norm"]},
                             "o_proj": {"kernel": lw["wo"]}}
        if "w_gate" in lw:
            layer["mlp"] = {"gate_proj": {"kernel": lw["w_gate"]},
                            "up_proj": {"kernel": lw["w_up"]},
                            "down_proj": {"kernel": lw["w_down"]}}
        else:
            layer["moe"] = {"router": lw["router"],
                            "expert_bias": lw["expert_bias"],
                            "expert_gate": lw["e_gate"],
                            "expert_up": lw["e_up"],
                            "expert_down": lw["e_down"]}
        out[f"layer_{i}"] = layer
    return out


# ---- the plain reference's blocks --------------------------------------

def conv_mixer(h, lw, sz: Sizes, mm):
    """[B | C | x] = h W_in; v = B * x; w_t = sum_j k[j] v_{t-(L-1)+j},
    zeros before the sequence; out = (C * w) W_out. No activation, no
    bias. h [B, T, d] float32."""
    T, L = h.shape[1], sz.conv_taps
    gate_b, gate_c, x = jnp.split(mm(h, lw["w_in"], "btd,de->bte"), 3,
                                  axis=-1)
    v = jnp.pad(gate_b * x, ((0, 0), (L - 1, 0), (0, 0)))
    taps = lw["conv_w"].astype(F32)
    w = sum(v[:, j:j + T] * taps[j] for j in range(L))
    return mm(gate_c * w, lw["w_out"], "btd,de->bte")


def _attention_one(q, k, v, mm):
    """q [B, T, G, D] (the G query heads of one KV head), k, v [B, T, D]:
    softmax(q k^T / sqrt(D) under the causal mask) v."""
    T, D = q.shape[1], q.shape[3]
    s = mm(q, k, "btgd,bsd->bgts") * (D ** -0.5)
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal[None, None], s, -jnp.inf), axis=-1)
    return mm(p, v, "bgts,bsd->btgd")


def attention_mixer(h, lw, sz: Sizes, mm):
    """q and k RMS-normed over head_dim, then rotated; causal attention of
    grouped queries; W_o. h [B, T, d] float32."""
    B, T = h.shape[:2]
    H, K, D = sz.n_heads, sz.n_kv_heads, sz.head_dim
    q = rms_norm(mm(h, lw["wq"], "btd,dhk->bthk"), lw["q_norm"], sz.rms_eps)
    k = rms_norm(mm(h, lw["wk"], "btd,dhk->bthk"), lw["k_norm"], sz.rms_eps)
    v = mm(h, lw["wv"], "btd,dhk->bthk")
    q, k = rope(q, sz.rope_theta), rope(k, sz.rope_theta)
    qg = q.reshape(B, T, K, H // K, D).transpose(2, 0, 1, 3, 4)
    kg, vg = k.transpose(2, 0, 1, 3), v.transpose(2, 0, 1, 3)
    # One KV head at a time: every head's [T, T] scores at once would not
    # fit beside the weights at T = 4096.
    og = lax.map(lambda a: _attention_one(a[0], a[1], a[2], mm),
                 (qg, kg, vg))
    o = og.transpose(1, 2, 0, 3, 4).reshape(B, T, H, D)
    return mm(o, lw["wo"], "bthk,hkd->btd")


def swiglu(h, g, u, dn, mm):
    return mm(jax.nn.silu(mm(h, g, "btd,df->btf")) * mm(h, u, "btd,df->btf"),
              dn, "btf,fd->btd")


def route(h, lw, sz: Sizes, mm):
    """[B, T, E] gate: zero off each token's top k (chosen by sigmoid
    score plus the bias), there the score, renormalised over the k as the
    source does (over the sum plus 1e-6) and times ``route_scale``."""
    scores = jax.nn.sigmoid(mm(h, lw["router"], "btd,de->bte"))
    _, idx = lax.top_k(scores + lw["expert_bias"].astype(F32), sz.top_k)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    if sz.route_norm:
        chosen = chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-6)
    on = jax.nn.one_hot(idx, sz.n_experts, dtype=F32)        # [B,T,K,E]
    return jnp.einsum("btk,btke->bte", chosen * sz.route_scale, on)


def experts(h, lw, sz: Sizes, mm):
    """Every expert on every token, each weighted by its gate (zero off
    the top k), summed; ``EXPERT_BLOCK`` experts at a time, each block's
    matrices made (and cast) there and nowhere else."""
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
    return y


def layer(x, lw, sz: Sizes, mm):
    """One block: x <- x + mixer(RMSNorm(x)); x <- x + ffn(RMSNorm(x)).
    x [B, T, d] float32."""
    h = rms_norm(x, lw["norm_mix"], sz.rms_eps)
    x = x + (conv_mixer(h, lw, sz, mm) if "w_in" in lw
             else attention_mixer(h, lw, sz, mm))
    h = rms_norm(x, lw["norm_mlp"], sz.rms_eps)
    return x + (swiglu(h, lw["w_gate"], lw["w_up"], lw["w_down"], mm)
                if "w_gate" in lw else experts(h, lw, sz, mm))


def trunk(w: dict, tokens, sz: Sizes, mm=f32_matmul, remat=False):
    """tokens [B, T] -> the last block's output [B, T, d] float32."""
    one = partial(layer, sz=sz, mm=mm)
    if remat:
        one = jax.checkpoint(one)
    x = w["embed"][tokens].astype(F32)
    for lw in w["layers"]:
        x = one(x, lw)
    return x


def head(w: dict, x, sz: Sizes, mm=f32_matmul):
    """x [..., T, d] -> logits [..., T, V] float32: the final RMSNorm
    (the source's ``embedding_norm``), then the tied embedding."""
    return mm(rms_norm(x, w["norm_f"], sz.rms_eps), w["embed"],
              "...td,vd->...tv")


# ---- operations and bytes from shapes ----------------------------------

def conv_params(sz: Sizes) -> int:
    """One conv mixer: in, out and its taps."""
    d = sz.d_model
    return 3 * d * d + d * d + sz.conv_taps * d


def attention_params(sz: Sizes) -> int:
    """One attention mixer: q, k, v, o and the two norms over head_dim."""
    d, H, K, D = sz.d_model, sz.n_heads, sz.n_kv_heads, sz.head_dim
    return 2 * d * H * D + 2 * d * K * D + 2 * D


def dense_params(sz: Sizes) -> int:
    return 3 * sz.d_model * sz.d_ff


def expert_params(sz: Sizes) -> int:
    """One routed expert."""
    return 3 * sz.d_model * sz.expert_d_ff


def router_params(sz: Sizes) -> int:
    """A MoE layer's router and selection bias."""
    return sz.d_model * sz.n_experts + sz.n_experts


def head_params(sz: Sizes) -> int:
    """The tied table: the embedding, read as the head."""
    return sz.d_model * sz.vocab


def fixed_params(sz: Sizes) -> int:
    """Every weight a decode step reads whatever the routing: the mixers,
    the dense MLPs, the routers, the tied table once."""
    return (sz.n_conv * conv_params(sz) + sz.n_attention * attention_params(sz)
            + sz.n_dense * dense_params(sz) + sz.n_moe * router_params(sz)
            + head_params(sz))


def parameters(sz: Sizes) -> int:
    """All parameters: the above, every routed expert, the two norms of
    each block and the final one (the tied table once)."""
    return (fixed_params(sz) + sz.n_moe * sz.n_experts * expert_params(sz)
            + sz.n_layers * 2 * sz.d_model + sz.d_model)


def attention_flops(sz: Sizes, n_query: int, n_keys: float) -> float:
    """QK^T and PV of one attention layer."""
    return 2 * 2 * sz.n_heads * sz.head_dim * n_query * n_keys


def forward_flops_per_token(sz: Sizes, mean_keys: float) -> float:
    """One token through every block and the head: the top-k routed
    experts (not all), the conv mixers' taps and two gates, attention's
    term in the attention layers only."""
    conv_elementwise = (2 * sz.conv_taps + 2) * sz.d_model
    return (2 * (fixed_params(sz) + sz.n_moe * sz.top_k * expert_params(sz))
            + sz.n_attention * attention_flops(sz, 1, mean_keys)
            + sz.n_conv * conv_elementwise)


def kv_bytes_per_token(sz: Sizes, bytes_per_el: int = 2) -> int:
    """Keys and values of one token, the attention layers'."""
    return 2 * sz.n_attention * sz.n_kv_heads * sz.head_dim * bytes_per_el


def state_bytes_per_row(sz: Sizes, conv_bytes_per_el: int = 2) -> dict:
    """What one sequence holds outside the pages: the carried inputs of
    every conv mixer (bfloat16)."""
    return {"conv": sz.n_conv * (sz.conv_taps - 1) * sz.d_model
            * conv_bytes_per_el}


def _arch(name: str):
    from chipbench.cell import load_arch

    return load_arch(name, os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))


def experts_per_step(sz: Sizes, rows: float, record: dict = None) -> float:
    """Distinct experts a decode step reads, summed over the expert
    layers: the window's ``experts_read`` over its decode steps, else
    what uniform routing of ``rows`` rows gives (``afmoe.py``'s)."""
    return _arch("afmoe").experts_per_step(sz, rows, record)


def decode_step_cost(sz: Sizes, rows: float, mean_context: float,
                     record: dict = None) -> dict:
    """One decode step over ``rows`` live sequences: the fixed weights
    once, the experts its rows were routed to once, each row's carried
    convolution inputs read and written, its keys and values in the
    attention layers read once. ``state_bytes`` is the part of ``bytes``
    that is per-slot state."""
    read = experts_per_step(sz, rows, record)
    state = rows * 2 * state_bytes_per_row(sz)["conv"]
    return {"flops": rows * forward_flops_per_token(sz, mean_context),
            "bytes": (2 * (fixed_params(sz) + read * expert_params(sz))
                      + state + rows * mean_context * kv_bytes_per_token(sz)),
            "experts_read": read, "state_bytes": state}


# ---- the engine's programs: which a mix reaches, and their signatures --

def reachable_shapes(engine, mix_params: dict) -> tuple:
    """The (nb, T, W) prefill and (nb, W) decode programs this mix's own
    lengths can form."""
    return _arch("hybrid_ssm").reachable_shapes(engine, mix_params)


def warm(engine, mix_params: dict) -> int:
    """Run every reachable program once on the engine's own state, every
    write dropped."""
    return _arch("hybrid_ssm").warm(engine, mix_params)


def lower_largest(engine, params, mix_params: dict, sharding) -> list:
    """The largest decode and prefill programs, lowered on shapes."""
    return _arch("hybrid_ssm").lower_largest(engine, params, mix_params,
                                              sharding)
