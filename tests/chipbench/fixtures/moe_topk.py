"""A second architecture, for the harness's own tests: the registry's
``moe_tiny`` family (grouped-query attention as the dense decoder's, and in
place of its MLP a softmax router over E SwiGLU experts, the top k of them
renormalised). ``test_arch_dropped_in.py`` copies this file into a tiny
tree as ``arch/moe_topk.py`` and runs its cells with no edit to any file of
``chipbench/``: it defines the names ``chipbench/README.md`` ("An
architecture") lists, with its own leaves, its own plain float32 reference
and its own counts.

The program drops a token that finds its expert's slots full. The cells
state ``moe_capacity_factor`` = E / k, at which every expert has a slot for
every token of its group, so none is dropped and the reference needs no
notion of capacity; the load-balance loss is weighted by nought, so the
loss compared is the language-model loss alone.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from chipbench.reference import F32, f32_matmul, rms_norm, rope


@dataclasses.dataclass(frozen=True)
class Sizes:
    vocab: int
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    n_experts: int
    top_k: int
    rope_theta: float
    rms_eps: float


def sizes(config: dict) -> Sizes:
    return Sizes(
        vocab=int(config["vocab_size"]), d_model=int(config["hidden_size"]),
        n_layers=int(config["num_hidden_layers"]),
        n_heads=int(config["num_attention_heads"]),
        n_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]),
        d_ff=int(config["intermediate_size"]),
        n_experts=int(config["num_local_experts"]),
        top_k=int(config["num_experts_per_tok"]),
        rope_theta=float(config["rope_theta"]),
        rms_eps=float(config["rms_norm_eps"]))


def model_overrides(config: dict) -> dict:
    c = config
    return {
        "vocab_size": c["vocab_size"], "d_model": c["hidden_size"],
        "n_layers": c["num_hidden_layers"],
        "n_heads": c["num_attention_heads"],
        "n_kv_heads": c["num_key_value_heads"],
        "d_ff": c["intermediate_size"],
        "max_seq_len": c["max_position_embeddings"],
        "rope_theta": c["rope_theta"], "tie_embeddings": False,
        "n_experts": c["num_local_experts"],
        "moe_top_k": c["num_experts_per_tok"],
        # A slot for every token in every expert: no token is dropped.
        "moe_capacity_factor": c["num_local_experts"]
        / c["num_experts_per_tok"],
        "moe_aux_weight": 0.0,
    }


def at_depth(config: dict, depth: int) -> dict:
    return dict(config, num_hidden_layers=depth)


# ---- leaves -------------------------------------------------------------

def _layer_leaves(sz: Sizes) -> dict:
    d, H, K, D, F, E = (sz.d_model, sz.n_heads, sz.n_kv_heads, sz.head_dim,
                        sz.d_ff, sz.n_experts)
    return {
        "norm_attn": ((d,), None), "norm_mlp": ((d,), None),
        "wq": ((d, H, D), d ** -0.5), "wk": ((d, K, D), d ** -0.5),
        "wv": ((d, K, D), d ** -0.5), "wo": ((H, D, d), (H * D) ** -0.5),
        "router": ((d, E), d ** -0.5),
        "e_gate": ((E, d, F), d ** -0.5), "e_up": ((E, d, F), d ** -0.5),
        "e_down": ((E, F, d), F ** -0.5),
    }


def leaf_shapes(sz: Sizes) -> dict:
    return {
        "top": {"embed": ((sz.vocab, sz.d_model), 1.0),
                "head": ((sz.d_model, sz.vocab), sz.d_model ** -0.5),
                "norm_f": ((sz.d_model,), None)},
        "layers": [_layer_leaves(sz)] * sz.n_layers,
    }


_TOP = (("embed", ("embedder", "embedding")), ("head", ("lm_head", "kernel")),
        ("norm_f", ("norm_f", "scale")))
_LAYER = (("norm_attn", ("norm_attn", "scale")),
          ("norm_mlp", ("norm_mlp", "scale")),
          ("wq", ("attn", "q_proj", "kernel")),
          ("wk", ("attn", "k_proj", "kernel")),
          ("wv", ("attn", "v_proj", "kernel")),
          ("wo", ("attn", "o_proj", "kernel")),
          ("router", ("moe", "router")),
          ("e_gate", ("moe", "expert_gate")), ("e_up", ("moe", "expert_up")),
          ("e_down", ("moe", "expert_down")))


def _put(tree: dict, path: tuple, leaf) -> None:
    for key in path[:-1]:
        tree = tree.setdefault(key, {})
    tree[path[-1]] = leaf


def _get(tree: dict, path: tuple):
    for key in path:
        tree = tree[key]
    return tree


def to_program_tree(w: dict) -> dict:
    out: dict = {}
    for name, path in _TOP:
        _put(out, path, w[name])
    for i, lw in enumerate(w["layers"]):
        for name, path in _LAYER:
            _put(out, (f"layer_{i}",) + path, lw[name])
    return out


def trained_of_program_tree(params: dict, sz: Sizes) -> list:
    """Every leaf is trained: one dict per layer, then the top leaves."""
    out = [{name: _get(params[f"layer_{i}"], path) for name, path in _LAYER}
           for i in range(sz.n_layers)]
    return out + [{name: _get(params, path) for name, path in _TOP}]


def split_trained(w: dict):
    f32 = lambda d: {k: v.astype(F32) for k, v in d.items()}
    top = {k: v for k, v in w.items() if k != "layers"}
    return {}, [f32(lw) for lw in w["layers"]] + [f32(top)]


def merge_trained(frozen, trained):
    return dict(trained[-1], layers=trained[:-1])


# ---- the plain reference -------------------------------------------------

def _attention(h, lw, sz: Sizes, mm):
    B, T = h.shape[:2]
    H, K, D = sz.n_heads, sz.n_kv_heads, sz.head_dim
    q = rope(mm(h, lw["wq"], "btd,dhk->bthk"), sz.rope_theta)
    k = rope(mm(h, lw["wk"], "btd,dhk->bthk"), sz.rope_theta)
    v = mm(h, lw["wv"], "btd,dhk->bthk")
    q = q.reshape(B, T, K, H // K, D)
    s = mm(q, k, "btkgd,bskd->bkgts") * (D ** -0.5)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    o = mm(jax.nn.softmax(s, axis=-1), v, "bkgts,bskd->btkgd")
    return mm(o.reshape(B, T, H, D), lw["wo"], "bthk,hkd->btd")


def _experts(h, lw, sz: Sizes, mm):
    """Softmax over all E experts, the top k renormalised; every expert is
    computed for every token and weighted by its (mostly zero) gate."""
    probs = jax.nn.softmax(mm(h, lw["router"], "btd,de->bte"), axis=-1)
    top, idx = jax.lax.top_k(probs, sz.top_k)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    gates = jnp.einsum("btk,btke->bte", top,
                       jax.nn.one_hot(idx, sz.n_experts, dtype=F32))
    act = jax.nn.silu(mm(h, lw["e_gate"], "btd,edf->btef")) \
        * mm(h, lw["e_up"], "btd,edf->btef")
    return jnp.einsum("bte,bted->btd", gates,
                      mm(act, lw["e_down"], "btef,efd->bted"))


def layer(x, lw, sz: Sizes, mm):
    x = x + _attention(rms_norm(x, lw["norm_attn"], sz.rms_eps), lw, sz, mm)
    return x + _experts(rms_norm(x, lw["norm_mlp"], sz.rms_eps), lw, sz, mm)


def trunk(w: dict, tokens, sz: Sizes, mm=f32_matmul, remat=False):
    x = w["embed"][tokens].astype(F32)
    for lw in w["layers"]:
        x = layer(x, lw, sz, mm)
    return x


def head(w: dict, x, sz: Sizes, mm=f32_matmul):
    return mm(rms_norm(x, w["norm_f"], sz.rms_eps), w["head"],
              "...td,dv->...tv")


# ---- counts: the experts a token uses, the experts a step touches --------

def _attention_params(sz: Sizes) -> int:
    d, H, K, D = sz.d_model, sz.n_heads, sz.n_kv_heads, sz.head_dim
    return d * H * D + 2 * d * K * D + H * D * d


def _expert_params(sz: Sizes) -> int:
    return 3 * sz.d_model * sz.d_ff


def attention_flops(sz: Sizes, n_query: int, n_keys: float) -> float:
    return 2 * 2 * sz.n_heads * sz.head_dim * n_query * n_keys


def forward_flops_per_token(sz: Sizes, mean_keys: float) -> float:
    """A token multiplies the attention's weights, the router and the k
    experts it is routed to: not all E."""
    per_layer = (_attention_params(sz) + sz.d_model * sz.n_experts
                 + sz.top_k * _expert_params(sz))
    return (2 * (sz.n_layers * per_layer + sz.d_model * sz.vocab)
            + sz.n_layers * attention_flops(sz, 1, mean_keys))


def train_flops_per_token(sz: Sizes, seq_len: int) -> float:
    """Every leaf is trained: forward, and twice as much backward."""
    return 3 * forward_flops_per_token(sz, seq_len / 2)


def experts_touched(sz: Sizes, rows: float) -> float:
    """Expected distinct experts that ``rows`` tokens touch in one layer,
    each choosing k of E uniformly."""
    return sz.n_experts * (1.0 - (1.0 - sz.top_k / sz.n_experts) ** rows)


def decode_step_cost(sz: Sizes, rows: float, mean_context: float,
                     record: dict = None) -> dict:
    """One decode step: the attention's weights, the router and the head
    once, the experts that the step's rows touch once (not every expert),
    each row's keys and values once. The program counts no routing, so
    the experts touched are the expectation for ``rows`` rows; an
    architecture whose program does count them reads ``record``."""
    per_layer = (_attention_params(sz) + sz.d_model * sz.n_experts
                 + experts_touched(sz, rows) * _expert_params(sz))
    weights = 2 * (sz.n_layers * per_layer + sz.d_model * sz.vocab)
    kv = 2 * sz.n_layers * sz.n_kv_heads * sz.head_dim * 2
    return {"flops": rows * forward_flops_per_token(sz, mean_context),
            "bytes": weights + rows * mean_context * kv}


# ---- the engine's programs ------------------------------------------------
# The same paged pool of keys and values as the dense decoder's, so the
# same programs: the tree's ``arch/dense_gqa.py`` knows their signatures.

def _dense():
    import os

    from chipbench.cell import load_arch

    return load_arch("dense_gqa", os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def reachable_shapes(engine, mix_params: dict) -> tuple:
    return _dense().reachable_shapes(engine, mix_params)


def warm(engine, mix_params: dict) -> int:
    return _dense().warm(engine, mix_params)


def lower_largest(engine, params, mix_params: dict, sharding) -> list:
    return _dense().lower_largest(engine, params, mix_params, sharding)
