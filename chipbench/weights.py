"""Seeded weights, made on the device in one jitted call.

The benchmark, not the program, makes the weights, so the program under
test and the plain reference can each be handed the same values without
either taking anything from the other. The layout here is the benchmark's
own ("canonical"); ``to_program_tree`` renames it into the parameter tree
of ``serverless_learn_tpu.models.transformer.Transformer``.

The seed is a traced argument: every seed runs the same compiled program,
so only a checkout's first run compiles it.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp


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

    @classmethod
    def from_config(cls, model: dict, lora: dict | None = None) -> "Sizes":
        """``model`` holds the published ``config.json`` keys."""
        lora = lora or {}
        return cls(
            vocab=int(model["vocab_size"]),
            d_model=int(model["hidden_size"]),
            n_layers=int(model["num_hidden_layers"]),
            n_heads=int(model["num_attention_heads"]),
            n_kv_heads=int(model["num_key_value_heads"]),
            head_dim=int(model["head_dim"]),
            d_ff=int(model["intermediate_size"]),
            rope_theta=float(model["rope_theta"]),
            rms_eps=float(model["rms_norm_eps"]),
            lora_rank=int(lora.get("rank", 0)),
            lora_alpha=float(lora.get("alpha", 16.0)),
        )


def _leaf_shapes(sz: Sizes) -> dict:
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


ADAPTER_LEAVES = ("q_a", "q_b", "v_a", "v_b")


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _scale(key, shape, dtype):
    return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
            ).astype(dtype)


@partial(jax.jit, static_argnums=(0, 2))
def make_weights(sz: Sizes, seed, dtype) -> dict:
    """All weights of ``sz`` from ``seed`` (a traced uint32), in ``dtype``.

    Projections are N(0, 1/fan_in), the embedding N(0, 1), norm scales
    1 + 0.1 N(0, 1). Each leaf has a key of its own, folded from the
    layer and the leaf's position, so the values do not depend on depth.
    """
    root = jax.random.key(seed, impl="rbg")
    shapes = _leaf_shapes(sz)

    def leaf(key, shape, std):
        if std is None:
            return _scale(key, shape, dtype)
        return _normal(key, shape, std, dtype)

    layers = []
    for i in range(sz.n_layers):
        lkey = jax.random.fold_in(root, i + 1)
        layers.append({
            name: leaf(jax.random.fold_in(lkey, j), shape, std)
            for j, (name, (shape, std)) in enumerate(shapes.items())})
    top = jax.random.fold_in(root, 0)
    return {
        "embed": _normal(jax.random.fold_in(top, 0),
                         (sz.vocab, sz.d_model), 1.0, dtype),
        "head": _normal(jax.random.fold_in(top, 1),
                        (sz.d_model, sz.vocab), sz.d_model ** -0.5, dtype),
        "norm_f": _scale(jax.random.fold_in(top, 2), (sz.d_model,), dtype),
        "layers": layers,
    }


def seed_u32(seed: int):
    """``--seed`` may exceed 2**31; fold it into 32 bits for the key."""
    import numpy as np

    return np.uint32(int(seed) % (2 ** 32))


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


def adapters_of_program_tree(params: dict, n_layers: int) -> list:
    """The adapter leaves of a program parameter (or same-shaped moment)
    tree, back in canonical names: one dict per layer."""
    out = []
    for i in range(n_layers):
        attn = params[f"layer_{i}"]["attn"]
        out.append({
            "q_a": attn["q_lora"]["lora_a"]["kernel"],
            "q_b": attn["q_lora"]["lora_b"]["kernel"],
            "v_a": attn["v_lora"]["lora_a"]["kernel"],
            "v_b": attn["v_lora"]["lora_b"]["kernel"],
        })
    return out


def check_tree_matches(tree: dict, abstract) -> None:
    """Raise unless ``tree`` has exactly the paths, shapes and dtypes of
    the program's abstract parameters: a renamed or reshaped leaf must
    fail here, by name, and not deep inside a jitted step."""
    def flat(t):
        return {jax.tree_util.keystr(p): (tuple(l.shape), jnp.dtype(l.dtype))
                for p, l in jax.tree_util.tree_flatten_with_path(t)[0]}

    got, want = flat(tree), flat(abstract)
    problems = ([f"missing {k}" for k in sorted(want.keys() - got.keys())]
                + [f"unknown {k}" for k in sorted(got.keys() - want.keys())]
                + [f"{k}: {got[k]} vs program {want[k]}"
                   for k in sorted(got.keys() & want.keys())
                   if got[k] != want[k]])
    if problems:
        raise ValueError("benchmark weights do not fit the program's "
                         "parameter tree: " + "; ".join(problems[:6]))
