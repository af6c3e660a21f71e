"""Seeded weights, made on the device in one jitted call.

The benchmark, not the program, makes the weights, so the program under
test and the plain reference can each be handed the same values without
either taking anything from the other. The layout here is the benchmark's
own ("canonical": top-level leaves and ``layers``, one dict per layer);
which leaves there are, and their names in the program's parameter tree,
is the architecture's to say (``arch/<name>.py``: ``leaf_shapes``,
``to_program_tree``).

The seed is a traced argument: every seed runs the same compiled program,
so only a checkout's first run compiles it.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _scale(key, shape, dtype):
    return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
            ).astype(dtype)


@partial(jax.jit, static_argnums=(0, 1, 3))
def make_weights(arch, sz, seed, dtype) -> dict:
    """All weights of ``sz`` from ``seed`` (a traced uint32), in ``dtype``:
    the leaves ``arch.leaf_shapes(sz)`` names, each N(0, std) or, where
    its std is None, a norm scale 1 + 0.1 N(0, 1). Each leaf has a key of
    its own, folded from the layer and the leaf's position, so the values
    do not depend on depth.
    """
    root = jax.random.key(seed, impl="rbg")
    shapes = arch.leaf_shapes(sz)

    def leaf(key, shape, std):
        if std is None:
            return _scale(key, shape, dtype)
        return _normal(key, shape, std, dtype)

    def leaves(key, of: dict) -> dict:
        return {name: leaf(jax.random.fold_in(key, j), shape, std)
                for j, (name, (shape, std)) in enumerate(of.items())}

    return dict(leaves(jax.random.fold_in(root, 0), shapes["top"]),
                layers=[leaves(jax.random.fold_in(root, i + 1), of)
                        for i, of in enumerate(shapes["layers"])])


def seed_u32(seed: int):
    """``--seed`` may exceed 2**31; fold it into 32 bits for the key."""
    import numpy as np

    return np.uint32(int(seed) % (2 ** 32))


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
