"""A pin on the seeded weights. Every cell's readings, and each number
under ``checked``, follow from the values ``make_weights`` draws from the
seed and from the names ``to_program_tree`` gives them: the hashes below
were taken on the tree before the architecture moved behind
``chipbench/arch/`` (PR 28) and hold after it. The same key folding and
the same order of leaves give the same bits; a reordered leaf or a renamed
path changes a hash."""

import hashlib

import jax
import numpy as np
import pytest

import tiny
from chipbench.cell import load_cell


def _digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str((a.shape, a.dtype)).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _paths(tree) -> str:
    return hashlib.sha256("\n".join(
        jax.tree_util.keystr(p) for p, _ in
        jax.tree_util.tree_flatten_with_path(tree)[0]).encode()).hexdigest()


# (cell, seed) -> (canonical weights, program tree, program key paths)
PINS = {
    ("tiny-train", 7): (
        "5d037632ed756d4270a8c57ced7610d17b60fbc603a3155ad36ade8997d5e2c8",
        "32b24e9740456c618c50e040c8bd3b6d61f7153277c55edbd70fea35edbd5b0c",
        "566c9eec4771a8b4573cf34e3acf6e43a7099abd34ac7358e2fe24ad0fd940ec"),
    ("tiny-train", 2 ** 31 + 11): (
        "8b0b0aa04aaa6b50a5dab7b7d9b65fc642d5c83125630d836a02cef5631239e8",
        "e1fbb31b51c422c48ec3d50917804116c621e2c6291dc7ca5804c1d806857504",
        "566c9eec4771a8b4573cf34e3acf6e43a7099abd34ac7358e2fe24ad0fd940ec"),
    ("tiny-backlog", 7): (
        "f856b9906be3bfb02c736bd2ab1cc1030f67137e55eb381d3fc324a6adb8931f",
        "501e684b373b5e06f4e96e8d62cb9b79bac3f6b9b2b2384cef441c1e8e7ca9c6",
        "8bd7b03e1fcf2d4b1d8bd552b5e4613909a93889fd28c9939384b216170d4ec6"),
}


@pytest.mark.parametrize("cell_name,seed", list(PINS))
def test_seeded_weights_and_their_program_tree_are_pinned(
        tiny_root, cell_name, seed):
    w, tree = tiny.seeded_trees(load_cell(cell_name, tiny_root), seed)
    got = (_digest(w), _digest(tree), _paths(tree))
    assert got == PINS[(cell_name, seed)], got


def test_the_program_tree_renames_and_changes_no_value(tiny_root):
    w, tree = tiny.seeded_trees(load_cell("tiny-train", tiny_root), 7)
    flat = lambda t: sorted(np.asarray(l).tobytes()
                            for l in jax.tree_util.tree_leaves(t))
    assert flat(w) == flat(tree)
