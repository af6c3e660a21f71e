"""The expert layers' grouped-product kernel (``ops/pallas/moe_gmm.py``)
interpreted on the CPU, against ``jax.lax.ragged_dot`` at small shapes
that tile (8 experts, k 256, n 128): empty groups, groups that do not
fill their m tiles, every row in one group, fewer rows than one tile;
the tiles it picks for the benchmark cell's shapes; and
``dropless_experts`` taking the kernel where the shapes tile (against
routing each token by hand) and ``ragged_dot`` where they do not."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from serverless_learn_tpu.ops import moe
from serverless_learn_tpu.ops.pallas import moe_gmm
from test_afmoe_serving import _dense_by_hand

E, K, N = 8, 256, 128
SIZES = {
    "empty_groups": [10, 0, 6, 20, 0, 8, 12, 8],
    "groups_not_multiples_of_tm": [17, 3, 29, 1, 33, 5, 11, 9],
    "all_rows_in_one_group": [0, 0, 0, 64, 0, 0, 0, 0],
    "fewer_rows_than_one_tile": [3, 0, 0, 1, 0, 0, 2, 0],
}


def _operands(sizes, dtype, k=K, n=N, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    m = sum(sizes)
    lhs = jax.random.normal(ks[0], (m, k)).astype(dtype)
    rhs = (jax.random.normal(ks[1], (len(sizes), k, n)) / k ** 0.5
           ).astype(dtype)
    return lhs, rhs, jnp.asarray(sizes, jnp.int32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(SIZES))
def test_the_kernel_matches_ragged_dot(case, dtype):
    lhs, rhs, sizes = _operands(SIZES[case], dtype)
    got = moe_gmm.gmm(lhs, rhs, sizes, tiles=(16, 128, 128))
    want = jax.lax.ragged_dot(lhs, rhs, sizes)
    assert got.dtype == want.dtype == dtype
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    else:   # both sum in float32 and round once to bfloat16
        np.testing.assert_allclose(got, want, rtol=2 ** -7,
                                   atol=2 ** -8 * np.abs(want).max())


@pytest.mark.parametrize("tiles", [(16, 256, 128), (32, 128, 128),
                                   (64, 256, 128)])
def test_rows_padded_up_to_the_tile_are_dropped(tiles):
    """37 rows in tiles of 16 to 64: the rows are padded to the tile,
    the padding lies in no group, and the output has the rows it was
    given. k whole and k in two tiles alike."""
    lhs, rhs, sizes = _operands([5, 0, 11, 0, 9, 0, 12, 0], jnp.float32)
    got = moe_gmm.gmm(lhs, rhs, sizes, tiles=tiles)
    assert got.shape == (37, N)
    np.testing.assert_allclose(np.asarray(got), np.asarray(
        jax.lax.ragged_dot(lhs, rhs, sizes)), rtol=1e-5, atol=1e-5)


def test_the_tiles_follow_the_cells_shapes():
    """Trinity-Mini's expert layer: hidden 2,048, width 1,024. A decode
    step of 32 slots has 256 rows, a prefill program of 32 rows of 32
    tokens 8,192 (top 8): the sweep's winners, k and n whole, tm 128."""
    for m in (256, 1024, 8192):
        assert moe_gmm.tiling(m, 2048, 1024) == (128, 2048, 1024)
        assert moe_gmm.tiling(m, 1024, 2048) == (128, 1024, 2048)
    # Fewer rows than a tile: the tile is the rows rounded up to 16.
    assert moe_gmm.tiling(24, 256, 128) == (32, 256, 128)
    # Mixtral-8x7B's experts (hidden 4,096, width 14,336): k in two
    # tiles, and each tile divides its dimension by whole lanes.
    for k, n in ((4096, 14336), (14336, 4096)):
        tm, tk, tn = moe_gmm.tiling(2048, k, n)
        assert (tm, tk) == (128, 2048)
        assert n % tn == 0 and tn % 128 == 0
        assert tk * tn * 2 <= moe_gmm.MAX_RHS_BLOCK_BYTES


@pytest.mark.parametrize("lhs, rhs, reason", [
    ((8, 64), (4, 64, 128), "multiples of 128"),
    ((8, 128), (4, 128, 96), "multiples of 128"),
])
def test_untileable_shapes_are_named(lhs, rhs, reason):
    a = jnp.zeros(lhs, jnp.float32)
    b = jnp.zeros(rhs, jnp.float32)
    assert reason in moe_gmm.untileable_reason(a, b)
    with pytest.raises(ValueError, match=reason):
        moe_gmm.gmm(a, b, jnp.zeros(rhs[0], jnp.int32))


def test_untileable_dtypes_are_named():
    a = jnp.zeros((8, 128), jnp.int8)
    assert "takes one of" in moe_gmm.untileable_reason(
        a, jnp.zeros((4, 128, 128), jnp.int8))
    assert "takes one of" in moe_gmm.untileable_reason(
        a.astype(jnp.float32), jnp.zeros((4, 128, 128), jnp.bfloat16))


def _spy(monkeypatch):
    calls = []
    real = moe_gmm.gmm

    def counting(*a, **kw):
        calls.append(a[1].shape)
        return real(*a, **kw)

    monkeypatch.setattr(moe_gmm, "gmm", counting)
    return calls


@pytest.mark.parametrize("d, f, kernel", [(256, 128, True), (16, 8, False)],
                         ids=["tiles_kernel", "untileable_ragged_dot"])
def test_dropless_experts_take_the_kernel_where_the_shapes_tile(
        monkeypatch, d, f, kernel):
    calls = _spy(monkeypatch)
    n, e, top = 24, 6, 2
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    x = jax.random.normal(ks[0], (n, d))
    _, gate_w, idx = moe.route(jax.random.normal(ks[1], (n, e)), top,
                               "sigmoid", None, True, 2.5)
    wg, wu = (jax.random.normal(k, (e, d, f)) / d ** 0.5 for k in ks[2:4])
    wd = jax.random.normal(ks[4], (e, f, d)) / f ** 0.5
    got = moe.dropless_experts(x, gate_w, idx, wg, wu, wd)
    want = _dense_by_hand(*(np.asarray(a, np.float64) for a in
                            (x, gate_w, idx, wg, wu, wd)))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4,
                               atol=1e-4 * np.abs(want).max())
    assert calls == ([(e, d, f), (e, d, f), (e, f, d)] if kernel else [])
