"""The grouped product of a dropless expert layer as a Pallas TPU kernel.

``out[r, :] = lhs[r, :] @ rhs[group(r)]``: the rows of ``lhs [m, k]``
fall into ``E`` contiguous groups (``group_sizes [E]``, int32, as
``jax.lax.ragged_dot`` takes them), and each group is multiplied by its
own ``rhs [E, k, n]`` matrix. In a dropless expert layer
(``ops/moe.py`` ``dropless_experts``) the rows are the (token, expert)
pairs sorted by expert and the groups are the experts.

Adapted from the forward of the megablox ``gmm`` that ships with JAX
(``jax/experimental/pallas/ops/tpu/megablox/gmm.py``, Copyright 2024 The
JAX Authors, Apache License 2.0): the same grid of (n tiles, visited m
tiles, k tiles) in which an m tile that two groups share is visited once
for each, its rows kept where they belong to the visit's group, and no
tile visited for an empty group, so an expert no row was routed to is
never read. Left out: group offsets for sharded experts, an output to
accumulate into, a transposed ``rhs``, a k that the tile does not
divide. The kernel is named (``pallas_call(name=)``), so that a device
trace reads ``moe_gmm.<n>`` and the benchmark's ``device_ops`` sums its
calls under ``moe_gmm``.

Precision: the operands' dtype into the MXU, float32 accumulation,
output in the operands' dtype, as ``ragged_dot`` gives. Forward only:
serving runs it, training keeps its capacity dispatch.

Rows are padded up to the m tile; padding rows lie in no group. A row in
no group (padding, or past the groups' sum) is left as the kernel found
the output's memory: the caller reads only rows its groups cover.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KERNEL = "moe_gmm"
DTYPES = (jnp.bfloat16, jnp.float32)
LANE = 128
# The largest (tk, tn) block of ``rhs`` a grid step holds. The kernel
# asks for no scoped VMEM of its own: with ``vmem_limit_bytes`` set (16,
# 32 or 64 MiB alike) the TPU compiler gave the program's other fusions a
# smaller window of scoped VMEM, and the Trinity cell's attention
# softmax fusions in prefill ran some six times slower (PERF.md section
# 6, PR 41). So the tiles fit the default: two ``rhs`` blocks, two
# ``lhs`` and two output tiles and the float32 accumulator.
MAX_RHS_BLOCK_BYTES = 4 * 2 ** 20


def untileable_reason(lhs, rhs) -> Optional[str]:
    """Why the kernel cannot run this product, or None when it can.
    ``dropless_experts`` asks this and takes ``jax.lax.ragged_dot``
    otherwise."""
    from serverless_learn_tpu.ops.pallas.flash_attention import _sharding_mesh

    k, n = rhs.shape[1], rhs.shape[2]
    if lhs.dtype != rhs.dtype or jnp.dtype(lhs.dtype) not in map(
            jnp.dtype, DTYPES):
        return (f"operands {lhs.dtype} x {rhs.dtype}: the kernel takes "
                f"one of {[jnp.dtype(d).name for d in DTYPES]} for both")
    if k % LANE or n % LANE:
        return f"k {k} and n {n} are not both multiples of {LANE}"
    if _sharding_mesh() is not None:
        # GSPMD has no partitioning rule for pallas_call.
        return "a multi-device mesh is live"
    return None


def _divisor_tile(dim: int, cap: int) -> int:
    """The largest multiple of ``LANE`` that divides ``dim`` and is at
    most ``cap``."""
    t = max(LANE, min(cap, dim) // LANE * LANE)
    while dim % t:
        t -= LANE
    return t


# The sweep (``benchmarks/moe_gmm_sweep.py`` on one TPU v5e, bfloat16,
# 128 experts, top 8, rows routed uniformly; PERF.md section 6, PR 41):
# the fastest (tm, tk, tn) of tm 16-256, tk and tn 256-2,048, ms a call.
#
#   rows  experts  gate/up (k 2048, n 1024)   down (k 1024, n 2048)
#    256     112   (64, 2048, 1024)  0.673    (128, 1024, 2048)  0.676
#   1024     127   (128, 2048, 1024) 0.782    (128, 1024, 2048)  0.786
#   2048     128   (128, 2048, 1024) 0.823    (128, 1024, 2048)  0.822
#   4096     128   (128, 2048, 1024) 0.877    (128, 1024, 2048)  0.888
#   8192     128   (128, 2048, 1024) 0.994    (128, 1024, 2048)  1.028
#
# k and n whole won at every row count, and tm 128 won or came within
# 0.05 % (tm 128 at 256 rows: 0.673). A tile taller than an expert's rows
# computes rows it throws away, but the product is bound by reading the
# experts' weights, and fewer, taller visits cost fewer grid steps.
TM = 128
MAX_TK = 2048


def tiling(m: int, k: int, n: int, itemsize: int = 2) -> tuple:
    """(tm, tk, tn) of a product of ``m`` rows, ``k`` deep and ``n``
    wide: the sweep's winner where it measured, and its rule elsewhere.
    k whole up to ``MAX_TK``: with one k tile the grid steps of one
    expert in consecutive m tiles ask for the same ``rhs`` block, which
    is then not fetched again, so each expert's weights are read once
    per n tile. n as wide as ``MAX_RHS_BLOCK_BYTES`` allows. tm ``TM``,
    or m rounded up to 16 where that is less."""
    tk = _divisor_tile(k, MAX_TK)
    tn = _divisor_tile(n, MAX_RHS_BLOCK_BYTES // (tk * itemsize))
    return min(TM, -(-m // 16) * 16), tk, tn


def _metadata(group_sizes, m: int, tm: int):
    """(group offsets [E + 1], the group of each visit, the m tile of
    each visit), and the number of visits.

    A group visits every m tile from the one holding its first row to
    the one holding its last, in order, and an empty group none, so
    visits of one m tile are consecutive. There are at most
    ``m // tm + E - 1`` visits: each group but the first can start
    inside a tile that the group before it ends in."""
    E = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes, dtype=jnp.int32)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    starts = offsets[:-1]
    visits = jnp.where(group_sizes > 0, (ends + tm - 1) // tm - starts // tm,
                       0).astype(jnp.int32)
    length = m // tm + E - 1
    group_ids = jnp.repeat(jnp.arange(E, dtype=jnp.int32), visits,
                           total_repeat_length=length)
    first_visit = jnp.cumsum(visits) - visits
    m_tile_ids = (jnp.take(starts // tm, group_ids)
                  + jnp.arange(length, dtype=jnp.int32)
                  - jnp.take(first_visit, group_ids))
    return (offsets, group_ids, m_tile_ids.astype(jnp.int32)), visits.sum()


@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def _gmm(lhs, rhs, group_sizes, tiles, interpret):
    m, k = lhs.shape
    n = rhs.shape[2]
    tm, tk, tn = tiles
    tiles_k = k // tk
    meta, n_visits = _metadata(group_sizes, m, tm)
    out_dtype = lhs.dtype

    def kernel(offsets, group_ids, m_tile_ids, lhs_ref, rhs_ref, out_ref,
               *acc):
        visit, k_i = pl.program_id(1), pl.program_id(2)

        def store(value):
            # Only the visit's group's rows: a tile that two groups share
            # keeps what the other group's visit stored.
            g = group_ids[visit]
            row = (m_tile_ids[visit] * tm
                   + jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 0))
            mine = (row >= offsets[g]) & (row < offsets[g + 1])
            out_ref[...] = jnp.where(
                mine, value, out_ref[...].astype(jnp.float32)
            ).astype(out_dtype)

        prod = jnp.dot(lhs_ref[...], rhs_ref[...],
                       preferred_element_type=jnp.float32)
        if tiles_k == 1:
            store(prod)
            return
        acc_ref, = acc

        @pl.when(k_i == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += prod

        @pl.when(k_i == tiles_k - 1)
        def _():
            store(acc_ref[...])

    def lhs_map(n_i, visit, k_i, offsets, group_ids, m_tile_ids):
        return m_tile_ids[visit], k_i

    def rhs_map(n_i, visit, k_i, offsets, group_ids, m_tile_ids):
        return group_ids[visit], k_i, n_i

    def out_map(n_i, visit, k_i, offsets, group_ids, m_tile_ids):
        return m_tile_ids[visit], n_i

    item = jnp.dtype(lhs.dtype).itemsize
    cost = pl.CostEstimate(
        flops=2 * m * k * n, transcendentals=0,
        bytes_accessed=(m * k * (n // tn) + rhs.shape[0] * k * n + m * n)
        * item)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                      pl.BlockSpec((None, tk, tn), rhs_map)],
            out_specs=pl.BlockSpec((tm, tn), out_map),
            grid=(n // tn, n_visits, tiles_k),
            scratch_shapes=[] if tiles_k == 1 else [
                pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        cost_estimate=cost,
        interpret=interpret,
        name=KERNEL,
    )(*meta, lhs, rhs)


def gmm(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array, *,
        tiles: Optional[tuple] = None) -> jax.Array:
    """``lhs [m, k]``, ``rhs [E, k, n]``, ``group_sizes [E]`` int32 ->
    ``[m, n]`` in the operands' dtype; rows past the groups' sum are
    not written. ``tiles`` (tm, tk, tn) defaults to ``tiling``'s choice
    for the shapes; it raises where ``untileable_reason`` names one."""
    reason = untileable_reason(lhs, rhs)
    if reason is not None:
        raise ValueError(f"moe_gmm cannot run this product: {reason}")
    m, k = lhs.shape
    n = rhs.shape[2]
    tiles = tiles or tiling(m, k, n, jnp.dtype(lhs.dtype).itemsize)
    tm, tk, tn = tiles
    if k % tk or n % tn or tm % 8:
        raise ValueError(f"tiles {tiles} do not divide (k {k}, n {n}) or "
                         f"tm is not a multiple of 8")
    interpret = jax.default_backend() == "cpu"
    pad = -m % tm
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    out = _gmm(lhs, rhs, group_sizes.astype(jnp.int32), tuple(tiles),
               interpret)
    return out[:m] if pad else out
