"""Pallas dequantize-inside-the-matmul kernel for weight-only int8 —
kept as a MEASURED NEGATIVE RESULT, off by default.

Hypothesis: the XLA lowering of ``x @ convert(w_int8)`` materializes the
converted bf16 weights through HBM (measured 0.85x of plain bf16 decode
on the v5e), so converting each int8 tile in VMEM on its way into the MXU
should recover the 2x byte win.

Measured (llama_1b b8 decode, v5e, two tuning rounds): the kernel runs
**0.61-0.66x** of bf16 — WORSE than the XLA convert path it was meant to
beat. Diagnosis: bf16 decode itself reaches only ~30% of HBM bandwidth
(12.3 ms/token vs the 3.7 ms the 3 GB weight read would cost), i.e.
decode at this scale is DISPATCH/FUSION-bound, not weight-bandwidth
bound — and a custom call forfeits XLA's fusion of the surrounding
elementwise work while adding per-tile overhead to 100+ small GEMVs per
token. Weight-only int8's real win on this chip is RESIDENT MEMORY
(1.5 GB vs 3 GB of params — fit a 2x larger model), which the default
XLA path already delivers; ``SLT_QUANT_PALLAS=1`` re-enables this kernel
for future re-tuning (a fatter chip or a fused decode step changes the
math).

Layout: ``x [R, I] @ wq [I, O] * scale [O] -> [R, O]`` with a
(O-blocks, I-blocks) grid, I minor (sequential) so each output tile's f32
partial sums live in a VMEM scratch accumulator across the I sweep.
Inference-only: generation never differentiates, so no custom VJP exists.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, wq_ref, s_ref, o_ref, acc_ref, *, n_i: int):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 tile -> bf16 in-register on its way into the MXU: the whole
    # point — HBM traffic for this tile was 1 byte/weight.
    w = wq_ref[...].astype(jnp.bfloat16)
    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.bfloat16), w,
                            preferred_element_type=jnp.float32)

    @pl.when(i == n_i - 1)
    def _emit():
        o_ref[...] = (acc_ref[...] * s_ref[...].astype(jnp.float32)
                      ).astype(o_ref.dtype)


def _pick_tiles(R: int, I: int, O: int):
    """(block_i, block_o) honoring MXU/VMEM geometry, or None.

    Prefer LARGE tiles: at decode row counts (R=8) each invocation is a
    skinny GEMV and the cost is dominated by per-tile overhead + DMA
    setup, so fewer, bigger weight tiles win (measured: 512x512 tiles ran
    0.6x of XLA; 2048-deep tiles are what recovers the int8 byte win)."""
    bi = next((b for b in (2048, 1024, 512, 256, 128) if I % b == 0), None)
    bo = next((b for b in (1024, 512, 256, 128) if O % b == 0), None)
    if bi is None or bo is None:
        return None

    # Scoped-VMEM budget (16 MB): inputs are DOUBLE-BUFFERED by the
    # pipeline (2x the x and w tiles), plus the f32 accumulator scratch
    # and the output tile. The first deploy omitted the 2x and OOM'd
    # scoped vmem at prefill row counts.
    def need(bi, bo):
        return (2 * (R * bi * 2 + bi * bo)  # x bf16 + w int8, buffered
                + R * bo * 4                # acc scratch
                + R * bo * 2)               # out tile

    while need(bi, bo) > 11 * 1024 * 1024:
        if bi > 128:
            bi //= 2
        elif bo > 128:
            bo //= 2
        else:
            return None
    return bi, bo


def quant_matmul(x: jax.Array, wq: jax.Array, scale: jax.Array,
                 out_dtype=None) -> jax.Array:
    """``x [..., I] @ wq [I, O] * scale [O]``: the XLA convert-then-dot
    form by default (see the module docstring for why it MEASURED faster
    than the kernel on v5e), the in-kernel dequant under
    ``SLT_QUANT_PALLAS=1`` — where a shape the kernel can't tile raises
    instead of quietly taking the XLA form."""
    import os

    out_dtype = out_dtype or x.dtype
    lead = x.shape[:-1]
    I, O = wq.shape
    R = 1
    for d in lead:
        R *= d
    if not os.environ.get("SLT_QUANT_PALLAS"):
        y = jnp.tensordot(x, wq.astype(x.dtype), axes=1)
        return (y * scale.astype(x.dtype)).astype(out_dtype)
    tiles = _pick_tiles(max(R, 8), I, O)
    if tiles is None or R > 4096:
        raise ValueError(
            f"SLT_QUANT_PALLAS=1 but the dequant kernel cannot tile "
            f"x [{R}, {I}] @ wq [{I}, {O}]")
    x2 = x.reshape(R, I)
    bi, bo = tiles
    # Pad rows to the 8-sublane tile (decode calls are R=batch, often < 8).
    Rp = max(8, -(-R // 8) * 8)
    if Rp != R:
        x2 = jnp.pad(x2, ((0, Rp - R), (0, 0)))
    n_i = I // bi
    out = pl.pallas_call(
        functools.partial(_kernel, n_i=n_i),
        grid=(O // bo, n_i),
        in_specs=[
            pl.BlockSpec((Rp, bi), lambda o, i: (0, i)),
            pl.BlockSpec((bi, bo), lambda o, i: (i, o)),
            pl.BlockSpec((1, bo), lambda o, i: (0, o)),
        ],
        out_specs=pl.BlockSpec((Rp, bo), lambda o, i: (0, o)),
        out_shape=jax.ShapeDtypeStruct((Rp, O), out_dtype),
        scratch_shapes=[pltpu.VMEM((Rp, bo), jnp.float32)],
        interpret=jax.default_backend() == "cpu",
    )(x2, wq, scale.reshape(1, O))
    return out[:R].reshape(*lead, O)
