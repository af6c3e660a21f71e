"""Blocked (flash) attention as Pallas TPU kernels — forward AND backward.

The hot op of the transformer families (the reference has no compute kernels
at all — its hot loop is a 1 MB-chunk socket write, ``src/file_server.cc:68-77``).
Forward: Q is blocked over the grid, K/V stream through VMEM in ``block_k``
tiles with online-softmax accumulation in fp32, so the [T, S] score matrix
never hits HBM — the HBM-bandwidth win flash attention exists for.
Scores/accumulation run on the MXU via ``dot_general`` with
``preferred_element_type=float32``. A block does per score element only
what it needs (PR 32): one that no mask can touch (``_block_kind``:
*interior*) takes a path with no iota, compare or select, in all three
kernels; the forward's running maximum and sum never leave the
[block_q, 128] layout of their scratch (a [block_q] vector and the
[block_q, 1] column the scores need are different layouts, and changing
between them some eight times a block was 2.4 ms of the forward's 6.5 at
the training cell's shape); and a block above the causal diagonal fetches
no K or V.

Backward: two Pallas kernels recomputing scores from the saved logsumexp —
``dq`` (grid over Q blocks, K/V streaming) and ``dkv`` (grid over K/V
blocks, Q/dO streaming) — the standard flash-attention-2 recompute split.
[T, S] never materializes in either direction.

Key-padding masks are first-class kernel inputs (a [B, S] validity row,
which is exactly BERT's ``attn_mask[:, None, None, :]`` broadcast — VERDICT
round 1 item 4: BERT used to silently fall back to dense). GQA reads the
shared KV head via the BlockSpec index map — grouped K/V are never
expanded in HBM. A call the kernels can't run (sequence not a multiple of
the block size, non-padding mask forms, a mesh layout that can't stay
device-local) raises: ``untileable_reason`` is the one test, and the
``auto`` dispatcher in ``ops/attention.py`` asks it before choosing flash.

Numerics note: a K block can be entirely masked (all padding) yet still be
visited, making every score ``_NEG``; ``exp(s - m)`` with ``m == _NEG``
would then be exp(0) = 1, silently corrupting the softmax (and producing
inf/NaN through the backward's ``exp(s - lse)``). Both directions therefore
zero probabilities where ``s`` is at the mask floor. Queries with NO valid
key produce output 0 and garbage lse; that is fine for padding queries
because their upstream gradient is zero (the loss masks them), which the
zero-probability guard keeps NaN-free.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NEG = -1e30
# The largest candidate that divides the length is the block, for Q and
# for K alike. Measured on one TPU v5 lite at the training cell's shape
# (B 2, H 32 over K 8, T = S = 4,096, D 128, bf16, causal; one call, host
# clock over 20 calls; my chip runs, PR 32), forward | dq + dk/dv:
# 128 blocks 24.5 | 43.5 ms, 256 8.08 | 19.6, 512 3.61 | 9.67 (least times
# 1.40 | 3.49). A grid step costs some 0.5 us whether its block computes
# or is skipped (4,096 empty steps: 2.05 ms), which is what small blocks
# pay. Not candidates: 1,024 x 1,024 read 3.18 | 8.52 and 512 x 1,024
# 3.53 | 8.85, a tenth less, at four times and twice the score tile in
# VMEM and with half the causal blocks on the masked path.
_BLOCK_CANDIDATES = (512, 256, 128)

# The kernels' names are an interface: ``pallas_call(name=)`` puts them
# into the HLO instruction's name (``flash_fwd.<n>``; without it all
# three read ``attn.<n>``, the calling flax module's scope), which is how
# a device trace and the benchmark's ``device_ops`` tell forward, dq and
# dk/dv apart. ``tests/test_program_names.py`` pins them.
FWD_KERNEL = "flash_fwd"
BWD_DQ_KERNEL = "flash_bwd_dq"
BWD_DKV_KERNEL = "flash_bwd_dkv"


def _pick_block(n: int):
    for c in _BLOCK_CANDIDATES:
        if n % c == 0:
            return c
    return None


def _block_kind(i, j, block_q, block_k, causal, mask_mode, vlen):
    """(active, interior) of the block of Q block ``i`` and K block ``j``.

    *Active*: some score of the block can be unmasked, so it is computed;
    the others are skipped. *Interior*: no score of it can be masked, so
    it takes the path with no iota, compare or select. Decided from the
    block's position, the causal flag, the mode and the valid length
    alone, with operators that Python ints, numpy arrays
    (``block_census``) and the kernels' traced scalars all take: this is
    the one definition all three kernels and the census share. A "rows"
    block is never interior (its mask is data); a padded Q block
    (``i * block_q >= vlen``) is skipped in the "len" mode only, where q
    and k share positions (see ``_fwd_kernel``)."""
    active = interior = True
    if causal:
        active = j * block_k <= (i + 1) * block_q - 1
        interior = (j + 1) * block_k - 1 <= i * block_q
    if vlen is not None:
        active = active & (j * block_k < vlen)
        interior = interior & ((j + 1) * block_k <= vlen)
        if mask_mode == "len":
            active = active & (i * block_q < vlen)
    # A plain False, so that ``_per_kind`` emits no unmasked body at all.
    return active, interior if mask_mode != "rows" else False


def block_census(T, S, block_q, block_k, causal, vlen=None, mask_mode=None):
    """{"skipped", "interior", "masked"}: how many of the
    ``(T // block_q) x (S // block_k)`` blocks of one (batch row, head)
    the kernels skip, run on the unmasked path and run on the masked
    path. Shapes decide it, so nothing counts at run time. ``vlen`` is
    that batch row's valid length; ``mask_mode`` defaults to "len" with
    a length and "none" without."""
    import numpy as np

    if mask_mode is None:
        mask_mode = "none" if vlen is None else "len"
    i = np.arange(T // block_q)[:, None]
    j = np.arange(S // block_k)[None, :]
    active, interior = _block_kind(i, j, block_q, block_k, causal, mask_mode,
                                   vlen)
    active = np.broadcast_to(active, (i.size, j.size))
    interior = np.broadcast_to(interior, (i.size, j.size)) & active
    return {"skipped": int((~active).sum()), "interior": int(interior.sum()),
            "masked": int((active & ~interior).sum())}


def _per_kind(active, interior, compute):
    """Run ``compute(masked)`` on an active block: ``masked=False`` where
    the block is interior. A kind that the call's shape rules out is not
    emitted."""
    if interior is not False:
        pl.when(jnp.logical_and(active, interior))(lambda: compute(False))
    if interior is not True:
        pl.when(jnp.logical_and(active, jnp.logical_not(interior)))(
            lambda: compute(True))


def _probs(s, ref, masked):
    """exp(s - ref); on the masked path mask-floor scores are exactly zero
    probability (see numerics note in the module docstring)."""
    p = jnp.exp(s - ref)
    return jnp.where(s <= _NEG * 0.5, 0.0, p) if masked else p


def _scores(q, k, scale, masked, *, q0=0, k0=0, causal=False, vlen=None,
            valid=None):
    """[rows of q, rows of k] fp32 scores. ``masked``: the causal and
    padding masks are applied (``q0`` / ``k0`` are the positions of q's
    and k's first rows); an interior block (``_block_kind``) passes False
    and gets ``qk * scale`` alone, the same bits the masked path gives it.

    Two padding-mask mechanisms:
    * ``vlen`` (suffix padding, the common case): a per-row valid length
      read from SMEM — masking is the same iota-compare as causal, and
      the caller skips fully-padded blocks outright.
    * ``valid`` (arbitrary [B, S] masks): this block's row of the batch
      row's mask, which the kernel holds ENTIRE as [1, n_k, block_k]
      (index map (b, 0, 0), revisited so the DMA only fires when b
      advances; a (1, 1, block_k) tile re-DMAs 2 KB every innermost
      step, and a [B, block_k] tile forces a dynamic-sublane gather). It
      is ADDED as a [1, block_k] row of 0 and ``_NEG`` (``s + _NEG`` is
      ``_NEG`` to the bit for any score that is one), not selected by: a
      select on a mask row broadcast down the sublanes made the forward
      2.0-2.6x the unmasked call on this chip, the added row 1.01-1.02x
      (TPU v5 lite, forward alone, PR 32: B 16 H 12 T 512 D 64 1.16 ms
      selected, 0.452 added, 0.442 unmasked; B 2 H 32/8 T 4,096 D 128
      21.4, 4.63, 4.57).
    """
    # q/k stay in storage dtype (bf16 on TPU): the MXU runs bf16 inputs at
    # full rate with fp32 accumulation; upcasting first would halve it.
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if not masked:
        return s
    if valid is not None:
        s = s + jnp.where(valid, 0.0, _NEG)[None, :]
    if causal:
        q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos <= q_pos, s, _NEG)
    if vlen is not None:
        k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(k_pos < vlen, s, _NEG)
    return s


def _mask_row(mask_ref, j):
    """K block ``j``'s [block_k] row of a "rows" call's mask, as bools."""
    return None if mask_ref is None else mask_ref[0, j, :] != 0


def _lanes(x, n: int):
    """A [rows, 128] array whose lanes all hold the row's value, as
    [rows, n]."""
    if n <= x.shape[1]:
        return x[:, :n]
    if n % x.shape[1] == 0:
        return jnp.tile(x, (1, n // x.shape[1]))
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _causal_last_j(i, block_q, block_k):
    """Last K block that Q block ``i`` attends to under the causal mask."""
    return ((i + 1) * block_q - 1) // block_k


def _fwd_kernel(*refs, scale: float, causal: bool, mask_mode: str):
    vlen_ref = mask_ref = None
    if mask_mode in ("len", "klen"):
        vlen_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    elif mask_mode == "rows":
        q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    # Grid (B, H, n_q, n_k) with K/V STREAMED: per grid step only one
    # [block_k, D] tile of K and V is resident in VMEM (the whole point of
    # flash attention — full-S K/V would blow the ~16 MB VMEM at long
    # sequences). Online-softmax state lives in VMEM scratch, which persists
    # across the innermost (j) grid iterations.
    i = pl.program_id(2)
    j = pl.program_id(3)
    n_k = pl.num_programs(3)
    block_q = q_ref.shape[2]
    block_k = k_ref.shape[2]
    D = q_ref.shape[3]
    # Last K/V block this Q block attends to: the final write keys off it
    # (blocks fully above the causal diagonal are skipped, ``_block_kind``).
    if causal:
        last_j = jnp.minimum(n_k - 1, _causal_last_j(i, block_q, block_k))
    else:
        last_j = n_k - 1
    vlen = vlen_ref[pl.program_id(0)] if vlen_ref is not None else None
    # Skipped: blocks above the causal diagonal; fully-padded K blocks
    # (this is where suffix padding becomes FREE, not just correct); and,
    # in SELF-attention only ("len": q and kv share positions), Q blocks
    # of padding queries, whose outputs are loss-masked. A skipped Q
    # block's output is zeros via the unconditional init+finalize; its lse
    # is garbage, which is safe ONLY because the backward kernels skip the
    # same blocks. Ring hops use "klen": their q is a DIFFERENT sequence
    # shard than the kv the lengths describe, so every q block computes.
    active, interior = _block_kind(i, j, block_q, block_k, causal, mask_mode,
                                   vlen)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    # m and l live as [block_q, 128] and are never read as one column: m
    # holds the row's maximum in every lane, l 128 partial sums per row
    # that ``_finalize`` adds up, so the row sum costs no reduction across
    # lanes per block, and no statistic changes layout between a
    # [block_q] vector and the [block_q, 1] column the scores need.
    lanes = l_ref.shape[1]

    def _compute(masked):
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        s = _scores(q, k, scale, masked, q0=i * block_q, k0=j * block_k,
                    causal=causal, vlen=vlen,
                    valid=_mask_row(mask_ref, j))
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = _probs(s, _lanes(m_new, block_k), masked)
        alpha = jnp.exp(jnp.maximum(m_prev - m_new, _NEG))
        if block_k % lanes == 0:
            l_add = functools.reduce(jnp.add, (
                p[:, c:c + lanes] for c in range(0, block_k, lanes)))
        else:  # a block that is no whole number of lanes: lane 0 sums
            l_add = jnp.pad(p.sum(axis=-1, keepdims=True),
                            ((0, 0), (0, lanes - 1)))
        l_ref[...] = l_ref[...] * alpha + l_add
        acc_ref[...] = acc_ref[...] * _lanes(alpha, D) + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    _per_kind(active, interior, _compute)

    @pl.when(j == last_j)
    def _finalize():
        l = jnp.maximum(l_ref[...].sum(axis=-1, keepdims=True), 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)
        # lse is laid out [1, 1, n_q, block_q] (whole (n_q, block_q) tail —
        # Mosaic rejects (1, block_q) tails, and a dynamic LANE offset
        # store is unimplemented; a dynamic SUBLANE index is fine).
        lse_ref[0, 0, i, :] = (m_ref[:, :1] + jnp.log(l))[:, 0]


def _mask_operand(mask_arg, mask_mode, B, S, block_k):
    """(extra_specs_front, extra_specs_back, args_front, args_back)."""
    from jax.experimental.pallas import tpu as pltpu

    if mask_mode in ("len", "klen"):
        return ([pl.BlockSpec(memory_space=pltpu.SMEM)], [],
                [mask_arg.astype(jnp.int32)], [])
    if mask_mode == "rows":
        return ([], [pl.BlockSpec((1, S // block_k, block_k),
                                  lambda b, h, i, j: (b, 0, 0))],
                [], [mask_arg.reshape(B, S // block_k, block_k)])
    return [], [], [], []


# Forward and backward are jitted so that a program which calls them from
# every layer traces and lowers each once: a model whose layers are unrolled
# otherwise traces the kernels' bodies and serialises them again at every
# call site, on every start, compile cache or not (the training cell's 63
# call sites: 2.5 s of lowering, 0.2 s so; a second body per kernel, the
# interior path, had made it 3.4-4.3 s). The kernels' instruction names
# in the compiled program are what they were (``flash_fwd.<n>``).
_kernel_jit = functools.partial(
    jax.jit, static_argnums=(4,),
    static_argnames=("causal", "block_q", "block_k", "interpret"))


@_kernel_jit
def _flash_fwd_bhsd(q, k, v, mask_arg, mask_mode, *, causal: bool,
                    block_q: int, block_k: int, interpret: bool):
    """q [B,H,T,D]; k,v [B,K,S,D] with H % K == 0 (GQA via index map).
    ``mask_arg``: [B] valid lengths ("len" mode: self-attention suffix
    padding, q and k blocks both skipped; "klen": lengths describe the
    KEYS only — ring hops, where q is a different sequence shard) or
    [B, S] rows ("rows").
    Returns (out [B,H,T,D], lse [B,H,n_q,block_q])."""
    from jax.experimental.pallas import tpu as pltpu

    B, H, T, D = q.shape
    K, S = k.shape[1], k.shape[2]
    group = H // K
    scale = D ** -0.5
    grid = (B, H, T // block_q, S // block_k)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               mask_mode=mask_mode)
    sf, sb, af, ab = _mask_operand(mask_arg, mask_mode, B, S, block_k)

    def kv_index(b, h, i, j):
        # A block above the causal diagonal is skipped; naming the row's
        # last active block again makes the pipeline see an unchanged
        # block index and fetch nothing for it.
        if causal:
            j = jnp.minimum(j, _causal_last_j(i, block_q, block_k))
        return (b, h // group, j, 0)

    qspec = pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0))
    kspec = pl.BlockSpec((1, 1, block_k, D), kv_index)
    out, lse = pl.pallas_call(
        kernel,
        name=FWD_KERNEL,
        grid=grid,
        in_specs=sf + [qspec, kspec, kspec] + sb,
        out_specs=[
            qspec,
            pl.BlockSpec((1, 1, T // block_q, block_q),
                         lambda b, h, i, j: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, T // block_q, block_q), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),    # acc
            pltpu.VMEM((block_q, 128), jnp.float32),  # running max, every lane
            pltpu.VMEM((block_q, 128), jnp.float32),  # running sum, per lane
        ],
        interpret=interpret,
    )(*af, q, k, v, *ab)
    return out, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(*refs, scale: float, causal: bool, mask_mode: str):
    vlen_ref = mask_ref = None
    if mask_mode in ("len", "klen"):
        (vlen_ref, q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
         dq_ref, acc_ref) = refs
    elif mask_mode == "rows":
        (q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, mask_ref,
         dq_ref, acc_ref) = refs
    else:
        (q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
         dq_ref, acc_ref) = refs
    i = pl.program_id(2)
    j = pl.program_id(3)
    n_k = pl.num_programs(3)
    block_q = q_ref.shape[2]
    block_k = k_ref.shape[2]
    if causal:
        last_j = jnp.minimum(n_k - 1, _causal_last_j(i, block_q, block_k))
    else:
        last_j = n_k - 1
    vlen = vlen_ref[pl.program_id(0)] if vlen_ref is not None else None
    # The forward's skips, mirrored: padded Q rows get dq = 0 (see
    # _fwd_kernel).
    active, interior = _block_kind(i, j, block_q, block_k, causal, mask_mode,
                                   vlen)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _compute(masked):
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        g = g_ref[0, 0]
        s = _scores(q, k, scale, masked, q0=i * block_q, k0=j * block_k,
                    causal=causal, vlen=vlen,
                    valid=_mask_row(mask_ref, j))
        lse = lse_ref[0, 0, i, :]
        delta = delta_ref[0, 0, i, :]
        p = _probs(s, lse[:, None], masked)
        dp = jax.lax.dot_general(g, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[:, None]) * scale).astype(k.dtype)
        acc_ref[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _per_kind(active, interior, _compute)

    @pl.when(j == last_j)
    def _fin():
        dq_ref[0, 0] = acc_ref[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale: float, causal: bool, mask_mode: str):
    vlen_ref = mask_ref = None
    if mask_mode in ("len", "klen"):
        (vlen_ref, q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
    elif mask_mode == "rows":
        (q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref, mask_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
    else:
        (q_ref, k_ref, v_ref, g_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
    # Grid (B, H, n_k, n_q): K/V block fixed per middle index, Q/dO stream
    # through the innermost index, dK/dV accumulate in VMEM scratch.
    j = pl.program_id(2)
    i = pl.program_id(3)
    n_q = pl.num_programs(3)
    block_q = q_ref.shape[2]
    block_k = k_ref.shape[2]
    vlen = vlen_ref[pl.program_id(0)] if vlen_ref is not None else None
    # The forward's skips, mirrored. A fully-padded K block receives zero
    # gradient. In self-attention ("len") a fully-padded Q block MUST be
    # skipped — the forward skipped it, so its saved lse is garbage and
    # exp(s - lse) would be inf (NaN through 0*inf). "klen" (ring hops)
    # computes every q block, and its forward wrote real lse.
    active, interior = _block_kind(i, j, block_q, block_k, causal, mask_mode,
                                   vlen)

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _compute(masked):
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        g = g_ref[0, 0]
        s = _scores(q, k, scale, masked, q0=i * block_q, k0=j * block_k,
                    causal=causal, vlen=vlen,
                    valid=_mask_row(mask_ref, j))
        lse = lse_ref[0, 0, i, :]
        delta = delta_ref[0, 0, i, :]
        p = _probs(s, lse[:, None], masked)  # [block_q, block_k]
        dv_acc[...] += jax.lax.dot_general(
            p.astype(g.dtype), g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(g, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[:, None]) * scale).astype(q.dtype)
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _per_kind(active, interior, _compute)

    @pl.when(i == n_q - 1)
    def _fin():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


@_kernel_jit
def _flash_bwd_bhsd(q, k, v, mask_arg, mask_mode, lse, g, out, *,
                    causal: bool, block_q: int, block_k: int,
                    interpret: bool, g_lse=None):
    """Pallas backward. q,g,out [B,H,T,D]; k,v [B,K,S,D]. Returns
    (dq [B,H,T,D], dk, dv [B,K,S,D]).

    ``g_lse`` is the cotangent of the forward's logsumexp output (same
    [B, H, n_q, block_q] layout), for callers that consume lse (ring
    attention's cross-hop merge). It folds into the existing kernels for
    free: d lse_i / d s_ij = p_ij, so the ds term p*(dp - delta) becomes
    p*(dp - delta + g_lse) — i.e. delta_eff = delta - g_lse.
    """
    from jax.experimental.pallas import tpu as pltpu

    B, H, T, D = q.shape
    K, S = k.shape[1], k.shape[2]
    group = H // K
    scale = D ** -0.5
    # delta = rowsum(dO * O), laid out like lse: [B, H, n_q, block_q].
    delta = (g.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1)
    delta = delta.reshape(B, H, T // block_q, block_q)
    if g_lse is not None:
        delta = delta - g_lse.astype(jnp.float32)
    sf, sb, af, ab = _mask_operand(mask_arg, mask_mode, B, S, block_k)

    qspec = pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0))
    kspec = pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j: (b, h // group, j, 0))
    statspec = pl.BlockSpec((1, 1, T // block_q, block_q),
                            lambda b, h, i, j: (b, h, 0, 0))
    in_specs = sf + [qspec, kspec, kspec, qspec, statspec, statspec] + sb
    args = af + [q, k, v, g, lse, delta] + ab
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          mask_mode=mask_mode),
        name=BWD_DQ_KERNEL,
        grid=(B, H, T // block_q, S // block_k),
        in_specs=in_specs,
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
    )(*args)

    # dkv grid: (B, H, n_k, n_q) — Q streams innermost.
    qspec2 = pl.BlockSpec((1, 1, block_q, D), lambda b, h, j, i: (b, h, i, 0))
    kspec2 = pl.BlockSpec((1, 1, block_k, D),
                          lambda b, h, j, i: (b, h // group, j, 0))
    statspec2 = pl.BlockSpec((1, 1, T // block_q, block_q),
                             lambda b, h, j, i: (b, h, 0, 0))
    dkspec = pl.BlockSpec((1, 1, block_k, D), lambda b, h, j, i: (b, h, j, 0))
    sb2 = ([pl.BlockSpec((1, S // block_k, block_k),
                         lambda b, h, j, i: (b, 0, 0))]
           if mask_mode == "rows" else [])
    in_specs2 = sf + [qspec2, kspec2, kspec2, qspec2, statspec2,
                      statspec2] + sb2
    args2 = af + [q, k, v, g, lse, delta] + ab
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          mask_mode=mask_mode),
        name=BWD_DKV_KERNEL,
        grid=(B, H, S // block_k, T // block_q),
        in_specs=in_specs2,
        out_specs=[dkspec, dkspec],
        out_shape=[jax.ShapeDtypeStruct((B, H, S, D), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, S, D), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, D), jnp.float32)],
        interpret=interpret,
    )(*args2)
    if group > 1:
        # Grouped heads share K/V: reduce the per-q-head partials.
        dk = dk_h.reshape(B, K, group, S, D).sum(2)
        dv = dv_h.reshape(B, K, group, S, D).sum(2)
    else:
        dk, dv = dk_h, dv_h
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------------
# custom-vjp core + public wrapper
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_core(q, k, v, mask_arg, mask_mode, causal, block_q, block_k,
                interpret):
    out, _ = _flash_fwd_bhsd(q, k, v, mask_arg, mask_mode, causal=causal,
                             block_q=block_q, block_k=block_k,
                             interpret=interpret)
    return out


def _flash_core_fwd(q, k, v, mask_arg, mask_mode, causal, block_q, block_k,
                    interpret):
    out, lse = _flash_fwd_bhsd(q, k, v, mask_arg, mask_mode, causal=causal,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret)
    return out, (q, k, v, mask_arg, out, lse)


def _flash_core_bwd(mask_mode, causal, block_q, block_k, interpret, res, g):
    q, k, v, mask_arg, out, lse = res
    dq, dk, dv = _flash_bwd_bhsd(q, k, v, mask_arg, mask_mode, lse, g, out,
                                 causal=causal, block_q=block_q,
                                 block_k=block_k, interpret=interpret)
    return dq, dk, dv, None


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def flash_with_lse_bhsd(q, k, v, mask_arg, mask_mode, causal, block_q,
                        block_k, interpret):
    """Forward flash in [B,H,T,D]/[B,K,S,D] layout returning BOTH the
    output and the logsumexp [B, H, T] — the building block ring attention
    merges across hops. Differentiable in q/k/v including through lse
    (the lse cotangent folds into the backward's delta, see
    ``_flash_bwd_bhsd``).

    ``mask_arg``/``mask_mode`` follow ``_flash_core``'s contract ("none" |
    "len" | "klen" | "rows"); ring hops use "klen" to push per-hop local
    ``kv_lengths`` (suffix padding sliced to the hop's K/V shard) into the
    kernel instead of falling back to dense attention. Rows whose every
    key is invalid come back with lse ~= log(0) — callers gate those with
    their hop-visibility weighting."""
    out_lse, _ = _flash_with_lse_fwd(q, k, v, mask_arg, mask_mode, causal,
                                     block_q, block_k, interpret)
    return out_lse


def _flash_with_lse_fwd(q, k, v, mask_arg, mask_mode, causal, block_q,
                        block_k, interpret):
    out, lse = _flash_fwd_bhsd(q, k, v, mask_arg, mask_mode, causal=causal,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret)
    B, H, T, _ = q.shape
    return (out, lse.reshape(B, H, T)), (q, k, v, mask_arg, out, lse)


def _flash_with_lse_bwd(mask_mode, causal, block_q, block_k, interpret, res,
                        cts):
    q, k, v, mask_arg, out, lse = res
    g_out, g_lse = cts
    B, H, T, _ = q.shape
    dq, dk, dv = _flash_bwd_bhsd(
        q, k, v, mask_arg, mask_mode, lse, g_out, out, causal=causal,
        block_q=block_q, block_k=block_k, interpret=interpret,
        g_lse=g_lse.reshape(B, H, T // block_q, block_q))
    return dq, dk, dv, None


flash_with_lse_bhsd.defvjp(_flash_with_lse_fwd, _flash_with_lse_bwd)


def as_kv_mask(mask: Optional[jax.Array], B: int, S: int
               ) -> Optional[jax.Array]:
    """Reduce a general attention mask to the [B, S] key-padding row the
    kernels support, or None if it isn't one. Accepts [B, S] directly or
    the broadcast form [B, 1, 1, S]; boolean/integer dtypes only (a float
    mask could be additive — its zeros mean KEEP, the opposite of this
    nonzero-means-keep contract)."""
    if mask is None:
        return None
    if not (jnp.issubdtype(mask.dtype, jnp.integer)
            or jnp.issubdtype(mask.dtype, jnp.bool_)):
        return None
    if mask.ndim == 2 and mask.shape == (B, S):
        return mask.astype(jnp.int32)
    if mask.ndim == 4 and mask.shape == (B, 1, 1, S):
        return mask[:, 0, 0, :].astype(jnp.int32)
    return None


def _sharding_mesh():
    """The live multi-device mesh the kernel must be shard_mapped over, or
    None: no mesh, one device, or already inside an enclosing shard_map
    (a GPipe stage), where the data is device-local and nesting shard_map
    over the same mesh is an error."""
    from serverless_learn_tpu.parallel.compat import in_manual_region
    from serverless_learn_tpu.parallel.ring_attention import get_active_mesh

    mesh = get_active_mesh()
    if mesh is None or mesh.size == 1 or in_manual_region():
        return None
    return mesh


def untileable_reason(q, k, *, mask=None, kv_lengths=None,
                      block_q: Optional[int] = None,
                      block_k: Optional[int] = None) -> Optional[str]:
    """Why the kernels cannot run this call, or None when they can. The
    ``auto`` dispatcher asks this before it chooses flash, and
    ``flash_attention`` raises on it, so an explicit request never runs
    dense attention under the kernel's name."""
    from serverless_learn_tpu.parallel.mesh import live_batch_axes

    B, T, H, _ = q.shape
    S, K = k.shape[1], k.shape[2]
    if (mask is not None and kv_lengths is None
            and as_kv_mask(mask, B, S) is None):
        return (f"mask of shape {tuple(mask.shape)} / dtype {mask.dtype} is "
                f"not a [B, S] or [B, 1, 1, S] integer/bool key-padding row")
    block_q = block_q or _pick_block(T)
    block_k = block_k or _pick_block(S)
    if block_q is None or block_k is None or T % block_q or S % block_k:
        return (f"sequence lengths (q {T}, kv {S}) are not multiples of a "
                f"block size in {_BLOCK_CANDIDATES}")
    mesh = _sharding_mesh()
    if mesh is not None:
        # GSPMD has no partitioning rule for pallas_call, so every shard
        # must stay device-local under a shard_map over batch and heads.
        if mesh.shape.get("sp", 1) > 1:
            return "the mesh shards the sequence over sp (use impl='ring')"
        _, n_batch = live_batch_axes(mesh)
        tp = mesh.shape.get("tp", 1)
        if B % n_batch or H % tp or K % tp:
            return (f"batch {B} / heads {H} / kv heads {K} do not divide "
                    f"the mesh's data axes ({n_batch}) and tp ({tp})")
    return None


def flash_attention(
    q: jax.Array,  # [B, T, H, D]
    k: jax.Array,  # [B, S, K, D]
    v: jax.Array,
    *,
    causal: bool = False,
    mask: Optional[jax.Array] = None,
    kv_lengths: Optional[jax.Array] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Flash attention in the framework's [B, T, H, D] convention; GQA KV
    heads are read through the kernel's index map (never expanded in HBM).

    Padding, fastest first:
    * ``kv_lengths`` [B] — keys at positions >= kv_lengths[b] are invalid
      (SUFFIX padding, the standard batch layout). Near-free masking (SMEM
      scalar + iota compare) and fully-padded blocks are skipped outright.
      The CALLER asserts suffix-ness; a non-suffix mask squeezed into
      lengths would be silently wrong.
    * ``mask`` [B, S] or [B, 1, 1, S] (nonzero = attend) — arbitrary
      per-key validity, in-kernel at 1.01-1.02x the unmasked forward
      (``_scores`` has the readings), with no block skipped.
    * other mask forms, and shapes the kernels can't tile, raise
      ``ValueError`` (see ``untileable_reason``).

    On a live multi-device mesh the kernel is shard_mapped over the batch
    (dp/fsdp) and head (tp) axes — GSPMD has no partitioning rule for
    ``pallas_call`` and would otherwise all-gather q/k/v onto every device
    and run the kernel fully replicated. Layouts the wrapper can't keep
    device-local (sp-sharded sequence, indivisible batch/heads) raise too."""
    B, T, H, D = q.shape
    S, K = k.shape[1], k.shape[2]
    reason = untileable_reason(q, k, mask=mask, kv_lengths=kv_lengths,
                               block_q=block_q, block_k=block_k)
    if reason is not None:
        raise ValueError(f"flash attention cannot run this call: {reason}")
    if kv_lengths is not None:
        mask_arg, mask_mode = kv_lengths.astype(jnp.int32), "len"
    else:
        kv_mask = as_kv_mask(mask, B, S)
        if kv_mask is not None:
            mask_arg, mask_mode = kv_mask, "rows"
        else:
            mask_arg, mask_mode = None, "none"
    block_q = block_q or _pick_block(T)
    block_k = block_k or _pick_block(S)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"

    def local(ql, kl, vl, ml=None):
        qt = ql.transpose(0, 2, 1, 3)
        kt = kl.transpose(0, 2, 1, 3)
        vt = vl.transpose(0, 2, 1, 3)
        out = _flash_core(qt, kt, vt, ml, mask_mode, causal, block_q,
                          block_k, interpret)
        return out.transpose(0, 2, 1, 3)

    mesh = _sharding_mesh()
    if mesh is None:
        if mask_arg is not None:
            return local(q, k, v, mask_arg)
        return local(q, k, v)
    from jax.sharding import PartitionSpec as P

    from serverless_learn_tpu.parallel.compat import shard_map_no_check
    from serverless_learn_tpu.parallel.mesh import live_batch_axes

    batch_axes, _ = live_batch_axes(mesh)
    tp = mesh.shape.get("tp", 1)
    spec = P(batch_axes or None, None, "tp" if tp > 1 else None, None)
    if mask_arg is not None:
        mspec = (P(batch_axes or None) if mask_mode in ("len", "klen")
                 else P(batch_axes or None, None))
        fn = shard_map_no_check(local, mesh=mesh,
                                in_specs=(spec, spec, spec, mspec),
                                out_specs=spec)
        return fn(q, k, v, mask_arg)
    fn = shard_map_no_check(lambda a, b, c: local(a, b, c), mesh=mesh,
                            in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)
