"""Ring attention — sequence/context parallelism over the ``sp`` mesh axis.

Long-context support the reference never had (its "model" is a flat double
vector, ``src/protos/serverless_learn.proto:81-83``; SURVEY.md §5 records
long-context as absent). Design: the sequence dimension is sharded over the
``sp`` axis; each device holds a [B, T/n, H, D] shard of Q and streams K/V
shards around an ICI ring with ``lax.ppermute`` while merging per-hop
softmax statistics online, so the full [T, T] score matrix never
materializes and each hop is nearest-neighbor.

Round-2 redesign (VERDICT round 1 item 9):

* Each hop runs the BLOCKED flash kernel (``flash_with_lse_bhsd``) on the
  resident K/V shard instead of a dense [T_loc, T_loc] fp32 einsum —
  per-device attention memory drops from O(T_loc^2) to O(T_loc x block),
  which is the entire point at 32k+ context. Hops combine by logsumexp
  merge of (out, lse); the merge is differentiable and the kernel's custom
  VJP folds the lse cotangent into its existing backward.
* GQA K/V stay UNEXPANDED on the wire: the ring carries [B, T_loc, K, D]
  shards (K = kv heads), cutting ring traffic by H/K; the flash kernel
  reads the shared head through its BlockSpec index map, and the dense
  fallback uses a grouped einsum.
* Shapes the kernel can't tile (T_loc not 128-divisible) or non-TPU/CPU
  backends fall back to a grouped-dense hop — same math, old memory.

Causal masking across hops, round-3 upgrades (VERDICT r2 item 6):

* **Zigzag schedule** (default for causal): inputs are re-dealt so each
  device owns one early and one late half-block; every causal hop is then
  exactly two visible half-pairs on every device — balanced, and ~half
  the hop compute of the contiguous schedule (which computed hidden hops
  only to discard them). See ``_ring_attention_zigzag``.
* **Suffix padding through the ring**: global ``kv_lengths`` slice to
  per-hop local lengths and ride the flash kernel's "len" mode, so sp>1
  with padded batches stays on the ring path instead of falling back to
  GSPMD-partitioned dense attention (the exact [T, T] materialization sp
  exists to avoid).

Works inside ``jit``: the public entry wraps the per-shard kernel in
``shard_map`` over the active mesh (registered by ``build_trainer``), so the
same model code runs sp=1 (no-op) or sp=N by changing the mesh shape.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from serverless_learn_tpu.parallel.compat import (
    shard_map_no_check as _shard_map)

_NEG = -1e30  # finite "minus infinity": avoids NaN from (-inf) - (-inf)

_ACTIVE_MESH: Optional[Mesh] = None


def set_active_mesh(mesh: Optional[Mesh]) -> None:
    """Register the mesh ring attention should shard_map over. Called by
    ``build_trainer``; one active mesh per process (the elastic controller
    re-registers on re-mesh)."""
    global _ACTIVE_MESH
    _ACTIVE_MESH = mesh


def get_active_mesh() -> Optional[Mesh]:
    """The mesh registered by ``set_active_mesh`` (shared by the shard_map
    users inside model code: ring attention and the GPipe block stack)."""
    return _ACTIVE_MESH


def _dense_hop(q, k, v, *, causal: bool, scale: float, kv_len=None):
    """Grouped-dense hop: (normalized out [B,T,H,D], lse [B,H,T]) without
    expanding GQA K/V. Fallback for shapes the flash kernel can't tile.
    ``kv_len`` [B]: keys at local positions >= kv_len[b] are padding."""
    B, T, H, D = q.shape
    S = k.shape[1]
    K = k.shape[2]
    G = H // K
    qg = q.reshape(B, T, K, G, D)
    s = jnp.einsum("btkgd,bskd->bkgts", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    s = s.reshape(B, H, T, S)
    if causal:
        keep = jnp.tril(jnp.ones((T, S), bool))
        s = jnp.where(keep[None, None], s, _NEG)
    if kv_len is not None:
        keep = jnp.arange(S)[None, :] < kv_len[:, None]  # [B, S]
        s = jnp.where(keep[:, None, None, :], s, _NEG)
    m = s.max(axis=-1)
    p = jnp.exp(s - m[..., None])
    l = p.sum(axis=-1)
    pk = p.reshape(B, K, G, T, S)
    o = jnp.einsum("bkgts,bskd->btkgd", pk, v.astype(jnp.float32))
    o = o.reshape(B, T, H, D) / jnp.maximum(l, 1e-30).transpose(
        0, 2, 1)[..., None]
    return o, m + jnp.log(jnp.maximum(l, 1e-30))


def _flash_hop(q, k, v, *, causal: bool, block: int, interpret: bool,
               kv_len=None):
    """Blocked hop via the Pallas kernel (GQA through the index map).
    ``kv_len`` rides the kernel's "len" mask mode — suffix padding is
    masked in-kernel and fully-padded key blocks are skipped."""
    from serverless_learn_tpu.ops.pallas.flash_attention import (
        flash_with_lse_bhsd)

    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    if kv_len is None:
        out, lse = flash_with_lse_bhsd(qt, kt, vt, None, "none", causal,
                                       block, block, interpret)
    else:
        # "klen", not "len": the lengths describe the RESIDENT KV SHARD,
        # while q is a different sequence shard — the self-attention "len"
        # mode would skip valid q blocks whose index exceeds the kv
        # shard's local length (silently dropping the hop's keys for
        # those rows).
        out, lse = flash_with_lse_bhsd(qt, kt, vt,
                                       kv_len.astype(jnp.int32), "klen",
                                       causal, block, block, interpret)
    return out.transpose(0, 2, 1, 3).astype(jnp.float32), lse


def _merge(o, lse, o_h, lse_h):
    """Combine two normalized partial attentions by their logsumexps."""
    m = jnp.maximum(lse, lse_h)
    a = jnp.exp(lse - m)
    b = jnp.exp(lse_h - m)
    denom = jnp.maximum(a + b, 1e-30)
    w_a = (a / denom).transpose(0, 2, 1)[..., None]  # [B,T,H,1]
    w_b = (b / denom).transpose(0, 2, 1)[..., None]
    return o * w_a + o_h * w_b, m + jnp.log(denom)


def _hop_lengths(kv_lengths, offset, size):
    """Global suffix lengths -> a K/V shard's local lengths: the shard
    covers global positions [offset, offset + size)."""
    if kv_lengths is None:
        return None
    return jnp.clip(kv_lengths - offset, 0, size).astype(jnp.int32)


def _gate_empty(lse, kv_len):
    """Rows whose K/V shard is fully padded must not contribute: their
    kernel lse is meaningless (all blocks skipped)."""
    if kv_len is None:
        return lse
    return jnp.where((kv_len > 0)[:, None, None], lse, _NEG)


def _ring_attention_local(q, k, v, kv_lengths, *, axis_name: str,
                          causal: bool, hop_fn):
    """Per-device kernel, CONTIGUOUS layout: device i holds sequence block
    i. q [B, T_loc, H, D]; k,v [B, T_loc, K, D] — GQA K/V ride the ring
    unexpanded. ``kv_lengths`` [B] are GLOBAL suffix lengths; each hop
    slices them to its resident block. Causal hidden hops still compute
    (gated in the merge) — the zigzag layout removes that waste."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    T_loc = q.shape[1]

    # Hop 0: the resident (diagonal) block — the only hop where causal
    # masking is positional rather than all-or-nothing.
    len0 = _hop_lengths(kv_lengths, idx * T_loc, T_loc)
    o, lse = hop_fn(q, k, v, causal=causal, kv_len=len0)
    lse = _gate_empty(lse, len0)

    def step(carry, s):
        o, lse, k_cur, v_cur = carry
        # Rotate first: hop s sees the block that started s devices behind.
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        block_idx = (idx - s) % n
        len_h = _hop_lengths(kv_lengths, block_idx * T_loc, T_loc)
        o_h, lse_h = hop_fn(q, k_cur, v_cur, causal=False, kv_len=len_h)
        lse_h = _gate_empty(lse_h, len_h)
        if causal:
            # Contiguous shards: an off-diagonal block is fully visible iff
            # it lies before this device's block. Hidden hops contribute
            # -inf lse, which the merge zero-weights.
            visible = block_idx < idx
            lse_h = jnp.where(visible, lse_h, _NEG)
        o, lse = _merge(o, lse, o_h, lse_h)
        return (o, lse, k_cur, v_cur), None

    if n > 1:
        (o, lse, _, _), _ = jax.lax.scan(
            step, (o, lse, k, v), jnp.arange(1, n))
    return o.astype(q.dtype)


def _zig_relayout(x, idx, n, axis_name, inverse=False):
    """Contiguous <-> zigzag half-block exchange.

    Contiguous: device i holds global half-blocks (2i, 2i+1). Zigzag:
    device i holds (i, 2n-1-i) — every device then owns one "early" and
    one "late" half, which is what balances causal hop work. Each half
    slot moves under its own bijective device permutation (two ppermutes),
    and devices with odd index swap their slots afterwards so slot 0 is
    always the early half. The inverse runs the same wiring backwards.
    ``x`` is [B, T_loc, ...]; halves split on axis 1."""
    B = x.shape[0]
    Th = x.shape[1] // 2
    h0, h1 = x[:, :Th], x[:, Th:]
    # Forward: contiguous half h lands on device (h if h < n else 2n-1-h).
    dest = lambda h: h if h < n else 2 * n - 1 - h
    perm_a = [(i, dest(2 * i)) for i in range(n)]
    perm_b = [(i, dest(2 * i + 1)) for i in range(n)]
    odd = idx % 2 == 1
    if not inverse:
        a = jax.lax.ppermute(h0, axis_name, perm_a)
        b = jax.lax.ppermute(h1, axis_name, perm_b)
        # On odd devices the early half arrived in slot b: swap.
        lo = jnp.where(odd, b, a)
        hi = jnp.where(odd, a, b)
        return jnp.concatenate([lo, hi], axis=1)
    # Inverse: undo the local swap, then run the inverse permutations.
    lo, hi = h0, h1
    a = jnp.where(odd, hi, lo)
    b = jnp.where(odd, lo, hi)
    inv = lambda p: [(d, s) for s, d in p]
    h0 = jax.lax.ppermute(a, axis_name, inv(perm_a))
    h1 = jax.lax.ppermute(b, axis_name, inv(perm_b))
    return jnp.concatenate([h0, h1], axis=1)


def _ring_attention_zigzag(q, k, v, kv_lengths, *, axis_name: str, hop_fn):
    """Causal ring attention in the ZIGZAG layout.

    With contiguous blocks, causal hop work is device-skewed: device i has
    i visible hops of n-1 (device 0 idles, device n-1 computes all) —
    wall-clock is set by the worst device while half the fleet's FLOPs are
    discarded. Zigzag gives device i half-blocks (i, 2n-1-i); at every hop
    exactly TWO of the four (q half x kv half) pairs are causally visible
    on EVERY device, so each hop is one uniform flash call over the two
    half-pairs stacked on the batch axis:

        j = (i - s) mod n owns the resident kv halves (j, 2n-1-j)
        j < i:  visible = (q_lo x kv_lo), (q_hi x kv_lo)
        j > i:  visible = (q_hi x kv_lo), (q_hi x kv_hi)

    Per causal hop that is HALF the all-pairs compute of the contiguous
    schedule, perfectly balanced. Inputs/outputs stay in the contiguous
    layout: the relayout (two half-block ppermutes in, two out) is
    amortized against (n-1) hops of halved compute.
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    B, T_loc = q.shape[:2]
    Th = T_loc // 2

    q = _zig_relayout(q, idx, n, axis_name)
    k = _zig_relayout(k, idx, n, axis_name)
    v = _zig_relayout(v, idx, n, axis_name)
    # This device's halves are global chunks (idx, 2n-1-idx); device j's
    # (rotated in) are (j, 2n-1-j) — the visibility algebra in `step`.
    q_lo, q_hi = q[:, :Th], q[:, Th:]

    def half_lens(j):
        """Local suffix lengths of kv halves (lo, hi) of device j."""
        lo = _hop_lengths(kv_lengths, j * Th, Th)
        hi = _hop_lengths(kv_lengths, (2 * n - 1 - j) * Th, Th)
        return lo, hi

    # Hop 0 (resident): diagonal on both halves (one causal call, halves
    # stacked on batch) + the always-visible (q_hi x kv_lo) full pair.
    kv_lo, kv_hi = k[:, :Th], k[:, Th:]
    vv_lo, vv_hi = v[:, :Th], v[:, Th:]
    len_lo, len_hi = half_lens(idx)
    qs = jnp.concatenate([q_lo, q_hi], axis=0)
    ks = jnp.concatenate([kv_lo, kv_hi], axis=0)
    vs = jnp.concatenate([vv_lo, vv_hi], axis=0)
    ls = None if kv_lengths is None else jnp.concatenate([len_lo, len_hi])
    o_d, lse_d = hop_fn(qs, ks, vs, causal=True, kv_len=ls)
    lse_d = _gate_empty(lse_d, ls)
    o_lo, lse_lo = o_d[:B], lse_d[:B]
    o_hi, lse_hi = o_d[B:], lse_d[B:]
    o_f, lse_f = hop_fn(q_hi, kv_lo, vv_lo, causal=False, kv_len=len_lo)
    lse_f = _gate_empty(lse_f, len_lo)
    o_hi, lse_hi = _merge(o_hi, lse_hi, o_f, lse_f)

    def step(carry, s):
        o_lo, lse_lo, o_hi, lse_hi, k_cur, v_cur = carry
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        j = (idx - s) % n
        early = j < idx  # kv owner is earlier in the ring than us
        kv_lo, kv_hi = k_cur[:, :Th], k_cur[:, Th:]
        vv_lo, vv_hi = v_cur[:, :Th], v_cur[:, Th:]
        len_lo, len_hi = half_lens(j)
        # One uniform call over the two visible half-pairs:
        #   early:  (q_lo x kv_lo), (q_hi x kv_lo)
        #   late:   (q_hi x kv_lo), (q_hi x kv_hi)
        q_sel = jnp.concatenate(
            [jnp.where(early, q_lo, q_hi), q_hi], axis=0)
        k_sel = jnp.concatenate(
            [kv_lo, jnp.where(early, kv_lo, kv_hi)], axis=0)
        v_sel = jnp.concatenate(
            [vv_lo, jnp.where(early, vv_lo, vv_hi)], axis=0)
        l_sel = (None if kv_lengths is None else
                 jnp.concatenate([len_lo, jnp.where(early, len_lo,
                                                    len_hi)]))
        o_p, lse_p = hop_fn(q_sel, k_sel, v_sel, causal=False, kv_len=l_sel)
        lse_p = _gate_empty(lse_p, l_sel)
        o0, lse0 = o_p[:B], lse_p[:B]
        o1, lse1 = o_p[B:], lse_p[B:]
        # Slot lo gets the early case's first pair, nothing otherwise.
        o_lo, lse_lo = _merge(o_lo, lse_lo, o0,
                              jnp.where(early, lse0, _NEG))
        # Slot hi: early -> the second pair only; late -> both pairs.
        o_m, lse_m = _merge(o0, jnp.where(early, _NEG, lse0), o1, lse1)
        o_hi, lse_hi = _merge(o_hi, lse_hi, o_m, lse_m)
        return (o_lo, lse_lo, o_hi, lse_hi, k_cur, v_cur), None

    if n > 1:
        (o_lo, lse_lo, o_hi, lse_hi, _, _), _ = jax.lax.scan(
            step, (o_lo, lse_lo, o_hi, lse_hi, k, v), jnp.arange(1, n))
    out = jnp.concatenate([o_lo, o_hi], axis=1)
    out = _zig_relayout(out, idx, n, axis_name, inverse=True)
    return out.astype(q.dtype)


def _auto_zigzag(causal: bool, n: int, t_loc: int) -> bool:
    """The "auto" layout policy. Zigzag halves the causal hop compute —
    but only adopt it when its half-blocks still hit the flash kernel (or
    flash is out of reach at full blocks too): trading the blocked kernel
    for dense half-hops would give back more than the balance wins at
    short T_loc. At long context (T_loc >= 256) both hold."""
    from serverless_learn_tpu.ops.pallas.flash_attention import _pick_block

    if not (causal and n > 1 and t_loc % 2 == 0):
        return False
    return _pick_block(t_loc // 2) is not None or _pick_block(t_loc) is None


def _local_ring_fn(T_loc: int, n: int, causal: bool, layout: str,
                   scale: float):
    """The per-shard ring body — ``f(q, k, v, lens) -> out`` on LOCAL
    sequence shards, hop kernel chosen for these shapes. Shared by the
    GSPMD entry below (which wraps it in shard_map) and
    ``ring_attention_manual`` (callers already inside a manual region,
    e.g. pipeline stages)."""
    from serverless_learn_tpu.ops.pallas.flash_attention import _pick_block

    def make_hop(span):
        block = _pick_block(span)
        if block is not None:
            return partial(_flash_hop, block=block,
                           interpret=jax.default_backend() == "cpu")
        return partial(_dense_hop, scale=scale)

    zig_ok = causal and n > 1 and T_loc % 2 == 0
    if layout == "zigzag":
        if not zig_ok:
            raise ValueError(
                f"zigzag layout needs causal attention, sp>1 and an even "
                f"per-device sequence (got causal={causal}, n={n}, "
                f"T_loc={T_loc})")
        zigzag = True
    elif layout == "auto":
        zigzag = _auto_zigzag(causal, n, T_loc)
    else:
        zigzag = False
    if zigzag:
        return partial(_ring_attention_zigzag, hop_fn=make_hop(T_loc // 2))
    return partial(_ring_attention_local, causal=causal,
                   hop_fn=make_hop(T_loc))


def ring_attention_manual(q, k, v, *, axis_name: str = "sp",
                          causal: bool = False, kv_lengths=None,
                          layout: str = "auto"):
    """Ring attention for callers ALREADY inside a manual region over
    ``axis_name`` — the pipeline's shard_map (round-4 pp x sp composition).

    q [B, T_loc, H, D]; k/v [B, T_loc, K, D] are this device's LOCAL
    sequence shards (global T = T_loc * axis size); ``kv_lengths`` [B] are
    GLOBAL suffix lengths (each hop slices its resident block's span).
    Same math and hop kernels as the public ``ring_attention``; only the
    shard_map wrapper is omitted."""
    n = jax.lax.axis_size(axis_name)
    local = _local_ring_fn(q.shape[1], n, causal, layout,
                           q.shape[-1] ** -0.5)
    lens = None if kv_lengths is None else kv_lengths.astype(jnp.int32)
    return local(q, k, v, lens, axis_name=axis_name)


def ring_attention(q, k, v, *, axis_name: str = "sp", causal: bool = False,
                   kv_lengths=None, layout: str = "auto",
                   mesh: Optional[Mesh] = None):
    """Sequence-parallel attention. q [B,T,H,D], k/v [B,T,K,D] (global
    logical shapes; T sharded over ``axis_name``).

    ``kv_lengths`` [B] — global SUFFIX padding lengths; each hop slices
    them to its resident K/V shard and pushes them into the flash kernel's
    "len" mode (padded batches no longer force the dense fallback).

    ``layout``: "auto" uses the zigzag half-block schedule for causal
    attention (balanced hop work, ~2x less causal hop compute — see
    ``_ring_attention_zigzag``) when the half-blocks are kernel-tileable,
    and the contiguous schedule otherwise; "contiguous"/"zigzag" force.
    """
    mesh = mesh or _ACTIVE_MESH
    if mesh is None:
        raise RuntimeError(
            "ring_attention needs an active mesh; call set_active_mesh() "
            "(build_trainer does this automatically)")
    H, K = q.shape[2], k.shape[2]
    if H % K:
        raise ValueError(f"n_heads {H} not divisible by kv_heads {K}")
    n = mesh.shape[axis_name]
    local = _local_ring_fn(q.shape[1] // n, n, causal, layout,
                           q.shape[-1] ** -0.5)
    tp = mesh.shape.get("tp", 1)
    if tp > 1 and K > 1 and K % tp:
        # Replicating kv over tp here would silently mis-group: each tp
        # member's LOCAL q heads are a slice of the global heads, but the
        # hop kernels derive the q->kv grouping from local indices starting
        # at kv head 0. MQA (K == 1) is the only safe replication.
        raise NotImplementedError(
            f"ring attention with tp={tp} needs kv_heads ({K}) divisible "
            f"by tp (or kv_heads == 1)")
    qspec = P(("dp", "fsdp"), axis_name, "tp", None)
    kvspec = P(("dp", "fsdp"), axis_name, "tp" if K > 1 else None, None)
    lspec = P(("dp", "fsdp"))
    local = partial(local, axis_name=axis_name)
    if kv_lengths is not None:
        fn = _shard_map(local, mesh=mesh,
                        in_specs=(qspec, kvspec, kvspec, lspec),
                        out_specs=qspec)
        return fn(q, k, v, kv_lengths.astype(jnp.int32))
    fn = _shard_map(lambda a, b, c: local(a, b, c, None), mesh=mesh,
                    in_specs=(qspec, kvspec, kvspec), out_specs=qspec)
    return fn(q, k, v)
