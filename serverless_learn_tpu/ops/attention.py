"""Attention ops.

Single entry point ``dot_product_attention`` that dispatches between:

* ``auto`` — (default) ``xla`` below ``AUTO_FLASH_MIN_SEQ``, ``flash`` at or
  above it; thresholds measured on-chip (see constant below).
* ``xla``  — plain einsum attention; XLA fuses softmax into the matmuls well
  on TPU for moderate sequence lengths.
* ``flash`` — Pallas blocked flash-attention kernel (``ops/pallas``), for long
  sequences where the [T, T] score matrix would blow HBM bandwidth
  (measured 9x over ``xla`` at T=8192 on a v5e chip, fwd+bwd).
* ``ring`` — sequence-parallel ring attention over the mesh's ``sp`` axis
  (``parallel/ring_attention.py``): K/V blocks rotate around an ICI ring via
  ``ppermute`` while each shard keeps running softmax statistics.

The reference has no attention at all (its model is a flat double vector,
``src/protos/serverless_learn.proto:81-83``); this module exists for the
BERT/Llama rungs of BASELINE.md's config ladder.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def _causal_mask(q_len: int, kv_len: int, dtype) -> jax.Array:
    # q positions are the last q_len of kv_len (supports decode later).
    q_pos = jnp.arange(q_len)[:, None] + (kv_len - q_len)
    kv_pos = jnp.arange(kv_len)[None, :]
    return (kv_pos <= q_pos).astype(dtype)


def xla_attention(
    q: jax.Array,  # [B, T, H, D]
    k: jax.Array,  # [B, S, K, D]  (K heads; K == H or H % K == 0 for GQA)
    v: jax.Array,  # [B, S, K, D]
    *,
    causal: bool = False,
    mask: Optional[jax.Array] = None,  # [B, 1, T, S] or broadcastable, 1=keep
    softmax_scale: Optional[float] = None,
) -> jax.Array:
    B, T, H, D = q.shape
    S, K = k.shape[1], k.shape[2]
    scale = softmax_scale if softmax_scale is not None else D ** -0.5
    if K != H:
        group = H // K
        q = q.reshape(B, T, K, group, D)
        scores = jnp.einsum("btkgd,bskd->bkgts", q, k) * scale
        scores = scores.reshape(B, K * group, T, S)
    else:
        scores = jnp.einsum("bthd,bshd->bhts", q, k) * scale
    scores = scores.astype(jnp.float32)
    neg = jnp.finfo(jnp.float32).min
    if causal:
        cm = _causal_mask(T, S, jnp.bool_)
        scores = jnp.where(cm[None, None], scores, neg)
    if mask is not None:
        scores = jnp.where(mask.astype(jnp.bool_), scores, neg)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    if K != H:
        group = H // K
        probs4 = probs.reshape(B, K, group, T, S)
        out = jnp.einsum("bkgts,bskd->btkgd", probs4, v)
        return out.reshape(B, T, H, D)
    return jnp.einsum("bhts,bshd->bthd", probs, v)


# Sequence length at which "auto" switches from plain XLA attention to the
# Pallas flash kernel. Measured on a v5e chip (fwd+bwd, bf16, H=8, D=64):
# parity at 2048-4096, 9x at 8192 (242 ms -> 27 ms) — the [T, T] fp32 score
# matrix stops fitting the cache hierarchy.
AUTO_FLASH_MIN_SEQ = 4096


# With suffix padding expressed as kv_lengths, the flash kernel masks for
# (nearly) free AND skips fully-padded key blocks, so it wins from much
# shorter sequences than the general threshold. Measured v5e, BERT-base
# shape (B=8 H=12 D=64, half padded, fwd+bwd): flash wins at 512 already.
AUTO_FLASH_MIN_SEQ_LENGTHS = 512


def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    mask: Optional[jax.Array] = None,
    kv_lengths: Optional[jax.Array] = None,
    impl: str = "auto",
    axis_name: Optional[str] = None,  # sp axis for ring attention
    softmax_scale: Optional[float] = None,
) -> jax.Array:
    """``kv_lengths`` [B]: declares the mask to be SUFFIX padding (keys at
    positions >= kv_lengths[b] invalid) — the flash kernel's near-free
    masking path. Callers that pass it should pass the equivalent ``mask``
    too, for the impls that don't read lengths. ``softmax_scale``: in
    place of 1/sqrt(head_dim)."""
    if impl == "auto":
        # On an sp>1 mesh the sequence dim is sharded and ring attention is
        # the only impl that keeps it that way. Otherwise flash above the
        # measured threshold, when the kernels can run this call — the
        # kernel's own test decides, so the choice made here is the one
        # that runs.
        from serverless_learn_tpu.parallel.compat import in_manual_region
        from serverless_learn_tpu.parallel.ring_attention import (
            get_active_mesh)

        mesh = get_active_mesh()
        if (mesh is not None and mesh.shape.get("sp", 1) > 1
                and not in_manual_region()
                and (mask is None or kv_lengths is not None)
                and k.shape[1] % mesh.shape["sp"] == 0):
            # Suffix padding (kv_lengths) rides the ring's per-hop "len"
            # masking; only a GENERAL mask (no lengths form) takes dense
            # attention on an sp mesh.
            impl = "ring"
        else:
            min_seq = (AUTO_FLASH_MIN_SEQ_LENGTHS if kv_lengths is not None
                       else AUTO_FLASH_MIN_SEQ)
            impl = "xla"
            if q.shape[1] >= min_seq:
                from serverless_learn_tpu.ops.pallas.flash_attention import (
                    untileable_reason)

                if untileable_reason(q, k, mask=mask,
                                     kv_lengths=kv_lengths) is None:
                    impl = "flash"
    if impl == "xla":
        if kv_lengths is not None and mask is None:
            # Honor the lengths contract on this path too: a caller that
            # passes only kv_lengths must not silently attend to padding.
            S = k.shape[1]
            mask = (jnp.arange(S)[None, :] < kv_lengths[:, None])
            mask = mask[:, None, None, :]  # [B, 1, 1, S]
        return xla_attention(q, k, v, causal=causal, mask=mask,
                             softmax_scale=softmax_scale)
    if softmax_scale is not None:
        # The kernels scale by 1/sqrt(head_dim): fold the rest into q.
        q = q * (softmax_scale * q.shape[-1] ** 0.5)
    if impl == "flash":
        from serverless_learn_tpu.ops.pallas.flash_attention import flash_attention

        return flash_attention(q, k, v, causal=causal, mask=mask,
                               kv_lengths=kv_lengths)
    if impl == "ring":
        from serverless_learn_tpu.parallel.ring_attention import ring_attention

        if axis_name is None:
            raise ValueError("ring attention needs axis_name (the sp mesh axis)")
        return ring_attention(q, k, v, axis_name=axis_name, causal=causal,
                              kv_lengths=kv_lengths)
    raise ValueError(f"unknown attention impl {impl!r}")
