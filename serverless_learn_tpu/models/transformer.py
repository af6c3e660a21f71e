"""Shared transformer backbone for the BERT and Llama model families.

One configurable module covers both ends of BASELINE.md's ladder:

* BERT-base MLM — bidirectional, learned positions, LayerNorm, GeLU MLP.
* Llama-style LM — causal, RoPE, RMSNorm, SwiGLU, grouped-query attention,
  optional LoRA adapters on the projections (the "Llama-3-8B LoRA" stretch).

Parameter names (``q_proj``, ``wi``, ``embedder`` …) are load-bearing: the
sharding rule table in ``parallel/sharding.py`` keys on them, so the same
module runs DP / FSDP / TP / SP purely by mesh shape.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from serverless_learn_tpu.ops.attention import dot_product_attention
from serverless_learn_tpu.ops.moe import MoELayer


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: Optional[int] = None  # None => MHA
    d_ff: int = 2048
    max_seq_len: int = 512
    causal: bool = True
    # Where a token's position enters: "rope" (rotary, on q and k),
    # "learned" (a table added to the embedding; training only) or "none"
    # (nowhere: a causal model whose recurrent layers carry the order).
    position: str = "rope"
    rope_theta: float = 10000.0
    norm: str = "rms"  # "rms" | "layer"
    rms_norm_eps: float = 1e-6
    # The kind of each layer's mixer: a tuple of "attention" / "mamba",
    # one entry a layer; () => every layer attends. A "mamba" layer is a
    # Mamba-2 mixer (``MambaMixer``) of ``ssm_heads`` heads of
    # ``ssm_head_dim`` channels over a state of ``ssm_state`` per channel.
    layer_types: tuple = ()
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_groups: int = 1      # heads of one group share B and C
    ssm_conv: int = 4        # taps of the short causal convolution
    ssm_chunk: int = 256     # tokens per chunk of the chunked scan
    # Four fixed scalars some model families publish (all 1 = absent):
    # the embedding is multiplied by the first, both residual branches by
    # the second, the logits DIVIDED by the third; ``attention_multiplier``
    # replaces the softmax scale 1/sqrt(head_dim) (None: that default).
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    attention_multiplier: Optional[float] = None
    activation: str = "swiglu"  # "swiglu" | "gelu"
    lora_rank: int = 0
    lora_alpha: float = 16.0
    n_experts: int = 0  # 0 => dense MLP; >0 => MoE (ops/moe.py), ep-shardable
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    # Routing-subgroup token count (0 => full row). Bounds slot competition
    # and dispatch memory; ALSO sets the granularity of the load-balance aux
    # loss (a mean of per-group Switch terms, ops/moe.py), so changing it
    # perturbs the aux value/gradient, not just memory.
    moe_group_size: int = 1024
    tie_embeddings: bool = False
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32
    attention_impl: str = "auto"  # "auto" | "xla" | "flash" | "ring"
    sp_axis: Optional[str] = None  # mesh axis for ring attention
    # The model's attention masks are pure SUFFIX padding (valid prefix,
    # padded tail). Attention then derives per-row valid lengths from the
    # mask and takes the flash kernel's near-free kv_lengths path instead
    # of dense fallback. This is a data-pipeline CONTRACT: the bundle that
    # sets it must feed suffix-padded batches (mlm_transform checks it at
    # batch-build time; masks from other sources are trusted). An interior
    # pad would silently mask real trailing tokens.
    suffix_padding_mask: bool = False
    remat: bool = False
    pipeline: bool = False  # stack blocks [L,...] and GPipe over the pp axis
    pipeline_microbatches: int = 4
    # Interleaved schedule: each stage owns this many non-adjacent layer
    # chunks and microbatches make that many laps around a cyclic stage
    # ring — bubble (S-1)/(V*M+S-1) vs GPipe's (S-1)/(M+S-1). Requires
    # n_microbatches >= pp stages. 1 = classic GPipe.
    pipeline_interleave: int = 1
    # With interleave > 1 the layer EXECUTION order depends on the stage
    # count, so it must be pinned in the config (not read off whatever mesh
    # happens to be active) — a checkpoint trained interleaved on pp=S must
    # replay the same layer order when later run sequentially on pp=1.
    pipeline_stages: int = 0  # required when pipeline_interleave > 1
    # Megatron-style manual tensor parallelism INSIDE a pipeline stage's
    # shard_map: this config describes the LOCAL slice (n_heads/tp,
    # d_ff/tp), and Attention / MlpBlock psum their row-parallel outputs
    # over this axis. Set by PipelinedBlocks, never by users.
    manual_tp_axis: Optional[str] = None
    # GShard-style manual expert parallelism INSIDE a pipeline stage's
    # shard_map (round-4: pp x ep composition): this config's n_experts is
    # the LOCAL expert count (global / ep), routing runs over
    # moe_global_experts, and MoELayer all-to-alls token slots to their
    # owning ep member and back. Set by PipelinedBlocks, never by users.
    manual_ep_axis: Optional[str] = None
    moe_global_experts: int = 0  # routing-global E when manual_ep_axis set
    # Ring attention INSIDE a pipeline stage's shard_map (round-4 pp x sp
    # composition): the sequence dim of every pipeline operand is sharded
    # over this axis and Attention calls ring_attention_manual directly
    # (the dispatcher's shard_map wrapper can't nest in a manual region).
    # Set by PipelinedBlocks, never by users.
    manual_sp_axis: Optional[str] = None
    # Weight-only quantization for INFERENCE (round 4): "int8" stores every
    # projection kernel as int8 + per-output-channel scale, HALVING the
    # resident weight memory (a 2x larger model fits one chip). Measured
    # on v5e, it does NOT speed up 1B-scale decode (0.85x: decode there is
    # dispatch-bound, not weight-bandwidth-bound — see
    # ops/pallas/quant_matmul.py for the measured negative result of the
    # in-kernel dequant attempt). Params come from a trained checkpoint
    # via inference/quantize.quantize_params_int8; training with quant set
    # is unsupported (STE is out of scope).
    quant: Optional[str] = None
    head_dim_override: Optional[int] = None  # local-slice cfgs must pin it
    # Paged KV cache for INFERENCE (round 13): > 0 replaces the per-row
    # monolithic ``cached_k/v [B, max_seq_len, K, D]`` with one shared
    # block pool per layer (``pages_k/v [kv_pages, kv_page_size, K * D]``)
    # plus a per-row block table (``page_tbl [B, W]`` of page ids, the
    # sentinel id == kv_pages marking unallocated entries) and the same
    # ``cache_index`` vector. The table is HOST-OWNED: the engine
    # (``inference/continuous.py``) allocates pages from a free list,
    # shares refcounted prefix pages across rows, and passes the table
    # window it wants attended (W pages => attention span W*page_size,
    # usually far below max_seq_len). Writes resolve (position -> page id,
    # offset) through the table and DROP on the sentinel — a stray write
    # would corrupt another sequence's page, not this row's padding.
    # Training never reads these fields.
    kv_page_size: int = 0   # 0 => monolithic cache
    kv_pages: int = 0       # pool size; required > 0 when kv_page_size > 0

    def __post_init__(self):
        # A configuration file hands a list; the module's cfg is hashed.
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if self.position not in ("rope", "learned", "none"):
            raise ValueError(f"unknown position kind {self.position!r} "
                             "(rope | learned | none)")
        bad = set(self.layer_types) - {"attention", "mamba"}
        if bad or (self.layer_types
                   and len(self.layer_types) != self.n_layers):
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers "
                f"(unknown kinds: {sorted(bad)}) for n_layers="
                f"{self.n_layers}; kinds are attention | mamba")

    def layer_kind(self, i: int) -> str:
        return self.layer_types[i] if self.layer_types else "attention"

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def head_dim(self) -> int:
        if self.head_dim_override is not None:
            return self.head_dim_override
        return self.d_model // self.n_heads


def rope_angles(positions: jax.Array, head_dim: int, theta: float):
    """positions [B, T] -> (sin, cos) each [B, T, head_dim/2]."""
    freqs = 1.0 / theta ** (
        jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.sin(ang), jnp.cos(ang)


def apply_rope(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    """x [B, T, H, D]; rotate pairs (x[2i], x[2i+1])."""
    x1, x2 = x[..., 0::2], x[..., 1::2]
    s, c = sin[:, :, None, :], cos[:, :, None, :]
    r1 = x1 * c - x2 * s
    r2 = x2 * c + x1 * s
    return jnp.stack([r1, r2], axis=-1).reshape(x.shape).astype(x.dtype)


def constrain_residual(x: jax.Array) -> jax.Array:
    """Pin a [B, T, D] residual-stream activation to batch (+sp) sharding.

    Without this, GSPMD propagates the embedding table's tp sharding into
    the residual stream and then pays an "involuntary full rematerialization"
    reshard in the backward pass (observed on dp×fsdp×tp meshes). The
    residual stream is canonically batch-sharded; tp lives only inside the
    projections.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from serverless_learn_tpu.parallel.ring_attention import get_active_mesh

    mesh = get_active_mesh()
    if mesh is None or x.ndim < 3:
        return x
    from serverless_learn_tpu.parallel.mesh import live_batch_axes

    batch, n_batch = live_batch_axes(mesh)
    if batch and x.shape[0] % n_batch:
        batch = ()  # e.g. batch-1 decoding under a training mesh
    seq = "sp" if mesh.shape.get("sp", 1) > 1 else None
    if seq and x.shape[1] % mesh.shape["sp"]:
        seq = None  # single-token decode steps can't shard the seq dim
    if not batch and seq is None:
        return x
    spec = P(batch if batch else None, seq, None)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _shard_head_over_pp(x: jax.Array) -> jax.Array:
    """Shard a pipeline's [B, T, D] output over pp along the sequence dim,
    so the final norm + lm head (and the loss behind them) run 1/S of the
    tokens per stage instead of replicating the whole tail computation on
    every stage. No-op off a pp mesh or when T doesn't divide."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from serverless_learn_tpu.parallel.mesh import live_batch_axes
    from serverless_learn_tpu.parallel.ring_attention import get_active_mesh

    mesh = get_active_mesh()
    if mesh is None or mesh.shape.get("pp", 1) == 1:
        return x
    # Under pp x sp the sequence dim is ALREADY sp-sharded; the head runs
    # over ("sp", "pp") jointly — constraining to "pp" alone would force
    # an sp->pp reshard of the whole activation.
    seq = tuple(a for a in ("sp", "pp") if mesh.shape.get(a, 1) > 1)
    n_seq = 1
    for a in seq:
        n_seq *= mesh.shape[a]
    if x.shape[1] % n_seq:
        return x
    batch, n_batch = live_batch_axes(mesh)
    if batch and x.shape[0] % n_batch:
        batch = ()
    spec = P(batch if batch else None, seq if len(seq) > 1 else seq[0], None)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


class LoRAAdapter(nn.Module):
    """Low-rank delta added to a frozen projection's output: x @ A @ B * s."""

    rank: int
    alpha: float
    out_features: tuple
    dtype: jnp.dtype
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        a = nn.DenseGeneral(self.rank, use_bias=False, name="lora_a",
                            dtype=self.dtype, param_dtype=self.param_dtype,
                            kernel_init=nn.initializers.normal(0.02))(x)
        b = nn.DenseGeneral(self.out_features, use_bias=False, name="lora_b",
                            dtype=self.dtype, param_dtype=self.param_dtype,
                            kernel_init=nn.initializers.zeros)(a)
        return b * (self.alpha / self.rank)


class QuantDenseGeneral(nn.Module):
    """Weight-only int8 projection (inference): the kernel is stored int8
    with a per-output-channel float scale — HALF the resident weight
    memory of bf16, which is the feature's win (fit a ~2x larger model
    per chip). It is NOT a decode speedup on this chip: measured 1B-scale
    decode is dispatch-bound (see ops/pallas/quant_matmul.py for the
    preserved negative result). Params come from
    ``inference/quantize.quantize_params_int8`` over a trained
    checkpoint; the random init here exists only to give the pytree its
    shapes."""

    features: tuple  # output feature dims
    n_contract: int = 1  # trailing input dims contracted
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        from serverless_learn_tpu.ops.pallas.quant_matmul import quant_matmul

        in_dims = tuple(x.shape[-self.n_contract:])
        kq = self.param("kernel_q", nn.initializers.zeros,
                        (*in_dims, *self.features), jnp.int8)
        scale = self.param("scale", nn.initializers.ones,
                           self.features, jnp.float32)
        I = O = 1
        for d in in_dims:
            I *= d
        for d in self.features:
            O *= d
        lead = x.shape[:-self.n_contract]
        y = quant_matmul(x.reshape(*lead, I), kq.reshape(I, O),
                         scale.reshape(O), out_dtype=self.dtype)
        return y.reshape(*lead, *self.features)


def _proj(cfg: TransformerConfig, feats, name: str, n_contract: int = 1):
    """A projection layer honoring ``cfg.quant`` (same param paths the
    sharding rules key on; quantized variants add _q/scale leaves)."""
    if cfg.quant == "int8":
        return QuantDenseGeneral(
            features=feats if isinstance(feats, tuple) else (feats,),
            n_contract=n_contract, dtype=cfg.dtype, name=name)
    if cfg.quant is not None:
        raise ValueError(f"unknown quant mode {cfg.quant!r} (int8)")
    axis = -1 if n_contract == 1 else tuple(range(-n_contract, 0))
    return nn.DenseGeneral(feats, use_bias=False, name=name, axis=axis,
                           dtype=cfg.dtype, param_dtype=cfg.param_dtype)


def _times(x, multiplier: float):
    """``x`` times a configuration's fixed scalar; 1 adds no operation to
    a model that publishes none."""
    return x if multiplier == 1.0 else x * multiplier


def _norm(cfg: TransformerConfig, name: str):
    if cfg.norm == "rms":
        return nn.RMSNorm(epsilon=cfg.rms_norm_eps, dtype=cfg.dtype,
                          param_dtype=cfg.param_dtype, name=name)
    return nn.LayerNorm(dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                        name=name)


class Attention(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, *, mask=None, positions=None, decode=False,
                 prefill=False, extend=False, seq_lengths=None):
        cfg = self.cfg
        H, K, D = cfg.n_heads, cfg.kv_heads, cfg.head_dim
        dense = lambda feats, name: _proj(cfg, feats, name)
        q = dense((H, D), "q_proj")(x)
        k = dense((K, D), "k_proj")(x)
        v = dense((K, D), "v_proj")(x)
        if cfg.lora_rank > 0:
            q = q + LoRAAdapter(cfg.lora_rank, cfg.lora_alpha, (H, D),
                                cfg.dtype, cfg.param_dtype, name="q_lora")(x)
            v = v + LoRAAdapter(cfg.lora_rank, cfg.lora_alpha, (K, D),
                                cfg.dtype, cfg.param_dtype, name="v_lora")(x)
        causal = cfg.causal
        if decode or prefill or extend:
            # Autoregressive KV cache. decode: x is the single newest token
            # per sequence ([B, 1, d_model]); K/V land at slot
            # `cache_index[b]` and attention reads the whole cache under a
            # per-sequence <= index mask. RoPE must use the absolute
            # position, which *is* the cache index — so rotation happens
            # inside this branch. prefill: one batched causal forward over
            # the (right-padded) prompt that bulk-writes the cache. The
            # index is a [B] VECTOR: batched serving right-pads unequal
            # prompts to one shape and passes ``seq_lengths`` — pad slots
            # hold garbage K/V that the per-seq mask never reads and the
            # next decode writes straight over (inference/batching.py).
            B = x.shape[0]
            if cfg.kv_page_size > 0:
                # Paged KV cache: one block pool per layer, shared by every
                # row through per-row page tables. All three entry modes
                # collapse to ONE write pattern — append the new tokens at
                # each row's current index — because chunked prefill IS
                # repeated ragged appends (prefill on a fresh cache starts
                # at index 0, matching the monolithic semantics).
                ps, P = cfg.kv_page_size, cfg.kv_pages
                if P <= 0:
                    raise ValueError(
                        "kv_page_size > 0 requires kv_pages > 0")
                max_pages = -(-cfg.max_seq_len // ps)
                is_init = not self.has_variable("cache", "pages_k")
                # A token's K heads are ONE row of K * D: the leaves' minor
                # dimension fills whole lane tiles whatever the head's
                # width, so the scatter and the gather below want the same
                # layout and the compiler never re-lays the pool out.
                pk = self.variable("cache", "pages_k", jnp.zeros,
                                   (P, ps, K * D), k.dtype)
                pv = self.variable("cache", "pages_v", jnp.zeros,
                                   (P, ps, K * D), v.dtype)
                tbl = self.variable(
                    "cache", "page_tbl",
                    lambda: jnp.full((B, max_pages), P, jnp.int32))
                ci = self.variable("cache", "cache_index",
                                   lambda: jnp.zeros((B,), jnp.int32))
                if not is_init:
                    T = x.shape[1]
                    if decode and T != 1:
                        raise ValueError(
                            f"decode feeds one token at a time, got "
                            f"T={T}")
                    W = tbl.value.shape[1]  # engine passes the live window
                    S = W * ps
                    pos0 = ci.value  # [B]
                    positions_bt = (pos0[:, None]
                                    + jnp.arange(T, dtype=jnp.int32))
                    if cfg.position == "rope":
                        sin, cos = rope_angles(positions_bt, D,
                                               cfg.rope_theta)
                        q = apply_rope(q, sin, cos)
                        k = apply_rope(k, sin, cos)
                    # Ragged appends: rows may carry fewer than T real new
                    # tokens (chunked prefill pads the batch to a bucket).
                    if seq_lengths is None:
                        new_len = jnp.full((B,), T, jnp.int32)
                    else:
                        new_len = seq_lengths.astype(jnp.int32)
                    valid = jnp.arange(T)[None, :] < new_len[:, None]
                    page_idx = positions_bt // ps  # [B, T]
                    ids = jnp.take_along_axis(
                        tbl.value, jnp.clip(page_idx, 0, W - 1), axis=1)
                    # Pad positions and positions beyond the passed window
                    # resolve to the sentinel: the pool is SHARED, so a
                    # stray write would land in another sequence's page.
                    ids = jnp.where(valid & (page_idx < W), ids, P)
                    offs = positions_bt % ps
                    pk.value = pk.value.at[
                        ids.reshape(-1), offs.reshape(-1)].set(
                        k.reshape(B * T, K * D), mode="drop")
                    pv.value = pv.value.at[
                        ids.reshape(-1), offs.reshape(-1)].set(
                        v.reshape(B * T, K * D), mode="drop")
                    ci.value = pos0 + new_len
                    # Attention reads the gathered window; sentinel table
                    # entries clip to a real page whose garbage the
                    # per-position mask below never admits.
                    safe_tbl = jnp.clip(tbl.value, 0, P - 1)
                    k = jnp.take(pk.value, safe_tbl, axis=0).reshape(
                        B, S, K, D)
                    v = jnp.take(pv.value, safe_tbl, axis=0).reshape(
                        B, S, K, D)
                    mask = (jnp.arange(S)[None, None, :]
                            <= positions_bt[:, :, None])[:, None]
                    causal = False
            else:
                is_init = not self.has_variable("cache", "cached_k")
                ck = self.variable("cache", "cached_k", jnp.zeros,
                                   (B, cfg.max_seq_len, K, D), k.dtype)
                cv = self.variable("cache", "cached_v", jnp.zeros,
                                   (B, cfg.max_seq_len, K, D), v.dtype)
                ci = self.variable("cache", "cache_index",
                                   lambda: jnp.zeros((B,), jnp.int32))
            if cfg.kv_page_size > 0:
                pass  # the paged branch above handled everything
            elif not is_init and prefill:
                T = x.shape[1]
                if cfg.position == "rope":
                    p = jnp.broadcast_to(
                        jnp.arange(T, dtype=jnp.int32)[None, :], (B, T))
                    sin, cos = rope_angles(p, D, cfg.rope_theta)
                    q = apply_rope(q, sin, cos)
                    k = apply_rope(k, sin, cos)
                ck.value = jax.lax.dynamic_update_slice(
                    ck.value, k, (0, 0, 0, 0))
                cv.value = jax.lax.dynamic_update_slice(
                    cv.value, v, (0, 0, 0, 0))
                if seq_lengths is None:
                    ci.value = jnp.full((B,), T, jnp.int32)
                else:
                    ci.value = seq_lengths.astype(jnp.int32)
                # Attention runs causally over the padded prompt: real
                # token i attends only [0, i] — all real under right-
                # padding; pad rows produce garbage nobody reads.
            elif not is_init and extend:
                # Append T tokens at each row's current index (the
                # speculative-verify primitive): RoPE at absolute
                # positions ci+t, K/V written at per-row offsets, and a
                # shifted-causal mask — query t of row b sees cached keys
                # [0, ci_b + t]. Entries past the index that a later
                # rollback strands are dead by the <= index mask.
                T = x.shape[1]
                pos0 = ci.value  # [B]
                positions_bt = pos0[:, None] + jnp.arange(T,
                                                          dtype=jnp.int32)
                if cfg.position == "rope":
                    sin, cos = rope_angles(positions_bt, D, cfg.rope_theta)
                    q = apply_rope(q, sin, cos)
                    k = apply_rope(k, sin, cos)

                def write_span(c, new, p):  # [S,K,D], [T,K,D], []
                    z = jnp.zeros((), p.dtype)
                    return jax.lax.dynamic_update_slice(c, new, (p, z, z))

                ck.value = jax.vmap(write_span)(ck.value, k, pos0)
                cv.value = jax.vmap(write_span)(cv.value, v, pos0)
                ci.value = pos0 + T
                k, v = ck.value, cv.value
                mask = (jnp.arange(cfg.max_seq_len)[None, None, :]
                        <= positions_bt[:, :, None])[:, None]  # [B,1,T,S]
                causal = False
            elif not is_init:
                if x.shape[1] != 1:
                    raise ValueError(
                        f"decode feeds one token at a time, got T={x.shape[1]}")
                pos = ci.value  # [B]
                if cfg.position == "rope":
                    sin, cos = rope_angles(pos[:, None], D, cfg.rope_theta)
                    q = apply_rope(q, sin, cos)
                    k = apply_rope(k, sin, cos)

                def write_at(c, new, p):  # [S, K, D], [1, K, D], []
                    z = jnp.zeros((), p.dtype)
                    return jax.lax.dynamic_update_slice(c, new, (p, z, z))

                ck.value = jax.vmap(write_at)(ck.value, k, pos)
                cv.value = jax.vmap(write_at)(cv.value, v, pos)
                ci.value = pos + 1
                k, v = ck.value, cv.value
                mask = (jnp.arange(cfg.max_seq_len)[None, :]
                        <= pos[:, None])[:, None, None, :]
                causal = False  # the index mask already encodes causality
        elif cfg.position == "rope":
            if positions is None:
                positions = jnp.arange(x.shape[1])[None, :]
            sin, cos = rope_angles(positions, D, cfg.rope_theta)
            q = apply_rope(q, sin, cos)
            k = apply_rope(k, sin, cos)
        kv_lengths = None
        if (cfg.suffix_padding_mask and mask is not None
                and not (decode or prefill or extend) and mask.ndim == 4
                and mask.shape[1] == 1 and mask.shape[2] == 1
                and (jnp.issubdtype(mask.dtype, jnp.integer)
                     or jnp.issubdtype(mask.dtype, jnp.bool_))):
            # Contract (cfg.suffix_padding_mask): the mask is a valid
            # prefix + padded tail, so its row sum IS the valid length.
            # Float masks are excluded — they could be additive (0 = KEEP),
            # whose row sum would be garbage lengths.
            kv_lengths = mask[:, 0, 0, :].astype(jnp.int32).sum(-1)
        if cfg.manual_sp_axis and not (decode or prefill or extend):
            # Inside the pipeline's manual region with the seq dim sharded
            # over sp: hop the K/V shards around the ring directly.
            if mask is not None and kv_lengths is None:
                raise NotImplementedError(
                    "pp x sp with a general attention mask: a local mask "
                    "shard cannot express cross-shard visibility; use "
                    "causal and/or suffix kv_lengths")
            from serverless_learn_tpu.parallel.ring_attention import (
                ring_attention_manual)

            if kv_lengths is not None:
                # Derived from the LOCAL mask shard (the pipeline shards
                # the mask's key dim over sp), but the ring wants GLOBAL
                # suffix lengths; a suffix-padded mask's per-shard valid
                # counts sum to exactly the global valid length.
                kv_lengths = jax.lax.psum(kv_lengths, cfg.manual_sp_axis)
            if cfg.attention_multiplier is not None:
                q = q * (cfg.attention_multiplier * D ** 0.5)
            out = ring_attention_manual(q, k, v,
                                        axis_name=cfg.manual_sp_axis,
                                        causal=causal,
                                        kv_lengths=kv_lengths)
        else:
            out = dot_product_attention(
                q, k, v, causal=causal, mask=mask, kv_lengths=kv_lengths,
                impl="xla" if (decode or prefill or extend)
                else cfg.attention_impl,
                axis_name=cfg.sp_axis or "sp",
                softmax_scale=cfg.attention_multiplier)
        y = _proj(cfg, cfg.d_model, "o_proj", n_contract=2)(out)
        if cfg.manual_tp_axis:
            # Row-parallel output projection: each tp member contracted its
            # local heads; the partial sums combine here.
            y = jax.lax.psum(y, cfg.manual_tp_axis)
        return y


class MlpBlock(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dense = lambda feats, name: _proj(cfg, feats, name)
        if cfg.activation == "swiglu":
            gate = nn.silu(dense(cfg.d_ff, "gate_proj")(x))
            up = dense(cfg.d_ff, "up_proj")(x)
            y = dense(cfg.d_model, "down_proj")(gate * up)
        else:
            h = nn.gelu(dense(cfg.d_ff, "wi")(x))
            y = dense(cfg.d_model, "wo")(h)
        if cfg.manual_tp_axis:
            # Row-parallel down projection (each member holds d_ff/tp).
            y = jax.lax.psum(y, cfg.manual_tp_axis)
        return y


def _inverse_softplus(dt):
    return dt + jnp.log(-jnp.expm1(-dt))


class MambaMixer(nn.Module):
    """The Mamba-2 mixer (``ops/ssm.py`` has its three parts): one input
    projection to ``[z | xBC | dt]``, a short causal depthwise convolution
    and SiLU over ``xBC = [x | B | C]``, the selective state-space
    recurrence over ``x`` per head, a skip ``D x``, the gate
    ``y * silu(z)`` and THEN a grouped RMSNorm, and the output projection.
    No biases but the convolution's.

    Under ``decode`` / ``prefill`` / ``extend`` it carries two ``cache``
    leaves per row, both indexed by the serving engine's SLOT and not
    through its block table (``SLOT_LEAVES``): the recurrent state
    (float32) and the convolution's last ``ssm_conv - 1`` inputs. Each
    call appends to what the leaves hold: a sequence's first call has to
    find them zero (the engine zeroes a slot's rows where a prompt
    starts; ``init_cache`` makes them zero). ``seq_lengths`` [B]: real
    tokens of right-padded rows; the leaves are left as after those."""

    cfg: TransformerConfig
    SLOT_LEAVES = ("ssm_state", "conv_state")

    @nn.compact
    def __call__(self, u, *, decode=False, prefill=False, extend=False,
                 seq_lengths=None):
        from serverless_learn_tpu.ops import ssm

        cfg = self.cfg
        H, P, N, G, K = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
                         cfg.ssm_groups, cfg.ssm_conv)
        d_inner, gn = H * P, G * N
        conv_dim = d_inner + 2 * gn
        B_, T = u.shape[:2]
        zxbcdt = _proj(cfg, 2 * d_inner + 2 * gn + H, "in_proj")(u)
        z, xbc, dt = jnp.split(zxbcdt, [d_inner, d_inner + conv_dim], axis=-1)
        pd = cfg.param_dtype
        conv_w = self.param("conv_kernel", nn.initializers.lecun_normal(),
                            (K, conv_dim), pd)
        conv_b = self.param("conv_bias", nn.initializers.zeros,
                            (conv_dim,), pd)
        # Mamba-2's own initial values: A in [1, 16], dt in [1e-3, 1e-1]
        # (log-uniform) through softplus' inverse, D = 1.
        a_log = self.param(
            "A_log", lambda k, s, d: jnp.log(jax.random.uniform(
                k, s, jnp.float32, 1.0, 16.0)).astype(d), (H,), pd)
        dt_bias = self.param(
            "dt_bias", lambda k, s, d: _inverse_softplus(jnp.exp(
                jax.random.uniform(k, s, jnp.float32, math.log(1e-3),
                                   math.log(1e-1)))).astype(d), (H,), pd)
        skip = self.param("D", nn.initializers.ones, (H,), pd)
        norm_w = self.param("norm_scale", nn.initializers.ones,
                            (d_inner,), pd)
        # ``carried``: this call reads and writes the two leaves (an
        # ``init`` under one of the three modes only declares them).
        carried = False
        if decode or prefill or extend:
            carried = self.has_variable("cache", "ssm_state")
            hs = self.variable("cache", "ssm_state", jnp.zeros,
                               (B_, H, P, N), jnp.float32)
            cs = self.variable("cache", "conv_state", jnp.zeros,
                               (B_, K - 1, conv_dim), cfg.dtype)
        f32 = jnp.float32
        A = -jnp.exp(a_log.astype(f32))
        dt = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
        conv_in = cs.value if carried else jnp.zeros(
            (B_, K - 1, conv_dim), xbc.dtype)
        xbc, conv_out = ssm.causal_conv(xbc, conv_in, conv_w, conv_b,
                                        seq_lengths if carried else None)
        xbc = nn.silu(xbc).astype(cfg.dtype)
        x, Bm, Cm = jnp.split(xbc, [d_inner, d_inner + gn], axis=-1)
        x = x.reshape(B_, T, H, P)
        Bm, Cm = Bm.reshape(B_, T, G, N), Cm.reshape(B_, T, G, N)
        h0 = hs.value if carried else jnp.zeros((B_, H, P, N), f32)
        if decode and carried:
            if T != 1:
                raise ValueError(
                    f"decode feeds one token at a time, got T={T}")
            y, h1 = ssm.ssm_step(x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0],
                                 h0)
            y = y[:, None]
        else:
            if carried and seq_lengths is not None:
                real = jnp.arange(T)[None, :] < seq_lengths[:, None]
                dt = jnp.where(real[:, :, None], dt, 0.0)
            y, h1 = ssm.ssd_scan(x, dt, A, Bm, Cm, h0, cfg.ssm_chunk)
        if carried:
            hs.value, cs.value = h1, conv_out
        y = y + skip.astype(f32)[:, None] * x.astype(f32)
        # Gate first, then the norm, over each group's channels.
        g = y.reshape(B_, T, d_inner) * nn.silu(z.astype(f32))
        g = g.reshape(B_, T, G, d_inner // G)
        g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True)
                              + cfg.rms_norm_eps)
        g = (g.reshape(B_, T, d_inner) * norm_w.astype(f32)).astype(cfg.dtype)
        return _proj(cfg, cfg.d_model, "out_proj")(g)


def slot_leaves(cfg: TransformerConfig) -> tuple:
    """Names of the ``cache`` leaves this model keeps per SLOT of the
    serving engine (``[max_slots, ...]``, gathered and scattered by slot
    id) beside the paged pool: () for a model whose every layer attends."""
    return MambaMixer.SLOT_LEAVES if "mamba" in cfg.layer_types else ()


class Block(nn.Module):
    cfg: TransformerConfig
    kind: str = "attention"   # the mixer: "attention" | "mamba"

    @nn.compact
    def __call__(self, x, *, mask=None, positions=None, decode=False,
                 prefill=False, extend=False, seq_lengths=None):
        cfg = self.cfg
        res = cfg.residual_multiplier
        h = _norm(cfg, "norm_attn")(x)
        if self.kind == "mamba":
            mixed = MambaMixer(cfg, name="mamba")(
                h, decode=decode, prefill=prefill, extend=extend,
                seq_lengths=seq_lengths)
        else:
            mixed = Attention(cfg, name="attn")(
                h, mask=mask, positions=positions, decode=decode,
                prefill=prefill, extend=extend, seq_lengths=seq_lengths)
        x = x + _times(mixed, res)
        if cfg.n_experts > 0:
            moe_cfg = cfg
            if decode or prefill or extend:
                # Inference routes PER TOKEN (group size 1): capacity is
                # a training-efficiency construct, and grouped drops make
                # routing depend on the other tokens in the group — under
                # prefill that includes FUTURE positions, which would
                # break the cached-decode == full-forward equivalence
                # (tests/test_moe_generate.py pins it). Per-token groups
                # give every token its full top-k experts, no drops, and
                # identical routing between prefill and decode.
                moe_cfg = dataclasses.replace(cfg, moe_group_size=1)
            y = MoELayer(moe_cfg, name="moe")(_norm(cfg, "norm_mlp")(x))
        else:
            y = MlpBlock(cfg, name="mlp")(_norm(cfg, "norm_mlp")(x))
        return x + _times(y, res)


class PipelinedBlocks(nn.Module):
    """Block stack with layer-stacked params, executed as a GPipe pipeline.

    Params live under one ``pipe_blocks`` collection whose leaves carry a
    leading ``n_layers`` dim; the sharding rule table maps that dim to the
    ``pp`` mesh axis so each pipeline stage holds a contiguous layer slice
    (``parallel/sharding.py``). Execution delegates to
    ``parallel.pipeline.gpipe_apply`` (``pp > 1``) or its sequential golden
    model (``pp == 1``) against the process's active mesh.
    """

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, *, mask=None, positions=None):
        cfg = self.cfg
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(x.shape[1], dtype=jnp.int32)[None, :],
                (x.shape[0], x.shape[1]))

        # Params don't depend on the attention impl; pinning "xla" keeps
        # init's trace free of the auto dispatcher (which on an sp mesh
        # would wrap a shard_map around init's tiny dummy input).
        # Constructed HERE — at __call__'s trace level, not inside
        # init_stack or the vmap: flax >= 0.10 checks the trace level at
        # Module construction, and flax may invoke the initializer from a
        # transformed apply (e.g. under jax.grad), where construction
        # inside the initializer raises JaxTransformError. Calling .init
        # on an outside-built module inside the vmap is the supported
        # pattern.
        init_block = Block(dataclasses.replace(cfg, attention_impl="xla"))

        def init_stack(rng):
            dummy = jnp.zeros((1, 4, cfg.d_model), cfg.dtype)
            dpos = jnp.zeros((1, 4), jnp.int32)

            def one(r):
                return init_block.init(r, dummy, mask=None,
                                       positions=dpos)["params"]

            return jax.vmap(one)(jax.random.split(rng, cfg.n_layers))

        stacked = self.param("pipe_blocks", init_stack)

        from serverless_learn_tpu.parallel.ring_attention import (
            get_active_mesh)

        mesh = get_active_mesh()
        tp = mesh.shape.get("tp", 1) if mesh is not None else 1
        ep = mesh.shape.get("ep", 1) if mesh is not None else 1
        sp = mesh.shape.get("sp", 1) if mesh is not None else 1
        pp_live = mesh is not None and mesh.shape.get("pp", 1) > 1
        block_cfg = cfg
        param_specs = None
        if pp_live and sp > 1:
            # pp x sp (round 4): the pipeline's operands shard their seq
            # dim over sp and each stage's attention hops K/V around the
            # sp ring from inside the stage (manual ring attention).
            if not cfg.causal:
                raise NotImplementedError(
                    "pp x sp requires a causal model: a bidirectional "
                    "model's padding mask cannot be expressed per seq "
                    "shard (use sp without pp, where GSPMD reshards)")
            if cfg.n_experts > 0:
                # Routing groups would subdivide per-SHARD token runs, a
                # silently different grouping (capacity, drops, aux) from
                # the dp/ep golden semantics — refuse until per-shard
                # routing is a deliberate, tested mode.
                raise NotImplementedError(
                    "pp x sp x MoE is unsupported: sequence-sharded "
                    "routing changes group/capacity semantics; use "
                    "pp x ep (dp absorbs the sequence) instead")
            block_cfg = dataclasses.replace(
                block_cfg, manual_sp_axis="sp",
                head_dim_override=cfg.head_dim)
        if pp_live and tp > 1:
            # Megatron-style manual tp inside the pipeline's shard_map:
            # each tp member applies a LOCAL slice of every layer (heads
            # and d_ff divided by tp; the rule table shards the stacked
            # leaves to match) and psums its row-parallel outputs. Experts
            # tp-slice their d_ff exactly like the dense MLP (MoELayer
            # psums after its down projection).
            H, K = cfg.n_heads, cfg.kv_heads
            if H % tp or K % tp or cfg.d_ff % tp:
                raise ValueError(
                    f"pp x tp needs n_heads ({H}), kv_heads ({K}) and "
                    f"d_ff ({cfg.d_ff}) divisible by tp={tp}")
            block_cfg = dataclasses.replace(
                block_cfg, n_heads=H // tp, n_kv_heads=K // tp,
                d_ff=cfg.d_ff // tp, manual_tp_axis="tp",
                head_dim_override=cfg.head_dim)
        if pp_live and ep > 1 and cfg.n_experts > 0:
            # GShard-style manual ep inside the pipeline's shard_map
            # (round-4: the Mixtral-shaped flagship must pipeline): each ep
            # member owns n_experts/ep experts of every layer; MoELayer
            # routes over the global count and all-to-alls slots to their
            # owners. Batch rows are ep-sharded (gpipe_apply batch axes),
            # so attention is data-parallel over ep.
            if cfg.n_experts % ep:
                raise ValueError(
                    f"pp x ep needs n_experts ({cfg.n_experts}) divisible "
                    f"by ep={ep}")
            block_cfg = dataclasses.replace(
                block_cfg, n_experts=cfg.n_experts // ep,
                moe_global_experts=cfg.n_experts, manual_ep_axis="ep",
                head_dim_override=cfg.head_dim)
        if pp_live and (tp > 1 or (ep > 1 and cfg.n_experts > 0)):
            from serverless_learn_tpu.parallel.sharding import (
                DEFAULT_RULES, _path_str)

            def spec_of(path, leaf):
                return DEFAULT_RULES.spec_for(
                    "pipe_blocks/" + _path_str(path), leaf.ndim, mesh)

            param_specs = jax.tree_util.tree_map_with_path(spec_of, stacked)

        moe_aux = cfg.n_experts > 0

        # Construct the Block once, OUTSIDE the pipeline's scan/shard_map:
        # flax >= 0.10 checks the trace level at Module construction, so
        # building it inside the transformed region raises
        # JaxTransformError; the functional .apply on an outside-built
        # module is the supported pattern.
        pipe_block = Block(block_cfg)

        def block_apply(p, h, pos, m):
            if moe_aux:
                # Thread the MoE router loss out of the nested apply: the
                # sow collection cannot cross a module.apply boundary, so
                # each block returns its summed sown losses explicitly and
                # the pipeline/sequential scan accumulates them.
                def fn(pp_, h_, pos_, m_):
                    out, mut = pipe_block.apply(
                        {"params": pp_}, h_, mask=m_, positions=pos_,
                        mutable=["losses"])
                    leaves = jax.tree_util.tree_leaves(
                        mut.get("losses", {}))
                    aux = (sum(jnp.sum(l) for l in leaves) if leaves
                           else jnp.float32(0.0))
                    return out, aux
            else:
                fn = lambda pp_, h_, pos_, m_: pipe_block.apply(
                    {"params": pp_}, h_, mask=m_, positions=pos_)
            if cfg.remat:
                fn = jax.checkpoint(fn)
            return fn(p, h, pos, m)

        from serverless_learn_tpu.parallel.pipeline import (
            gpipe_apply, layer_execution_order, sequential_apply)

        V = cfg.pipeline_interleave
        order = None
        if V > 1:
            if cfg.pipeline_stages <= 0:
                raise ValueError(
                    "pipeline_interleave > 1 requires pipeline_stages: the "
                    "layer execution order is a function of the stage count "
                    "and must not drift with whatever mesh is active")
            order = layer_execution_order(cfg.n_layers, cfg.pipeline_stages,
                                          V)
        if mesh is None or mesh.shape.get("pp", 1) == 1:
            # Sequential path replays the exact layer order the interleaved
            # schedule trains with (identity for GPipe).
            out = sequential_apply(block_apply, stacked, x, positions, mask,
                                   layer_order=order, with_aux=moe_aux)
            if moe_aux:
                out, aux = out
                self.sow("losses", "pipeline_moe_aux", aux)
            return out
        if V > 1 and mesh.shape["pp"] != cfg.pipeline_stages:
            raise ValueError(
                f"mesh pp={mesh.shape['pp']} != config pipeline_stages="
                f"{cfg.pipeline_stages}; an interleaved checkpoint's layer "
                "order is tied to its stage count")
        out = gpipe_apply(block_apply, stacked, x, positions, mask,
                          mesh=mesh,
                          n_microbatches=cfg.pipeline_microbatches,
                          n_virtual=V, param_specs=param_specs,
                          with_aux=moe_aux,
                          seq_axis="sp" if sp > 1 else None)
        if moe_aux:
            out, aux = out
            # aux carries one entry per batch shard; the mean over shards
            # is the global router loss (shards saw disjoint data). Re-sown
            # so apply_with_losses consumes it like any in-line MoE layer.
            self.sow("losses", "pipeline_moe_aux", jnp.mean(aux))
        return out


def unstack_pipeline_params(params: dict, cfg: "TransformerConfig") -> dict:
    """Pipeline-trained params -> the sequential module's layout.

    A pipeline checkpoint stores the blocks as ONE ``pipe_blocks`` subtree
    (under the Transformer's ``pipeline`` submodule) with a leading
    ``n_layers`` dim; the sequential (servable, KV-cached) module wants
    per-layer ``layer_{i}`` subtrees. Interleaved schedules
    execute the stack in ``layer_execution_order``; sequential ``layer_i``
    is execution step i, so it takes stack index ``order[i]`` — a V-chunk
    checkpoint served without this mapping would run its layers in the
    wrong order. Non-block params (embedder, final norm, lm_head) share
    names across both layouts and pass through untouched.
    """
    stacked = None
    if "pipe_blocks" in params:  # stack at the root (direct Block stacks)
        out = {k: v for k, v in params.items() if k != "pipe_blocks"}
        stacked = params["pipe_blocks"]
    elif "pipe_blocks" in params.get("pipeline", {}):  # Transformer nesting
        out = {k: v for k, v in params.items() if k != "pipeline"}
        stacked = params["pipeline"]["pipe_blocks"]
    if stacked is None:
        return params
    from serverless_learn_tpu.parallel.pipeline import layer_execution_order
    if cfg.pipeline_interleave > 1:
        order = layer_execution_order(cfg.n_layers, cfg.pipeline_stages,
                                      cfg.pipeline_interleave)
    else:
        order = list(range(cfg.n_layers))
    for step, ident in enumerate(order):
        out[f"layer_{step}"] = jax.tree_util.tree_map(
            lambda leaf: leaf[ident], stacked)
    return out


class Transformer(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, *, mask=None, positions=None, decode=False,
                 prefill=False, extend=False, seq_lengths=None):
        """tokens [B, T] int32 -> logits [B, T, vocab].

        ``decode=True``: autoregressive inference mode — ``tokens`` is the
        single newest token per sequence ([B, 1]) and each attention layer
        maintains a KV cache in the ``cache`` variable collection.
        ``prefill=True``: one batched causal forward over the prompt that
        bulk-writes the cache (see ``inference/generate.py`` for the driver).
        ``seq_lengths`` [B] (prefill only): true prompt lengths of
        right-padded prompts — each sequence's cache index starts at its
        own length, so one batched prefill serves unequal prompts
        (``inference/batching.py``).
        ``extend=True``: feed T>1 tokens APPENDING at each row's current
        cache index (causal within the new span, full visibility of the
        cached prefix) — the speculative-verify primitive: one forward
        scores K drafted tokens (``inference/speculative.py``).
        """
        cfg = self.cfg
        if decode + prefill + extend > 1:
            raise ValueError(
                "decode, prefill and extend are mutually exclusive")
        infer = decode or prefill or extend
        if infer and cfg.pipeline:
            raise NotImplementedError(
                "decode with pipeline=True: serve the sequential twin "
                "instead — unstack_pipeline_params converts a pipeline "
                "checkpoint to the per-layer layout (the generate/serve "
                "CLIs do this automatically)")
        if infer and not cfg.causal:
            raise ValueError("decode requires a causal model")
        if infer and cfg.position == "learned":
            # Learned positions would need the cache index at this level.
            raise NotImplementedError(
                "decode requires position 'rope' or 'none', not 'learned'")
        if cfg.pipeline and "mamba" in cfg.layer_types:
            raise NotImplementedError(
                "pipeline=True stacks identical blocks; layer_types mixes "
                "two kinds")
        embed = nn.Embed(cfg.vocab_size, cfg.d_model, name="embedder",
                         dtype=cfg.dtype, param_dtype=cfg.param_dtype)
        x = constrain_residual(_times(embed(tokens),
                                      cfg.embedding_multiplier))
        if cfg.position == "learned":
            pos = positions if positions is not None else (
                jnp.arange(tokens.shape[1])[None, :])
            pos_emb = nn.Embed(cfg.max_seq_len, cfg.d_model, name="pos_embedder",
                               dtype=cfg.dtype, param_dtype=cfg.param_dtype)
            x = x + pos_emb(pos)
        if cfg.pipeline:
            x = PipelinedBlocks(cfg, name="pipeline")(x, mask=mask,
                                                      positions=positions)
            # The pipeline's output is replicated over pp; without a
            # constraint the final norm + lm head would run REDUNDANTLY on
            # every stage (round-1 verdict). Sharding the sequence dim over
            # pp makes GSPMD split that tail across stages instead.
            x = _shard_head_over_pp(x)
        else:
            use_remat = cfg.remat and not infer
            block = nn.remat(Block, static_argnums=()) if use_remat else Block
            for i in range(cfg.n_layers):
                blk = block(cfg, cfg.layer_kind(i), name=f"layer_{i}")
                if use_remat:
                    # remat traces every kwarg; the decode/prefill bools
                    # must stay Python-static, and here they are both False.
                    y = blk(x, mask=mask, positions=positions)
                else:
                    y = blk(x, mask=mask, positions=positions,
                            decode=decode, prefill=prefill, extend=extend,
                            seq_lengths=seq_lengths)
                x = constrain_residual(y)
        x = _norm(cfg, "norm_f")(x)
        if cfg.tie_embeddings:
            # Tied head reads the (unquantized) embedding table.
            logits = embed.attend(x.astype(cfg.param_dtype))
        else:
            logits = _proj(cfg, cfg.vocab_size, "lm_head")(x)
        if cfg.logits_scaling != 1.0:
            logits = logits / cfg.logits_scaling
        return logits
