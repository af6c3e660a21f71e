"""Llama-style causal-LM family — final rung of BASELINE.md's ladder
("Llama-3-8B LoRA fine-tune (stretch: elastic serverless workers on TPU pod)").

Sizes: ``llama_tiny`` (tests), ``llama_1b``, ``llama_8b`` (Llama-3-8B-shaped:
32 layers, 32 heads / 8 KV heads, d_model 4096, d_ff 14336, vocab 128256).
``lora_rank > 0`` adds frozen-base LoRA adapters on Q/V projections; the
bundle's ``trainable_mask`` confines the optimizer to adapter params.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from serverless_learn_tpu.models.registry import ModelBundle, register_model
from serverless_learn_tpu.models.transformer import Transformer, TransformerConfig
from serverless_learn_tpu.ops.losses import causal_lm_loss
from serverless_learn_tpu.ops.moe import apply_with_losses


def _llama_cfg(size: str, **overrides) -> TransformerConfig:
    presets = {
        "tiny": dict(vocab_size=512, d_model=128, n_layers=2, n_heads=4,
                     n_kv_heads=2, d_ff=512, max_seq_len=512),
        "1b": dict(vocab_size=128256, d_model=2048, n_layers=16, n_heads=32,
                   n_kv_heads=8, d_ff=8192, max_seq_len=8192),
        "8b": dict(vocab_size=128256, d_model=4096, n_layers=32, n_heads=32,
                   n_kv_heads=8, d_ff=14336, max_seq_len=8192,
                   rope_theta=500000.0),
    }
    kw = dict(causal=True, position="rope", norm="rms", activation="swiglu")
    kw.update(presets[size])
    kw.update(overrides)
    return TransformerConfig(**kw)


def _bundle(cfg: TransformerConfig, fused_ce: bool = False):
    module = Transformer(cfg)

    def loss_fn(params, batch, rngs=None, model_state=None):
        # apply_with_losses so n_experts model_overrides keep their aux loss
        logits, aux = apply_with_losses(module, params, batch["tokens"])
        loss, metrics = causal_lm_loss(logits, batch["tokens"], fused=fused_ce)
        if cfg.n_experts > 0:
            metrics = dict(metrics, moe_aux_loss=aux)
        return loss + aux, {"metrics": metrics, "model_state": {}}

    def input_spec(data_config, batch_size):
        return {"tokens": jax.ShapeDtypeStruct(
            (batch_size, data_config.seq_len), jnp.int32)}

    def make_batch(rng: np.random.Generator, data_config, batch_size):
        return {"tokens": rng.integers(
            0, cfg.vocab_size, (batch_size, data_config.seq_len)).astype(np.int32)}

    bundle = ModelBundle(module=module, loss_fn=loss_fn, input_spec=input_spec,
                         make_batch=make_batch, task="lm")
    if cfg.lora_rank > 0:
        bundle.trainable_mask = lora_trainable_mask
    return bundle


def lora_trainable_mask(params):
    """Pytree of bools: True only on LoRA adapter params (frozen base)."""

    def one(path, leaf):
        keys = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        return any(str(k).startswith("lora_") or str(k).endswith("_lora")
                   for k in keys)

    return jax.tree_util.tree_map_with_path(one, params)


@register_model("llama_tiny")
def make_llama_tiny(fused_ce=False, **overrides):
    return _bundle(_llama_cfg("tiny", **overrides), fused_ce=fused_ce)


@register_model("llama_1b")
def make_llama_1b(fused_ce=False, **overrides):
    # fused_ce=True opts into the Pallas loss kernel. Off by default: on the
    # v5e chip this was benchmarked on, XLA fuses the unfused loss into the
    # lm_head matmul epilogue and wins (13.6 ms vs 14.9 ms for the kernel at
    # N=8192, V=32000 — benchmarks/lm_bench.py --compare-fused). Re-measure
    # per hardware/scale before enabling.
    return _bundle(_llama_cfg("1b", **overrides), fused_ce=fused_ce)


@register_model("llama_8b")
def make_llama_8b(fused_ce=False, **overrides):
    return _bundle(_llama_cfg("8b", **overrides), fused_ce=fused_ce)
