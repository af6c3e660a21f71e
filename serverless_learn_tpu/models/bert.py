"""BERT-style masked-LM family — "BERT-base MLM (exercises shard streaming)"
rung of BASELINE.md's ladder.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from serverless_learn_tpu.models.registry import ModelBundle, register_model
from serverless_learn_tpu.models.transformer import Transformer, TransformerConfig
from serverless_learn_tpu.ops.losses import masked_lm_loss
from serverless_learn_tpu.ops.moe import apply_with_losses

MASK_TOKEN = 1  # synthetic vocab: 0=pad, 1=[MASK]


def _bert_cfg(size: str, **overrides) -> TransformerConfig:
    presets = {
        "tiny": dict(d_model=128, n_layers=2, n_heads=2, d_ff=512),
        "base": dict(d_model=768, n_layers=12, n_heads=12, d_ff=3072),
    }
    kw = dict(
        vocab_size=30522, max_seq_len=512, causal=False, position="learned",
        norm="layer", activation="gelu", tie_embeddings=False,
        # Both BERT data paths honor the suffix contract: the synthetic
        # make_batch emits all-ones masks, and the corpus pipeline's
        # mlm_transform derives attn_mask from suffix-padded rows — so
        # attention can run the flash kernel's kv_lengths path.
        suffix_padding_mask=True,
    )
    kw.update(presets[size])
    kw.update(overrides)
    return TransformerConfig(**kw)


def _bundle(cfg: TransformerConfig, mask_rate: float = 0.15):
    module = Transformer(cfg)

    def loss_fn(params, batch, rngs=None, model_state=None):
        # apply_with_losses so n_experts model_overrides keep their aux loss
        logits, aux = apply_with_losses(
            module, params, batch["tokens"],
            mask=batch["attn_mask"][:, None, None, :])
        loss, metrics = masked_lm_loss(logits, batch["labels"], batch["mlm_mask"])
        if cfg.n_experts > 0:
            metrics = dict(metrics, moe_aux_loss=aux)
        # loss_weight: the masked-token count this loss normalized by.
        # Gradient accumulation weights microbatch grads by it so accum runs
        # reproduce the whole-batch MLM gradient exactly (microbatches hold
        # different numbers of masked tokens). With n_experts > 0 the MoE
        # router aux loss (uniformly normalized) rides the same weighting,
        # so its gradient is approximate under accum — a deliberate trade:
        # the task loss stays exact, and the aux term is a regularizer.
        return loss + aux, {"metrics": metrics, "model_state": {},
                            "loss_weight": jnp.maximum(
                                batch["mlm_mask"].astype(jnp.float32).sum(),
                                1.0)}

    def input_spec(data_config, batch_size):
        T = data_config.seq_len
        i32 = jnp.int32
        return {
            "tokens": jax.ShapeDtypeStruct((batch_size, T), i32),
            "labels": jax.ShapeDtypeStruct((batch_size, T), i32),
            "mlm_mask": jax.ShapeDtypeStruct((batch_size, T), i32),
            "attn_mask": jax.ShapeDtypeStruct((batch_size, T), i32),
        }

    def make_batch(rng: np.random.Generator, data_config, batch_size):
        T = data_config.seq_len
        labels = rng.integers(2, cfg.vocab_size, (batch_size, T)).astype(np.int32)
        mlm_mask = (rng.random((batch_size, T)) < mask_rate).astype(np.int32)
        tokens = np.where(mlm_mask == 1, MASK_TOKEN, labels).astype(np.int32)
        return {
            "tokens": tokens,
            "labels": labels,
            "mlm_mask": mlm_mask,
            "attn_mask": np.ones((batch_size, T), np.int32),
        }

    return ModelBundle(module=module, loss_fn=loss_fn, input_spec=input_spec,
                       make_batch=make_batch, task="mlm")


@register_model("bert_tiny")
def make_bert_tiny(**overrides):
    return _bundle(_bert_cfg("tiny", **overrides))


@register_model("bert_base")
def make_bert_base(**overrides):
    return _bundle(_bert_cfg("base", **overrides))
