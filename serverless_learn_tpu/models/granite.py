"""Granite 4.0-H: a hybrid causal LM, Mamba-2 layers with an attention
layer every so often, SwiGLU MLPs, no positional term of any kind
(``models/transformer.py``: ``layer_types``, ``MambaMixer``,
``position="none"``), a tied head, and the family's four fixed scalars.

``granite_4_0_h_micro`` is ibm-granite/granite-4.0-h-micro at its
published sizes (config.json, ``model_type`` granitemoehybrid with no
experts: dense): 40 layers, attention at 5, 15, 25 and 35, d_model 2048,
32 query heads over 8 KV heads of 64, MLP 8192, 64 Mamba heads of 64 over
a state of 128, vocabulary 100,352: 3.19 B parameters.
``granite_hybrid_tiny`` is the same shape of model at a test's size, an
attention layer inside its period of four.
"""

from __future__ import annotations

from serverless_learn_tpu.models.llama import _bundle
from serverless_learn_tpu.models.registry import register_model
from serverless_learn_tpu.models.transformer import TransformerConfig


def _layer_types(n_layers: int, attention_at: tuple) -> tuple:
    return tuple("attention" if i in attention_at else "mamba"
                 for i in range(n_layers))


def _granite_cfg(size: str, **overrides) -> TransformerConfig:
    presets = {
        "tiny": dict(vocab_size=256, d_model=64, n_layers=4, n_heads=4,
                     n_kv_heads=2, d_ff=128, max_seq_len=256,
                     layer_types=_layer_types(4, (2,)),
                     ssm_heads=8, ssm_head_dim=16, ssm_state=16,
                     ssm_chunk=8, embedding_multiplier=3.0,
                     residual_multiplier=0.5, logits_scaling=2.0,
                     attention_multiplier=1.0 / 8),
        "4.0-h-micro": dict(
            vocab_size=100352, d_model=2048, n_layers=40, n_heads=32,
            n_kv_heads=8, d_ff=8192, max_seq_len=131072,
            layer_types=_layer_types(40, (5, 15, 25, 35)),
            ssm_heads=64, ssm_head_dim=64, ssm_state=128, ssm_chunk=256,
            embedding_multiplier=12.0, residual_multiplier=0.22,
            logits_scaling=8.0, attention_multiplier=1.0 / 64),
    }
    kw = dict(causal=True, position="none", norm="rms", activation="swiglu",
              rms_norm_eps=1e-5, tie_embeddings=True, ssm_groups=1,
              ssm_conv=4)
    kw.update(presets[size])
    kw.update(overrides)
    return TransformerConfig(**kw)


@register_model("granite_hybrid_tiny")
def make_granite_hybrid_tiny(fused_ce=False, **overrides):
    return _bundle(_granite_cfg("tiny", **overrides), fused_ce=fused_ce)


@register_model("granite_4_0_h_micro")
def make_granite_4_0_h_micro(fused_ce=False, **overrides):
    return _bundle(_granite_cfg("4.0-h-micro", **overrides),
                   fused_ce=fused_ce)
