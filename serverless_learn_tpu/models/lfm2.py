"""LFM2-MoE (Liquid AI's LFM2 family with experts): a causal LM whose
mixers come in two kinds, gated short convolutions and grouped-query
attention, three of the first to one of the second; leading dense
layers and then sparse-expert layers routed by sigmoid scores with a
selection bias, no shared expert; a tied head (``models/transformer.py``:
``layer_types`` kind ``conv``, ``ShortConvMixer``, ``qk_norm``, the
``moe_*`` fields; ``ops/moe.py``).

``lfm2_8b_a1b`` is LiquidAI/LFM2-8B-A1B at its published sizes
(config.json, ``model_type`` lfm2_moe): 24 layers (attention at 2, 6,
10, 14, 18 and 21, the rest convolutions of 3 taps), d_model 2048, 32
query heads over 8 KV heads of 64 with q/k norms and rotary positions
at theta 1e6, two dense layers of 7168, then 32 experts of 1792 (top 4,
sigmoid, renormalised); vocabulary 65,536; 8.34 B parameters.
``lfm2_moe_tiny`` is the same shape of model at a test's size, the
published pattern's first six layers.
"""

from __future__ import annotations

from serverless_learn_tpu.models.llama import _bundle
from serverless_learn_tpu.models.registry import register_model
from serverless_learn_tpu.models.transformer import TransformerConfig

ATTENTION_AT = (2, 6, 10, 14, 18, 21)


def layer_types(n_layers: int) -> tuple:
    """The published pattern's first ``n_layers`` layers."""
    return tuple("attention" if i in ATTENTION_AT else "conv"
                 for i in range(n_layers))


def _lfm2_cfg(size: str, **overrides) -> TransformerConfig:
    presets = {
        "tiny": dict(vocab_size=256, d_model=64, n_layers=6, n_heads=4,
                     n_kv_heads=2, d_ff=96, max_seq_len=256,
                     layer_types=layer_types(6), n_experts=8,
                     moe_top_k=2, moe_d_ff=32, moe_dense_layers=2),
        "8b-a1b": dict(vocab_size=65536, d_model=2048, n_layers=24,
                       n_heads=32, n_kv_heads=8, d_ff=7168,
                       max_seq_len=128000, layer_types=layer_types(24),
                       n_experts=32, moe_top_k=4, moe_d_ff=1792,
                       moe_dense_layers=2),
    }
    kw = dict(causal=True, position="rope", rope_theta=1e6, norm="rms",
              activation="swiglu", rms_norm_eps=1e-5, tie_embeddings=True,
              qk_norm=True, ssm_conv=3, moe_score="sigmoid",
              moe_route_norm=True, moe_route_scale=1.0,
              moe_select_bias=True)
    kw.update(presets[size])
    kw.update(overrides)
    return TransformerConfig(**kw)


@register_model("lfm2_moe_tiny")
def make_lfm2_moe_tiny(fused_ce=False, **overrides):
    return _bundle(_lfm2_cfg("tiny", **overrides), fused_ce=fused_ce)


@register_model("lfm2_8b_a1b")
def make_lfm2_8b_a1b(fused_ce=False, **overrides):
    return _bundle(_lfm2_cfg("8b-a1b", **overrides), fused_ce=fused_ce)
