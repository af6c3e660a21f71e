"""AFMoE (Arcee's Trinity family): a causal LM whose attention layers come
in two kinds, window layers that rotate and full layers with no
positional term, three of the first to one of the second; leading dense
layers and then sparse-expert layers with a shared expert, routed by
sigmoid scores with a selection bias (``models/transformer.py``:
``layer_types``, ``sliding_window``, ``qk_norm``, ``attn_output_gate``,
``post_norms``, the ``moe_*`` fields; ``ops/moe.py``).

``trinity_mini`` is arcee-ai/Trinity-Mini at its published sizes
(config.json, ``model_type`` afmoe): 32 layers, d_model 2048, 32 query
heads over 4 KV heads of 128, a window of 2048, two dense layers of
6144, then 128 experts of 1024 (top 8, sigmoid, renormalised, times
2.826) and one shared expert of 1024; vocabulary 200,192, untied head;
the embedding times sqrt(d_model) (``mup_enabled``). ``afmoe_tiny`` is
the same shape of model at a test's size.
"""

from __future__ import annotations

import math

from serverless_learn_tpu.models.llama import _bundle
from serverless_learn_tpu.models.registry import register_model
from serverless_learn_tpu.models.transformer import TransformerConfig


def layer_types(n_layers: int) -> tuple:
    """(window, window, window, full) repeated: the published pattern."""
    return tuple("full_nope" if i % 4 == 3 else "window_rope"
                 for i in range(n_layers))


def _afmoe_cfg(size: str, **overrides) -> TransformerConfig:
    presets = {
        "tiny": dict(vocab_size=256, d_model=64, n_layers=6, n_heads=4,
                     n_kv_heads=2, head_dim_override=16, d_ff=96,
                     max_seq_len=256, layer_types=layer_types(6),
                     sliding_window=16, n_experts=8, moe_top_k=2,
                     moe_d_ff=32, moe_shared_d_ff=32, moe_dense_layers=2,
                     embedding_multiplier=8.0),
        "mini": dict(vocab_size=200192, d_model=2048, n_layers=32,
                     n_heads=32, n_kv_heads=4, head_dim_override=128,
                     d_ff=6144, max_seq_len=131072,
                     layer_types=layer_types(32), sliding_window=2048,
                     n_experts=128, moe_top_k=8, moe_d_ff=1024,
                     moe_shared_d_ff=1024, moe_dense_layers=2,
                     moe_aux_weight=0.001,
                     embedding_multiplier=math.sqrt(2048)),
    }
    kw = dict(causal=True, position="none", norm="rms", activation="swiglu",
              rms_norm_eps=1e-5, rope_theta=10000.0, tie_embeddings=False,
              qk_norm=True, attn_output_gate=True, post_norms=True,
              moe_score="sigmoid", moe_route_norm=True,
              moe_route_scale=2.826, moe_select_bias=True)
    kw.update(presets[size])
    kw.update(overrides)
    return TransformerConfig(**kw)


@register_model("afmoe_tiny")
def make_afmoe_tiny(fused_ce=False, **overrides):
    return _bundle(_afmoe_cfg("tiny", **overrides), fused_ce=fused_ce)


@register_model("trinity_mini")
def make_trinity_mini(fused_ce=False, **overrides):
    return _bundle(_afmoe_cfg("mini", **overrides), fused_ce=fused_ce)
