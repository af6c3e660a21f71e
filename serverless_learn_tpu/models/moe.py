"""Mixture-of-experts causal-LM family (expert parallelism over ``ep``).

No counterpart exists in the reference (its model is an anonymous double
vector, ``src/protos/serverless_learn.proto:81-83``); this family completes
the parallelism-strategy checklist of SURVEY.md §2.9. Sizes: ``moe_tiny``
(tests/dryrun) and ``moe_mixtral_8x7b`` (Mixtral-8x7B-shaped: 32 layers,
8 experts, top-2, d_model 4096, d_ff 14336).

The task loss is causal-LM cross entropy plus the router load-balance
auxiliaries sown by ``ops/moe.MoELayer`` into the ``"losses"`` collection.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from serverless_learn_tpu.models.registry import ModelBundle, register_model
from serverless_learn_tpu.models.transformer import Transformer, TransformerConfig
from serverless_learn_tpu.ops.losses import causal_lm_loss
from serverless_learn_tpu.ops.moe import apply_with_losses


def _moe_cfg(size: str, **overrides) -> TransformerConfig:
    presets = {
        "tiny": dict(vocab_size=512, d_model=128, n_layers=2, n_heads=4,
                     n_kv_heads=2, d_ff=256, max_seq_len=512, n_experts=4,
                     moe_top_k=2),
        "mixtral_8x7b": dict(vocab_size=32000, d_model=4096, n_layers=32,
                             n_heads=32, n_kv_heads=8, d_ff=14336,
                             max_seq_len=8192, n_experts=8, moe_top_k=2,
                             rope_theta=1000000.0),
    }
    kw = dict(causal=True, position="rope", norm="rms", activation="swiglu")
    kw.update(presets[size])
    kw.update(overrides)
    return TransformerConfig(**kw)


def _bundle(cfg: TransformerConfig):
    module = Transformer(cfg)

    def loss_fn(params, batch, rngs=None, model_state=None):
        logits, aux = apply_with_losses(module, params, batch["tokens"])
        loss, metrics = causal_lm_loss(logits, batch["tokens"])
        metrics = dict(metrics)
        metrics["moe_aux_loss"] = aux
        return loss + aux, {"metrics": metrics, "model_state": {}}

    def input_spec(data_config, batch_size):
        return {"tokens": jax.ShapeDtypeStruct(
            (batch_size, data_config.seq_len), jnp.int32)}

    def make_batch(rng: np.random.Generator, data_config, batch_size):
        return {"tokens": rng.integers(
            0, cfg.vocab_size, (batch_size, data_config.seq_len)).astype(np.int32)}

    return ModelBundle(module=module, loss_fn=loss_fn, input_spec=input_spec,
                       make_batch=make_batch, task="lm")


@register_model("moe_tiny")
def make_moe_tiny(**overrides):
    return _bundle(_moe_cfg("tiny", **overrides))


@register_model("moe_mixtral_8x7b")
def make_moe_mixtral(**overrides):
    return _bundle(_moe_cfg("mixtral_8x7b", **overrides))
