"""Model registry.

The reference has exactly one "model": an anonymous vector of doubles whose
training is simulated (``src/worker.cc:221-231``). The rebuild's config
ladder (BASELINE.md) spans MNIST MLP → ResNet-18/50 → BERT-base MLM →
Llama-style LoRA; each family registers a factory here keyed by the config's
``model`` string.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

_REGISTRY: Dict[str, Callable[..., "ModelBundle"]] = {}


@dataclass
class ModelBundle:
    """Everything the trainer needs to know about a model family."""

    module: Any  # flax.linen.Module
    loss_fn: Callable  # (module, params, batch, rngs) -> (loss, metrics)
    input_spec: Callable  # (data_config, batch) -> dict of ShapeDtypeStruct
    make_batch: Callable  # (rng, data_config, batch) -> batch pytree (host)
    task: str  # "classification" | "mlm" | "lm"
    trainable_mask: Optional[Callable] = None  # params -> bool pytree (LoRA)
    # Inference-mode loss (e.g. BatchNorm running stats instead of batch
    # stats). None => loss_fn is already deterministic and state-free.
    eval_loss_fn: Optional[Callable] = None


def register_model(name: str):
    def deco(fn):
        _REGISTRY[name] = fn
        return fn

    return deco


def get_model(name: str, **overrides) -> ModelBundle:
    # Import model modules lazily so the registry populates on first use.
    from serverless_learn_tpu.models import (  # noqa: F401
        mlp, resnet, bert, llama, moe, granite)

    if name not in _REGISTRY:
        raise KeyError(f"unknown model {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name](**overrides)


def list_models():
    from serverless_learn_tpu.models import (  # noqa: F401
        mlp, resnet, bert, llama, moe, granite)

    return sorted(_REGISTRY)
