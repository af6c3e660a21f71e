"""Secondary benchmark: causal-LM train-step throughput (tokens/sec/chip).

Not the driver's headline bench (that is ``bench.py`` at the repo root —
ResNet-18/CIFAR); this measures the transformer path, optionally comparing
the fused Pallas cross-entropy against the unfused loss:

    python benchmarks/lm_bench.py [--model llama_tiny] [--seq 512]
        [--batch 32] [--vocab 32000] [--compare-fused]

Prints one JSON line per configuration.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run(model: str, batch: int, seq: int, vocab: int, fused: bool,
        steps: int = 20, warmup: int = 3) -> dict:
    import jax

    from serverless_learn_tpu.config import (
        DataConfig, ExperimentConfig, MeshConfig, OptimizerConfig, TrainConfig)
    from serverless_learn_tpu.data.datasets import SyntheticSource
    from serverless_learn_tpu.training.train_step import build_trainer

    n_dev = len(jax.devices())
    cfg = ExperimentConfig(
        model=model,
        model_overrides={"fused_ce": fused, "vocab_size": vocab},
        mesh=MeshConfig(dp=n_dev),
        optimizer=OptimizerConfig(name="adamw", learning_rate=1e-3),
        train=TrainConfig(batch_size=batch * n_dev),
        data=DataConfig(seq_len=seq),
    )
    trainer = build_trainer(cfg)
    state = trainer.init()
    src = iter(SyntheticSource(trainer.bundle.make_batch, cfg.data,
                               cfg.train.batch_size, seed=0))
    b = trainer.shard_batch(next(src))
    for _ in range(warmup):
        state, metrics = trainer.step(state, b)
    float(jax.device_get(metrics["loss"]))  # scalar sync
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = trainer.step(state, b)
    float(jax.device_get(metrics["loss"]))
    dt = time.perf_counter() - t0
    tokens = cfg.train.batch_size * seq * steps
    return {
        "metric": f"{model}_train_tokens_per_sec_per_chip",
        "model": model, "batch_per_chip": batch, "seq": seq, "vocab": vocab,
        "fused_ce": fused,
        "value": round(tokens / dt / n_dev, 1),
        "unit": "tokens/sec/chip",
    }


def main():
    from serverless_learn_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="llama_tiny")
    ap.add_argument("--batch", type=int, default=32, help="per-chip batch")
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--vocab", type=int, default=32000)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--fused", action="store_true")
    ap.add_argument("--compare-fused", action="store_true",
                    help="run both fused and unfused loss")
    args = ap.parse_args()
    variants = [False, True] if args.compare_fused else [args.fused]
    for fused in variants:
        print(json.dumps(run(args.model, args.batch, args.seq, args.vocab,
                             fused, steps=args.steps)))


if __name__ == "__main__":
    main()
