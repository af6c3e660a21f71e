"""Re-measure EVERY README ladder row through the shared regression guard.

    python benchmarks/ladder.py [--rows r18,r50,...]

One JSON line per row, each appended to the repo-root ``bench_history.json``
via ``utils/benchlog.record`` — so every README number is reproducible by
one command and drift-flagged (>5% vs the best comparable historical entry;
timing rows widen the threshold by their measured spread). Exit code 1 if
any row flagged a regression; rows still all run and report.

Rows (chip-side unless noted):
    r18        ResNet-18/CIFAR headline (the driver's bench.py, 3% guard)
    r18nf      ResNet-18 norm="none" (NF recipe, guarded since r4)
    r50        ResNet-50/ImageNet-shape b256
    r50nf      ResNet-50 norm="none"
    r50da      ResNet-50 with device-side crop+flip augmentation
    bert       BERT-base MLM b64 seq512
    llama1b    Llama-1B LoRA b8 seq1024 bf16+remat
    lm         llama_tiny-architecture LM seq512 (benchmarks/lm_bench.py)
    flash      flash-attention fwd+bwd T=8192 causal — min of 11 with the
               uncontended-cluster spread (the distribution is bimodal
               under chip sharing; median + full times ride along)
    decode     KV-cache decode tokens/sec (llama_tiny b8)
    decode8    weight-only int8 decode vs bf16 (llama_1b; capacity win,
               honest throughput cost)
    decodemoe  MoE decode (moe_tiny, per-token top-2 routing)
    spec       speculative decode (llama_1b, 4-layer prefix draft, K=4;
               acceptance recorded — weight-dependent)
    serve      4-client serving aggregate vs serialized under staggered
               arrivals (aggregate + p50/p95)
    llama8b    8B-width per-layer step time on real silicon (labeled
               extrapolation to the full model)
    llama8b_real  REAL full-depth Llama-8B on ONE chip: QLoRA train step
               (int8 frozen base + bf16 LoRA + remat) and int8 decode —
               the measured rung 5 (round 5)
    localsgd   Local SGD communication-interval sweep (r18, BatchNorm)
    data       shard-server raw stream + CIFAR ingest + ImageNet ingest
               (host-crop, device-augment, parallel-source scaling;
               host-side, no chip needed)
"""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from serverless_learn_tpu.utils.benchlog import record as record_history  # noqa: E402

HISTORY = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench_history.json")


def _device_kind():
    import jax

    return jax.devices()[0].device_kind


def _train_row(metric, model, batch_per_chip, seq=None, overrides=None,
               opt=None, steps=10, unit_tokens=False, train_kw=None):
    import jax

    from serverless_learn_tpu.config import (
        DataConfig, ExperimentConfig, MeshConfig, OptimizerConfig,
        TrainConfig)
    from serverless_learn_tpu.data.datasets import SyntheticSource
    from serverless_learn_tpu.training.train_step import build_trainer
    from serverless_learn_tpu.utils.flops import compiled_step_flops, mfu

    n_dev = len(jax.devices())
    batch = batch_per_chip * n_dev
    cfg = ExperimentConfig(
        model=model,
        model_overrides=overrides or {},
        mesh=MeshConfig(dp=n_dev),
        optimizer=opt or OptimizerConfig(name="adamw", learning_rate=1e-3),
        train=TrainConfig(batch_size=batch, **(train_kw or {})),
        data=DataConfig(seq_len=seq) if seq else DataConfig(),
    )
    trainer = build_trainer(cfg)
    state = trainer.init()
    src = iter(SyntheticSource(trainer.bundle.make_batch, cfg.data, batch,
                               seed=0))
    b = trainer.shard_batch(next(src))
    for _ in range(3):
        state, m = trainer.step(state, b)
    float(jax.device_get(m["loss"]))
    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = trainer.step(state, b)
    float(jax.device_get(m["loss"]))
    step_s = (time.perf_counter() - t0) / steps
    per_chip = batch / step_s / n_dev
    if unit_tokens:
        per_chip *= seq
    rec = {
        "metric": metric,
        "value": round(per_chip, 1),
        "unit": ("tokens/sec/chip" if unit_tokens else "samples/sec/chip"),
        "batch_per_chip": batch_per_chip,
        "device_kind": _device_kind(),
        "step_time_ms": round(step_s * 1e3, 2),
    }
    u = mfu(compiled_step_flops(trainer.step_fn, state, b, n_devices=n_dev),
            step_s, n_chips=n_dev)
    if u is not None:
        rec["mfu"] = round(u, 4)
    return rec


def row_r18():
    sys.path.insert(0, os.path.dirname(HISTORY))
    import bench

    return record_history(bench.measure(), HISTORY, better="max",
                          rel_threshold=0.03,
                          key_fields=("metric", "device_kind",
                                      "batch_per_chip"))


def row_r50():
    from serverless_learn_tpu.config import OptimizerConfig

    rec = _train_row(
        "resnet50_imagenet_train_samples_per_sec_per_chip",
        "resnet50_imagenet", batch_per_chip=256,
        opt=OptimizerConfig(name="sgd", learning_rate=0.1, momentum=0.9),
        steps=5)
    return record_history(rec, HISTORY, better="max",
                          key_fields=("metric", "device_kind",
                                      "batch_per_chip"))


def row_r18nf():
    """ResNet-18 with norm="none" (NF-style scale+bias, zero-init residual
    scales) as a FIRST-CLASS guarded row — round-3 verdict #6 promoted it
    out of its footnote. Captures the full measured 8.6% BN cost; the
    training recipe itself is pinned by tests/test_resnet_norms.py."""
    from serverless_learn_tpu.config import OptimizerConfig

    rec = _train_row(
        "resnet18_cifar_nfnorm_train_samples_per_sec_per_chip",
        "resnet18_cifar", batch_per_chip=4096,
        overrides={"norm": "none"},
        opt=OptimizerConfig(name="sgd", learning_rate=0.1, momentum=0.9),
        steps=10)
    return record_history(rec, HISTORY, better="max", rel_threshold=0.03,
                          key_fields=("metric", "device_kind",
                                      "batch_per_chip"))


def row_r50nf():
    """ResNet-50 norm="none" (measured +10% over BN in round 3: 2,518
    samples/s, 30.4% MFU) as a guarded row."""
    from serverless_learn_tpu.config import OptimizerConfig

    rec = _train_row(
        "resnet50_imagenet_nfnorm_train_samples_per_sec_per_chip",
        "resnet50_imagenet", batch_per_chip=256,
        overrides={"norm": "none"},
        opt=OptimizerConfig(name="sgd", learning_rate=0.1, momentum=0.9),
        steps=5)
    return record_history(rec, HISTORY, better="max",
                          key_fields=("metric", "device_kind",
                                      "batch_per_chip"))


def row_r50da():
    """ResNet-50 with DEVICE-side augmentation (round-4 data-plane
    geometry): batches carry stored-size 256x256 uint8 records and the
    step crops+flips on device from its PRNG. The row prices what that
    costs the chip (expected ~free: one gather + select against 100+ ms
    of convs) — the host-side win is measured in data_bench."""
    from serverless_learn_tpu.config import OptimizerConfig

    rec = _train_row(
        "resnet50_imagenet_device_aug_train_samples_per_sec_per_chip",
        "resnet50_imagenet", batch_per_chip=256,
        overrides={"device_augment": True},
        opt=OptimizerConfig(name="sgd", learning_rate=0.1, momentum=0.9),
        steps=5)
    return record_history(rec, HISTORY, better="max",
                          key_fields=("metric", "device_kind",
                                      "batch_per_chip"))


def row_bert():
    rec = _train_row(
        "bert_base_mlm_train_tokens_per_sec_per_chip", "bert_base",
        batch_per_chip=64, seq=512, unit_tokens=True, steps=10)
    return record_history(rec, HISTORY, better="max",
                          key_fields=("metric", "device_kind",
                                      "batch_per_chip"))


def row_llama1b():
    rec = _train_row(
        "llama1b_lora_train_tokens_per_sec_per_chip", "llama_1b",
        batch_per_chip=8, seq=1024,
        overrides={"lora_rank": 16}, train_kw={"remat": True},
        steps=5, unit_tokens=True)
    return record_history(rec, HISTORY, better="max",
                          key_fields=("metric", "device_kind",
                                      "batch_per_chip"))


def row_lm():
    from benchmarks.lm_bench import run as lm_run

    rec = lm_run("llama_tiny", batch=32, seq=512, vocab=32000, fused=False,
                 steps=10)
    rec["device_kind"] = _device_kind()
    return record_history(rec, HISTORY, better="max",
                          key_fields=("metric", "device_kind",
                                      "batch_per_chip", "seq", "vocab"))


def row_flash(repeats=11):
    """Flash fwd+bwd at T=8192 causal — MIN of ``repeats``, with the
    low-cluster spread.

    Round 3 recorded median-of-5 with min-max spread 0.41-0.45 — so wide
    a 30-40% real regression would pass the guard (verdict #9). Measured
    11-rep distributions on a SHARED chip were BIMODAL
    (13-14 ms uncontended vs 17-23 ms under contention; e.g.
    [13.2, 13.3, 13.5, 14.0, 16.5, 17.2, ... 23.0]), so median and IQR
    both straddle the modes and stay noisy. Contention only ever ADDS
    time, so the minimum estimates the true kernel cost; the recorded
    spread is (p25 - min)/min — the width of the uncontended cluster —
    which keeps the guard threshold tight (~5-10%). The median and full
    times ride along for honesty about the distribution."""
    import jax
    import jax.numpy as jnp

    from serverless_learn_tpu.ops.pallas.flash_attention import (
        flash_attention)

    B, T, H, D = 1, 8192, 8, 64
    rng = jax.random.PRNGKey(0)
    q = jax.random.normal(rng, (B, T, H, D), jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(rng, 1), (B, T, H, D),
                          jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(rng, 2), (B, T, H, D),
                          jnp.bfloat16)

    f = jax.jit(jax.grad(lambda q, k, v: flash_attention(
        q, k, v, causal=True).astype(jnp.float32).sum(), argnums=(0, 1, 2)))

    inner = 10

    def once():
        """ms per fwd+bwd, amortized over ``inner`` dispatches: a per-call
        scalar fetch would time the host round trip, not the kernel."""
        t0 = time.perf_counter()
        for _ in range(inner):
            g = f(q, k, v)
        float(jax.device_get(jnp.sum(g[0].astype(jnp.float32))))
        return (time.perf_counter() - t0) * 1e3 / inner

    once()  # compile + warm
    times = sorted(once() for _ in range(repeats))
    lo = times[0]
    p25 = times[min(len(times) - 1, max(1, repeats // 4))]
    spread = (p25 - lo) / lo if lo else 0.0
    rec = {
        "metric": "flash_attention_fwd_bwd_t8192_causal_ms",
        "value": round(lo, 2),
        "unit": "ms (min of %d)" % repeats,
        "spread_rel": round(spread, 4),  # uncontended-cluster width
        "median_ms": round(statistics.median(times), 2),
        "times_ms": [round(t, 2) for t in times],
        "device_kind": _device_kind(),
    }
    return record_history(rec, HISTORY, better="min",
                          key_fields=("metric", "device_kind"))


def row_decode():
    from benchmarks.gen_bench import run as gen_run

    rec = gen_run("llama_tiny", batch=8, prompt_len=128, new_tokens=128)
    rec["device_kind"] = _device_kind()
    return record_history(rec, HISTORY, better="max",
                          key_fields=("metric", "device_kind", "batch",
                                      "prompt_len", "new_tokens"))


def row_decodemoe():
    """MoE decode (round-5 verdict #3): KV-cache generation through
    per-token expert routing (moe_tiny: 4 experts, top-2). Exactness is
    pinned by tests/test_moe_generate.py; this row prices it — decode
    compute per token is ~top_k/n_experts of the dense-equivalent FFN
    plus routing overhead, and the row guards that serving a MoE stays
    within the decode family's envelope."""
    from benchmarks.gen_bench import run as gen_run

    rec = _best_of(lambda: gen_run("moe_tiny", batch=8, prompt_len=128,
                                   new_tokens=64, iters=3))
    rec["device_kind"] = _device_kind()
    return record_history(rec, HISTORY, better="max", rel_threshold=0.15,
                          key_fields=("metric", "device_kind", "batch",
                                      "prompt_len", "new_tokens"))


def row_llama8b_width():
    """8B-width on REAL silicon (round-3 verdict #7): every 8B artifact so
    far was abstract or compile-only. A 2-layer and a 4-layer slice of
    llama_8b (TRUE widths: d_model 4096, d_ff 14336, 32 heads/8 KV, vocab
    128256; LoRA + remat, bf16) both fit one v5e chip; their step-time
    difference isolates the marginal per-layer cost, and
    t(32) = t(2) + 30 x layer_ms extrapolates the full model. The
    extrapolated tokens/s is clearly labeled ESTIMATE: it assumes layer
    cost stays constant with depth (true under remat — each layer's
    weights and activation working set are depth-independent) and that
    32 layers' weights fit the target chip, which they do NOT on one v5e
    — the estimate prices the compute, pricing a sharded deployment's
    per-chip step where weights are fsdp-resident."""
    import jax

    from serverless_learn_tpu.config import (
        DataConfig, ExperimentConfig, MeshConfig, OptimizerConfig,
        TrainConfig)
    from serverless_learn_tpu.data.datasets import SyntheticSource
    from serverless_learn_tpu.training.train_step import build_trainer
    from serverless_learn_tpu.utils.flops import compiled_step_flops, mfu

    batch, seq = 4, 1024

    def step_time(n_layers, steps=6):
        cfg = ExperimentConfig(
            model="llama_8b",
            model_overrides=dict(n_layers=n_layers, lora_rank=16,
                                 max_seq_len=seq),
            mesh=MeshConfig(dp=len(jax.devices())),
            optimizer=OptimizerConfig(name="adamw", learning_rate=2e-4),
            train=TrainConfig(batch_size=batch * len(jax.devices()),
                              remat=True),
            data=DataConfig(seq_len=seq))
        trainer = build_trainer(cfg)
        state = trainer.init()
        src = iter(SyntheticSource(trainer.bundle.make_batch, cfg.data,
                                   cfg.train.batch_size, seed=0))
        b = trainer.shard_batch(next(src))
        for _ in range(3):
            state, m = trainer.step(state, b)
        float(jax.device_get(m["loss"]))
        t0 = time.perf_counter()
        for _ in range(steps):
            state, m = trainer.step(state, b)
        float(jax.device_get(m["loss"]))
        dt = (time.perf_counter() - t0) / steps
        fl = compiled_step_flops(trainer.step_fn, state, b,
                                 n_devices=len(jax.devices()))
        return dt, fl

    t2, f2 = step_time(2)
    t4, f4 = step_time(4)
    layer_s = (t4 - t2) / 2
    flops_layer = None if (f2 is None or f4 is None) else (f4 - f2) / 2
    t32 = t2 + 30 * layer_s
    tokens = batch * seq
    rec = {
        "metric": "llama8b_width_layer_ms",
        "value": round(layer_s * 1e3, 2),
        "unit": "ms/layer (b%d seq%d bf16 LoRA remat)" % (batch, seq),
        "step_ms_2layer": round(t2 * 1e3, 1),
        "step_ms_4layer": round(t4 * 1e3, 1),
        "extrapolated_full_8b_step_ms": round(t32 * 1e3, 1),
        "extrapolated_full_8b_tokens_per_sec_per_chip":
            round(tokens / t32, 1),
        "extrapolation_note": "t(32)=t(2)+30*layer; compute-price of a "
                              "weight-sharded deployment, NOT a one-chip "
                              "fit",
        "device_kind": _device_kind(),
    }
    if flops_layer is not None and f2 is not None:
        u = mfu(f2 + 30 * flops_layer, t32, n_chips=1)
        if u is not None:
            rec["extrapolated_full_8b_mfu"] = round(u, 4)
    return record_history(rec, HISTORY, better="min",
                          key_fields=("metric", "device_kind"))


def row_llama8b_real():
    """A REAL full-depth Llama-8B on ONE v5e chip (round-5 verdict #1 —
    replaces the rung-5 extrapolation with silicon).

    The round-4 int8 capacity win is the tool: the 8B base stored
    weight-only int8 is ~7.5 GB resident (vs 16 GB bf16, which cannot
    even load), leaving room for bf16 LoRA adapters + their adam moments,
    remat'd activations, and the KV cache. Two measurements:

    * QLoRA train step: int8 FROZEN base + bf16 LoRA (rank 16, q/v),
      remat, b4 seq1024. The partitioned trainer
      (``training/partition.py``) differentiates ONLY the LoRA subtree —
      an int8 base has no gradients, by construction not just by masking.
    * greedy decode at b8: prefill 128, 64 new tokens.

    Honest notes recorded in-row: params are RANDOM in the int8 layout
    (``random_quantized_params``) — identical compute graph and memory
    footprint to a quantized trained checkpoint, but nobody has measured
    fine-tune QUALITY here; the gradient-quality claim (LoRA grads through
    an int8 base track the bf16-base grads) is pinned by
    ``tests/test_qlora.py`` at small scale, not at 8B."""
    import jax
    import jax.numpy as jnp

    from benchmarks.gen_bench import run as gen_run
    from serverless_learn_tpu.config import (
        DataConfig, ExperimentConfig, MeshConfig, OptimizerConfig,
        TrainConfig)
    from serverless_learn_tpu.data.datasets import SyntheticSource
    from serverless_learn_tpu.inference.quantize import (
        random_quantized_params)
    from serverless_learn_tpu.training.train_step import build_trainer
    from serverless_learn_tpu.utils.flops import compiled_step_flops, mfu

    batch, seq = 4, 1024
    cfg = ExperimentConfig(
        model="llama_8b",
        model_overrides=dict(lora_rank=16, quant="int8", max_seq_len=seq,
                             param_dtype=jnp.bfloat16),
        mesh=MeshConfig(dp=len(jax.devices())),
        optimizer=OptimizerConfig(name="adamw", learning_rate=2e-4),
        train=TrainConfig(batch_size=batch * len(jax.devices()), remat=True),
        data=DataConfig(seq_len=seq))
    trainer = build_trainer(cfg)
    # Build the state MANUALLY from one random int8-layout tree:
    # trainer.init() would allocate a zero-init 7.5 GB base that then
    # coexists with its random replacement — ~15 GB of base weights on a
    # 16 GB chip. The optimizer state only covers the LoRA subtree
    # (training/partition.py), so it is cheap to init directly.
    from serverless_learn_tpu.training.optimizer import make_optimizer
    from serverless_learn_tpu.training.partition import prune
    from serverless_learn_tpu.training.train_state import TrainState

    params = random_quantized_params(trainer.bundle.module)
    tx = make_optimizer(cfg.optimizer)
    opt_state = tx.init(prune(params,
                              trainer.bundle.trainable_mask(params)))
    state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                       opt_state=opt_state, model_state={})
    src = iter(SyntheticSource(trainer.bundle.make_batch, cfg.data,
                               cfg.train.batch_size, seed=0))
    b = trainer.shard_batch(next(src))
    for _ in range(2):
        state, m = trainer.step(state, b)
    float(jax.device_get(m["loss"]))
    steps = 5
    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = trainer.step(state, b)
    float(jax.device_get(m["loss"]))
    step_s = (time.perf_counter() - t0) / steps
    tokens_s = batch * seq / step_s
    rec = {
        "metric": "llama8b_real_qlora_train_tokens_per_sec_per_chip",
        "value": round(tokens_s, 1),
        "unit": "tokens/sec/chip (b%d seq%d int8 base + bf16 LoRA, remat)"
                % (batch, seq),
        "step_time_ms": round(step_s * 1e3, 1),
        "batch_per_chip": batch,
        "params_note": "random int8-layout params; compute graph and "
                       "memory identical to a quantized checkpoint",
        "device_kind": _device_kind(),
    }
    u = mfu(compiled_step_flops(trainer.step_fn, state, b, n_devices=1),
            step_s, n_chips=1)
    if u is not None:
        rec["mfu"] = round(u, 4)
    out = [record_history(rec, HISTORY, better="max", rel_threshold=0.10,
                          key_fields=("metric", "device_kind",
                                      "batch_per_chip"))]
    # Free the training state before decode loads its own 7.5 GB copy.
    del state, trainer, b, src

    dec = gen_run("llama_8b", batch=8, prompt_len=128, new_tokens=64,
                  iters=3, quant="int8", quant_direct=True,
                  model_kw=dict(max_seq_len=512,
                                param_dtype=jnp.bfloat16))
    dec["metric"] = "llama8b_real_int8_decode_tokens_per_sec"
    dec["device_kind"] = _device_kind()
    out.append(record_history(dec, HISTORY, better="max", rel_threshold=0.15,
                              key_fields=("metric", "device_kind", "batch",
                                          "prompt_len", "new_tokens")))
    return out


def _best_of(fn, repeats=3):
    """Best-of-N for throughput rows on the shared chip (the flash-row
    treatment, round-5 verdict #6): contention only ever SUBTRACTS
    throughput, so the max estimates the uncontended rate; the recorded
    ``spread_rel`` (max-min)/max widens the guard via benchlog and keeps
    the distribution honest in-row."""
    recs = sorted((fn() for _ in range(repeats)), key=lambda r: r["value"])
    best = dict(recs[-1])
    best["spread_rel"] = round(
        (best["value"] - recs[0]["value"]) / max(best["value"], 1e-9), 4)
    best["values_all"] = [r["value"] for r in recs]
    return best


def row_decode8():
    """Weight-only int8 decode (round 4): llama_1b, int8 vs the same-shape
    bf16 baseline. The HONEST reading of this row: int8 halves resident
    weight memory (the capacity win); the RATIO guards that the
    throughput cost of the memory win stays bounded. Round 5 round 2 of
    methodology: the arms are INTERLEAVED pairwise — measuring all of one
    arm then all of the other let shared-chip contention land on one arm
    only (observed: bf16 785 tokens/s in a quiet window vs 476 under
    contention an hour later, flipping the 'ratio' from 0.61x to 1.66x
    with spread 0.4 inside each arm). Per-pair ratios ride in-row; the
    reported ratio is best-int8 / best-bf16 across interleaved pairs."""
    import jax.numpy as jnp

    from benchmarks.gen_bench import run as gen_run

    kw = dict(max_seq_len=512, dtype=jnp.bfloat16,
              param_dtype=jnp.bfloat16)
    pairs = []
    for _ in range(3):
        b = gen_run("llama_1b", batch=8, prompt_len=128, new_tokens=64,
                    iters=3, model_kw=kw)
        q = gen_run("llama_1b", batch=8, prompt_len=128, new_tokens=64,
                    iters=3, quant="int8", model_kw=kw)
        pairs.append((b, q))
    best_b = max(p[0]["value"] for p in pairs)
    best_q = max(p[1]["value"] for p in pairs)
    rec = dict(max((p[1] for p in pairs), key=lambda r: r["value"]))
    rec["bf16_tokens_per_sec"] = best_b
    rec["bf16_values_all"] = [p[0]["value"] for p in pairs]
    rec["values_all"] = [p[1]["value"] for p in pairs]
    rec["pair_ratios"] = [round(p[1]["value"] / p[0]["value"], 2)
                          for p in pairs]
    rec["int8_speedup_vs_bf16"] = round(best_q / best_b, 2)
    lo_q, lo_b = min(rec["values_all"]), min(rec["bf16_values_all"])
    rec["spread_rel"] = round(max((best_q - lo_q) / best_q,
                                  (best_b - lo_b) / best_b), 4)
    rec["device_kind"] = _device_kind()
    return record_history(rec, HISTORY, better="max", rel_threshold=0.15,
                          key_fields=("metric", "device_kind", "batch",
                                      "prompt_len", "new_tokens"))


def row_spec():
    """Speculative decoding (round 5): prefix-draft + one-pass verify on
    llama_1b. Exactness is free (greedy verify); throughput hinges on
    acceptance, a WEIGHTS property — recorded in-row next to tokens/s,
    with a self-draft arm pricing the mechanism ceiling."""
    import jax.numpy as jnp

    from benchmarks.gen_bench import run_speculative

    rec = run_speculative("llama_1b", draft_layers=4, K=4, batch=8,
                          prompt_len=128, new_tokens=64, iters=3,
                          model_kw=dict(max_seq_len=512,
                                        dtype=jnp.bfloat16,
                                        param_dtype=jnp.bfloat16))
    rec["device_kind"] = _device_kind()
    return record_history(rec, HISTORY, better="max", rel_threshold=0.15,
                          key_fields=("metric", "device_kind", "batch",
                                      "prompt_len", "new_tokens", "K",
                                      "draft_layers"))


def row_serve():
    """Multi-client serving aggregate against the serialized engine under
    STAGGERED arrivals: 40 ms per client, so requests land between chunk
    boundaries and are admitted at the next one. Best-of-3 with recorded
    spread (single-sample serve runs tripped the guard)."""
    from benchmarks.gen_bench import run_concurrent

    rec = _best_of(lambda: run_concurrent(
        "llama_tiny", clients=4, prompt_len=128, new_tokens=64,
        stagger_ms=40.0))
    rec["device_kind"] = _device_kind()
    return record_history(rec, HISTORY, better="max",
                          key_fields=("metric", "device_kind", "clients",
                                      "prompt_len", "new_tokens"))


def _demand_from_history(metric: str, fallback: float) -> float:
    """Chip-side demand for the ingest comparisons, from the best measured
    entry in the shared history — not a hand-recorded constant (the rule
    this ladder exists to enforce). Filtered to the CURRENT chip kind:
    values differ across chips, which is exactly why the guard keys on
    device_kind."""
    from serverless_learn_tpu.utils.benchlog import load_history

    try:
        kind = _device_kind()
    except Exception:
        kind = None
    vals = [h["value"] for h in load_history(HISTORY)
            if h.get("metric") == metric
            and (kind is None or h.get("device_kind") == kind)
            and isinstance(h.get("value"), (int, float))]
    return max(vals) if vals else fallback


def row_localsgd():
    """Local SGD communication-interval sweep on the REAL chip (round-3
    verdict #4): resnet18_cifar (BatchNorm — the stateful case round 3
    refused) under DiLoCo at inner_steps 1/8/32. On one chip the dp axis
    is 1 so the sweep prices the OUTER SYNC OVERHEAD itself (vmapped inner
    step + averaging cadence); on a pod the same knob trades ICI traffic
    for divergence. Value = samples/s at inner_steps=8 (the default)."""
    import jax

    from serverless_learn_tpu.config import (
        DataConfig, ExperimentConfig, MeshConfig, OptimizerConfig,
        TrainConfig)
    from serverless_learn_tpu.training.local_sgd import LocalSGDTrainer

    import numpy as np

    n_dev = len(jax.devices())
    cfg = ExperimentConfig(
        model="resnet18_cifar",
        mesh=MeshConfig(dp=n_dev),
        optimizer=OptimizerConfig(name="sgd", learning_rate=0.05),
        train=TrainConfig(batch_size=1024 * n_dev),
        data=DataConfig())
    sweep = {}
    for inner in (1, 8, 32):
        tr = LocalSGDTrainer(cfg, inner_steps=inner, outer="average")
        state = tr.init()
        batch = tr.shard_batch(tr.bundle.make_batch(
            np.random.default_rng(0), cfg.data, cfg.train.batch_size))
        for _ in range(3):
            state, losses = tr.inner_step(state, batch)
        state = tr.outer_sync(state)
        float(jax.device_get(losses.mean()))
        steps = 3 * inner if inner < 32 else 32
        t0 = time.perf_counter()
        for t in range(steps):
            state, losses = tr.inner_step(state, batch)
            if (t + 1) % inner == 0:
                state = tr.outer_sync(state)
        float(jax.device_get(losses.mean()))
        dt = time.perf_counter() - t0
        sweep[str(inner)] = round(cfg.train.batch_size * steps / dt, 1)
    rec = {
        "metric": "resnet18_local_sgd_samples_per_sec",
        "value": sweep["8"], "unit": "samples/sec (inner_steps=8)",
        "interval_sweep": sweep,
        "batch_per_replica": 1024,
        "device_kind": _device_kind(),
    }
    return record_history(rec, HISTORY, better="max", rel_threshold=0.10,
                          key_fields=("metric", "device_kind",
                                      "batch_per_replica"))


def row_data():
    """Host-side data plane rows (no chip involved)."""
    import socket
    import tempfile

    from benchmarks.data_bench import (
        bench_imagenet_device_augment, bench_imagenet_pipeline,
        bench_parallel_scaling, bench_raw, bench_real_pipeline)
    from serverless_learn_tpu.control.daemons import start_shard_server

    r18_demand = _demand_from_history(
        "resnet18_cifar_train_samples_per_sec_per_chip", 29793.0)
    r50_demand = _demand_from_history(
        "resnet50_imagenet_train_samples_per_sec_per_chip", 2440.0)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    out = []
    with tempfile.TemporaryDirectory() as root:
        proc = start_shard_server(port=port, root=root)
        addr = f"127.0.0.1:{port}"
        try:
            # Raw streaming swings hardest of all (149-286 MB/s observed
            # over one day on this shared-core box): median of 3 with the
            # spread recorded so benchlog widens its own threshold.
            raws = sorted((bench_raw(addr, 64, 4) for _ in range(3)),
                          key=lambda r: r["value"])
            raw = raws[1]
            raw["spread_rel"] = round(
                (raws[2]["value"] - raws[0]["value"]) / raw["value"], 4)
            for rec, key in (
                (raw, ("metric", "streams")),
                (bench_real_pipeline(addr, 4096, r18_demand), ("metric",)),
                (bench_imagenet_pipeline(addr, 2048, r50_demand),
                 ("metric",)),
                (bench_imagenet_device_augment(addr, 2048, r50_demand),
                 ("metric",)),
                (bench_parallel_scaling(addr, 2048, r50_demand),
                 ("metric",)),
            ):
                # 20%, not the default 5%: host-side rows share one core
                # with the server process and swing +-15% run to run
                # (measured across a day: raw 149-355 MB/s, ingest
                # 47-59k/s). The regressions this guard exists to catch
                # here (losing the fused transform, a chunking bug) are
                # 2x-class; chip-side rows keep the tighter bar.
                out.append(record_history(rec, HISTORY, better="max",
                                          rel_threshold=0.20,
                                          key_fields=key))
        finally:
            proc.terminate()
            proc.wait(timeout=5)
    return out


ROWS = {
    "r18": row_r18,
    "r18nf": row_r18nf,
    "r50": row_r50,
    "r50nf": row_r50nf,
    "r50da": row_r50da,
    "bert": row_bert,
    "llama1b": row_llama1b,
    "lm": row_lm,
    "flash": row_flash,
    "decode": row_decode,
    "decode8": row_decode8,
    "decodemoe": row_decodemoe,
    "spec": row_spec,
    "serve": row_serve,
    "llama8b": row_llama8b_width,
    "llama8b_real": row_llama8b_real,
    "localsgd": row_localsgd,
    "data": row_data,
}


# llama8b_real is opt-in, not in the default sweep: it resides ~8.5 GB of
# base weights plus activations on the chip — fine alone, but the shared
# dev chip may be holding other tenants' HBM, and a routine guard run
# should not OOM on their behalf. Run it explicitly:
#   python benchmarks/ladder.py --rows llama8b_real
DEFAULT_ROWS = [k for k in ROWS if k != "llama8b_real"]


def main():
    from serverless_learn_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", default=",".join(DEFAULT_ROWS),
                    help="comma-separated subset of: " + ",".join(ROWS))
    args = ap.parse_args()
    regressed = False
    for name in args.rows.split(","):
        name = name.strip()
        if not name:
            continue
        if name not in ROWS:
            raise SystemExit(f"unknown row {name!r}; rows: {','.join(ROWS)}")
        result = ROWS[name]()
        for rec in (result if isinstance(result, list) else [result]):
            print(json.dumps(rec), flush=True)
            regressed |= bool(rec.get("regression"))
    return 1 if regressed else 0


if __name__ == "__main__":
    sys.exit(main())
