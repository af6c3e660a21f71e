"""Inference benchmark: KV-cache decode throughput.

    python benchmarks/gen_bench.py [--model llama_tiny] [--batch 8]
        [--prompt 128] [--new 128]

Prints one JSON line: decode tokens/sec (total and per sequence) plus
prefill+decode wall time. Measures the jitted prefill+scan loop in
``inference/generate.py``. ``run()`` is the single shared measurement the
ladder's regression-guarded decode row also uses — one methodology, no
drifting twins (the r2 README's 6.0k one-off came from exactly such a
divergence).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run(model: str = "llama_tiny", batch: int = 8, prompt_len: int = 128,
        new_tokens: int = 128, iters: int = 5, quant=None,
        model_kw=None, quant_direct: bool = False) -> dict:
    """One decode measurement, amortized over ``iters`` calls.

    ``quant="int8"``: params quantize post-init and the module switches to
    the weight-only-int8 config — the decode is weight-HBM-bound, so the
    expected win is ~the byte ratio. ``quant_direct``: init random params
    straight in the int8 layout — the 8B path, where materializing the
    bf16 tree first (16 GB) cannot share a 16 GB chip with its copy."""
    import jax
    import jax.numpy as jnp

    from serverless_learn_tpu.inference.generate import generate
    from serverless_learn_tpu.models.registry import get_model

    bundle = get_model(model, **(model_kw or {}))
    module = bundle.module
    if quant_direct and not quant:
        raise ValueError("quant_direct=True requires quant: the flag picks "
                         "the int8-layout init path, not a measurement mode")
    if quant and quant_direct:
        import dataclasses

        from serverless_learn_tpu.inference.quantize import (
            random_quantized_params)

        module = type(module)(dataclasses.replace(module.cfg, quant=quant))
        params = random_quantized_params(module)
    elif quant:
        import dataclasses

        from serverless_learn_tpu.inference.quantize import (
            quantize_params_int8)

        params = jax.jit(lambda: quantize_params_int8(module.init(
            jax.random.PRNGKey(0),
            jnp.zeros((1, 8), jnp.int32))["params"]))()
        module = type(module)(dataclasses.replace(module.cfg, quant=quant))
    else:
        params = jax.jit(lambda: module.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])()
    prompt = jax.random.randint(
        jax.random.PRNGKey(1), (batch, prompt_len), 0,
        module.cfg.vocab_size)

    # Warm up with the SAME signature as the timed loop (rng passed): a
    # None-rng warmup traces a different pytree and the first timed call
    # would pay a recompile.
    out = generate(module, params, prompt, new_tokens,
                   rng=jax.random.PRNGKey(0))
    float(jax.device_get(out[0, -1]))  # scalar sync
    t0 = time.perf_counter()
    for i in range(iters):
        out = generate(module, params, prompt, new_tokens,
                       rng=jax.random.PRNGKey(i))
    float(jax.device_get(out[0, -1]))
    dt = (time.perf_counter() - t0) / iters
    suffix = f"_{quant}" if quant else ""
    return {
        "metric": f"{model}_decode{suffix}_tokens_per_sec",
        "batch": batch, "prompt_len": prompt_len, "new_tokens": new_tokens,
        "value": round(batch * new_tokens / dt, 1), "unit": "tokens/sec",
        "per_seq_tokens_per_sec": round(new_tokens / dt, 1),
        "wall_ms": round(dt * 1e3, 1),
    }


def run_concurrent(model: str = "llama_tiny", clients: int = 4,
                   prompt_len: int = 128, new_tokens: int = 64,
                   reqs: int = 3, stagger_ms: float = 0.0) -> dict:
    """Aggregate multi-client serving throughput: ``clients`` threads each
    fire ``reqs`` sequential requests at the serving engine, once batched
    and once serialized (max_slots=1). The ratio is the batching win.
    Decode is HBM-bound on TPU, so batch-4 decode steps cost ~ the same
    wall time as batch-1 — near-linear aggregate scaling is the expected
    physics.

    ``stagger_ms``: per-client start offset — arrivals that land between
    chunk boundaries, which slot-level admission takes at the next one.
    Per-request latencies are recorded; p50/p95 ride in the row."""
    import threading

    import jax
    import jax.numpy as jnp

    from serverless_learn_tpu.inference.continuous import (
        ContinuousBatchingEngine)
    from serverless_learn_tpu.models.registry import get_model
    from serverless_learn_tpu.telemetry import MetricsRegistry

    bundle = get_model(model)
    module = bundle.module
    params = jax.jit(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])()
    rng = jax.random.PRNGKey(1)
    prompts = [[int(t) for t in row] for row in jax.device_get(
        jax.random.randint(rng, (clients, prompt_len), 0,
                           module.cfg.vocab_size))]

    def measure(width: int):
        # Private registry per engine: the bench attaches this arm's
        # queue-wait/TTFT percentiles to its row without cross-arm (or
        # cross-process-default) contamination.
        eng = ContinuousBatchingEngine(module, params, max_slots=width,
                                       chunk_size=32,
                                       registry=MetricsRegistry())
        try:
            def round_trip():
                barrier = threading.Barrier(clients)
                errors = []
                lat: list = []
                lat_lock = threading.Lock()

                def client(i):
                    barrier.wait()
                    if stagger_ms:
                        time.sleep(stagger_ms * i / 1e3)
                    for _ in range(reqs):
                        t0 = time.perf_counter()
                        r = eng.submit(prompts[i], new_tokens,
                                       temperature=0.0, top_k=0,
                                       eos_id=None, seed=0)
                        if "error" in r:
                            errors.append(r)
                            return
                        with lat_lock:
                            lat.append(time.perf_counter() - t0)

                threads = [threading.Thread(target=client, args=(i,))
                           for i in range(clients)]
                t0 = time.perf_counter()
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                dt = time.perf_counter() - t0
                if errors:
                    # Fail loudly AFTER joining: a dead client thread must
                    # not let the bench report tokens never generated.
                    raise RuntimeError(f"serving errors: {errors[:3]}")
                return dt, sorted(lat)

            # Compile every bucket the timed round could form, without
            # traffic (how arrivals batch is timing-dependent, and an
            # uncompiled bucket inside the timed window would bill a
            # multi-second XLA compile as serving time).
            eng.warm_shapes([(prompt_len, new_tokens)],
                            batch_sizes=range(1, min(clients, width) + 1))
            round_trip()  # warm the queue path itself
            dt, lat = round_trip()
            return clients * reqs * new_tokens / dt, lat, eng.registry
        finally:
            eng.stop()

    serialized, _, _ = measure(1)
    batched, lat, reg = measure(clients * 2)
    rec = {
        "metric": f"{model}_serve_continuous_tokens_per_sec",
        "engine": "continuous", "clients": clients, "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "value": round(batched, 1), "unit": "tokens/sec aggregate",
        "serialized_tokens_per_sec": round(serialized, 1),
        "batching_speedup": round(batched / serialized, 2),
        "p50_latency_ms": round(lat[len(lat) // 2] * 1e3, 1),
        "p95_latency_ms": round(lat[min(len(lat) - 1,
                                        int(len(lat) * 0.95))] * 1e3, 1),
    }
    # Telemetry-substrate percentiles (engine-side spans, warm traffic
    # included): queue wait and TTFT ride the row so BENCH_*.json rounds
    # can track serving latency shape, not just aggregate throughput.
    for hname, key in (("slt_request_queue_wait_seconds", "queue_wait"),
                       ("slt_request_ttft_seconds", "ttft")):
        h = reg.histogram(hname, engine="continuous")
        for q, sfx in ((0.5, "p50"), (0.95, "p95")) if h.count else ():
            p = h.percentile(q)
            if p is not None:
                rec[f"{key}_{sfx}_ms"] = round(p * 1e3, 2)
    if stagger_ms:
        rec["stagger_ms"] = stagger_ms
    return rec


def run_speculative(model: str = "llama_1b", draft_layers: int = 4,
                    K: int = 4, batch: int = 8, prompt_len: int = 128,
                    new_tokens: int = 64, iters: int = 3,
                    model_kw=None) -> dict:
    """Speculative decode vs plain greedy decode, arms INTERLEAVED
    (the decode8 lesson: shared-chip contention lands on whole arms).

    The draft is the target's own first ``draft_layers`` layers plus its
    embedder/norm/head — zero extra weights, the self-speculative
    construction. Acceptance is measured and recorded: it is a property
    of the WEIGHTS (random-init pairs agree near chance; trained pairs
    at the literature's 60-90%), so the row reports tokens/s AND
    acceptance side by side, plus a self-draft arm (draft == target:
    acceptance 1.0 by construction) that prices the mechanism's
    overhead ceiling independent of weights."""
    import jax
    import jax.numpy as jnp

    from serverless_learn_tpu.inference.generate import generate
    from serverless_learn_tpu.inference.speculative import (
        prefix_draft, speculative_generate)
    from serverless_learn_tpu.models.registry import get_model

    bundle = get_model(model, **(model_kw or {}))
    module = bundle.module
    tparams = jax.jit(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])()
    draft, dparams = prefix_draft(module, tparams, draft_layers)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (batch, prompt_len),
                                0, module.cfg.vocab_size)

    def plain_once():
        out = generate(module, tparams, prompt, new_tokens)
        float(jax.device_get(out[0, -1]))

    def spec_once(dm, dp):
        out, stats = speculative_generate(module, tparams, dm, dp,
                                          prompt, new_tokens, K=K)
        float(jax.device_get(out[0, -1]))
        return stats

    # Warm all three compiled paths.
    plain_once()
    stats_prefix = spec_once(draft, dparams)
    stats_self = spec_once(module, tparams)

    t_plain = t_prefix = t_self = 0.0
    for _ in range(iters):
        t0 = time.perf_counter()
        plain_once()
        t_plain += time.perf_counter() - t0
        t0 = time.perf_counter()
        stats_prefix = spec_once(draft, dparams)
        t_prefix += time.perf_counter() - t0
        t0 = time.perf_counter()
        stats_self = spec_once(module, tparams)
        t_self += time.perf_counter() - t0
    tok = batch * new_tokens * iters
    return {
        "metric": f"{model}_speculative_decode_tokens_per_sec",
        "batch": batch, "prompt_len": prompt_len, "new_tokens": new_tokens,
        "K": K, "draft_layers": draft_layers,
        "value": round(tok / t_prefix, 1), "unit": "tokens/sec",
        "plain_tokens_per_sec": round(tok / t_plain, 1),
        "spec_over_plain": round(t_plain / t_prefix, 2),
        "acceptance": round(stats_prefix["acceptance"], 3),
        "selfdraft_tokens_per_sec": round(tok / t_self, 1),
        "selfdraft_acceptance": round(stats_self["acceptance"], 3),
        "weights_note": "random-init params: acceptance is weight-"
                        "dependent; trained pairs sit at 0.6-0.9",
    }


def main():
    from serverless_learn_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="llama_tiny")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt", type=int, default=128)
    ap.add_argument("--new", type=int, default=128)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--concurrent", action="store_true",
                    help="also run the multi-client batched-serving row")
    args = ap.parse_args()
    print(json.dumps(run(args.model, args.batch, args.prompt, args.new,
                         args.iters)))
    if args.concurrent:
        print(json.dumps(run_concurrent(args.model)))


if __name__ == "__main__":
    main()
