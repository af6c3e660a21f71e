"""Tile sweep of the expert layers' grouped-product kernel on one chip.

    python benchmarks/moe_gmm_sweep.py [--out chiprun_out/moe_gmm_sweep.jsonl]

Times ``ops/pallas/moe_gmm.py`` at the shapes of one of Trinity-Mini's
expert layers (128 experts of hidden 2,048 and width 1,024, top 8): the
gate/up product (k 2,048, n 1,024) and the down product (k 1,024,
n 2,048), at the rows of a decode step of 32 slots (256) and of prefill
programs of 4 to 32 rows of 32 tokens (1,024 to 8,192). Every row is
routed to 8 distinct experts drawn uniformly, as the seeded benchmark
cell's routing spreads them. Each tiling (tm, tk, tn) is one compile and
20 calls between two waits on the host's clock; ``jax.lax.ragged_dot``
and ``tiling``'s own choice are timed beside them, and one whole expert
layer (``ops/moe.py`` ``dropless_experts``: sort, three products,
combine) on either path. Prints one JSON line per timing, and the least
time of each product (the touched experts' weights and the rows in and
out at the chip's bandwidth, or its FLOPs at peak). It needs a TPU.
"""

from __future__ import annotations

import argparse
import functools
import itertools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

E, TOP_K, D, F = 128, 8, 2048, 1024
PRODUCTS = {"gate_up": (D, F), "down": (F, D)}
DECODE_M, PREFILL_M = 256, (1024, 2048, 4096, 8192)
TM = (16, 32, 64, 128, 256)
TKN = (256, 512, 1024, 2048)
PEAK_FLOPS, PEAK_BYTES = 197e12, 819e9       # TPU v5e
CALLS = 20


def _sizes(rows: int, seed: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    idx = np.stack([rng.choice(E, TOP_K, replace=False)
                    for _ in range(rows // TOP_K)])
    return np.bincount(idx.reshape(-1), minlength=E).astype(np.int32)


def _time(fn, *args) -> float:
    import jax

    for _ in range(3):
        out = fn(*args)
    jax.block_until_ready(out)
    t = time.perf_counter()
    for _ in range(CALLS):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t) / CALLS


def _least_ms(m: int, k: int, n: int, touched: int) -> tuple:
    nbytes = 2 * (touched * k * n + m * k + m * n)
    t_f, t_b = 2 * m * k * n / PEAK_FLOPS, nbytes / PEAK_BYTES
    return 1e3 * max(t_f, t_b), "compute" if t_f >= t_b else "memory"


def _grid(m: int, k: int, n: int):
    tks = [t for t in TKN if t <= k]
    tns = [t for t in TKN if t <= n]
    if m not in (DECODE_M, PREFILL_M[-1]):   # the others: k whole
        tks, tns = [k], [t for t in tns if t >= 512]
    return [(tm, tk, tn) for tm, tk, tn in itertools.product(TM, tks, tns)
            if tm <= m]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default="chiprun_out/moe_gmm_sweep.jsonl")
    ap.add_argument("--seed", type=int, default=41)
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp

    from serverless_learn_tpu.ops import moe
    from serverless_learn_tpu.ops.pallas import moe_gmm

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("the sweep times a TPU; JAX found "
                         f"{jax.devices()[0].device_kind}")
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    out = open(args.out, "w")

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    keys = jax.random.split(jax.random.PRNGKey(args.seed), 4)
    weights = {name: jax.random.normal(keys[i], (E, k, n), jnp.bfloat16)
               / k ** 0.5 for i, (name, (k, n)) in enumerate(PRODUCTS.items())}
    ragged = jax.jit(jax.lax.ragged_dot)
    for m in (DECODE_M,) + PREFILL_M:
        sizes = jnp.asarray(_sizes(m, args.seed + m))
        touched = int((sizes > 0).sum())
        for name, (k, n) in PRODUCTS.items():
            lhs = jax.random.normal(keys[3], (m, k), jnp.bfloat16)
            rhs = weights[name]
            least, bound = _least_ms(m, k, n, touched)
            base = dict(product=name, m=m, k=k, n=n, experts=touched,
                        least_ms=least, bound=bound)
            want = ragged(lhs, rhs, sizes)
            emit(dict(base, path="ragged_dot",
                      ms=_time(ragged, lhs, rhs, sizes)))
            chosen = moe_gmm.tiling(m, k, n)
            for tiles in _grid(m, k, n):
                # One compile per tiling: ``gmm``'s jitted core takes the
                # tiles as a static argument.
                fn = functools.partial(moe_gmm.gmm, tiles=tiles)
                try:
                    ms = _time(fn, lhs, rhs, sizes)
                except Exception as e:    # a tiling the compiler refuses
                    emit(dict(base, path="moe_gmm", tiles=tiles,
                              error=str(e).splitlines()[0][:200]))
                    continue
                rec = dict(base, path="moe_gmm", tiles=tiles, ms=ms,
                           chosen=tiles == chosen)
                if tiles == chosen:
                    got = fn(lhs, rhs, sizes).astype(jnp.float32)
                    rec["max_rel_gap"] = float(
                        jnp.abs(got - want.astype(jnp.float32)).max()
                        / jnp.abs(want.astype(jnp.float32)).max())
                emit(rec)
    # One whole expert layer, the sort and the combine included; the
    # fallback is traced with the kernel refused.
    layer = {path: jax.jit(functools.partial(moe.dropless_experts))
             for path in ("moe_gmm", "ragged_dot")}
    real = moe_gmm.untileable_reason
    for tokens in (DECODE_M // TOP_K, 1024):
        x = jax.random.normal(keys[3], (tokens, D), jnp.bfloat16)
        logits = jax.random.normal(keys[2], (tokens, E))
        _, gate_w, idx = moe.route(logits, TOP_K, "sigmoid")
        w = (weights["gate_up"], weights["gate_up"], weights["down"])
        for path, fn in layer.items():
            if path == "ragged_dot":
                moe_gmm.untileable_reason = lambda *a: "timed on purpose"
            try:
                ms = _time(fn, x, gate_w, idx, *w)
            finally:
                moe_gmm.untileable_reason = real
            emit(dict(product="layer", tokens=tokens, path=path, ms=ms))
    out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
