"""Seeded inputs: token batches for training, request mixes for serving.

One general generator reads a workload file's parameters; a new mix is a
new data file. Every seed gets the SAME multiset of lengths and of
arrival gaps, in another order (they come from the file's ``pool_seed``),
so that two seeds differ in their tokens and their order and not in the
amount of work: a run-to-run spread then measures the system, not the
draw.
"""

from __future__ import annotations

import math

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent streams from one ``--seed`` (which may exceed 2**31)."""
    return np.random.default_rng([int(seed) % (2 ** 63), int(stream)])


def train_batches(seed: int, vocab: int, batch: int, seq_len: int):
    """Endless {"tokens": int32 [batch, seq_len]}; every row differs."""
    rng = rng_for(seed, 1)
    while True:
        yield {"tokens": rng.integers(0, vocab, (batch, seq_len),
                                      dtype=np.int32)}


def _lognormal_lengths(rng, n: int, spec: dict) -> np.ndarray:
    raw = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def request_mix(params: dict, seed: int, vocab: int) -> list:
    """``params["pool"]`` requests: dicts of ``prompt`` (token ids),
    ``max_new_tokens`` and ``shared_prefix`` (index or None).

    Lengths and which requests carry a shared prefix come from
    ``pool_seed`` (the same for every ``--seed``) and are then permuted by
    ``--seed``; token contents come from ``--seed`` and never repeat. A shared prefix
    replaces the head of the prompt, so the prompt keeps its drawn length.
    """
    n = int(params["pool"])
    fixed = rng_for(int(params["pool_seed"]), 2)
    # ``length_cycle``: only so many (prompt, output) length pairs are
    # drawn, and the mix repeats them in a fixed cycle and order, so that
    # any stretch of it a window covers holds the same work.
    drawn = int(params.get("length_cycle", n))
    p_len = _lognormal_lengths(fixed, drawn, params["prompt_tokens"])
    o_len = _lognormal_lengths(fixed, drawn, params["output_tokens"])
    sp = params.get("shared_prefix")
    which = np.full((drawn,), -1, np.int64)
    if sp:
        which = np.where(fixed.random(drawn) < sp["share"],
                         fixed.integers(0, sp["count"], drawn), -1)
    rng = rng_for(seed, 3)
    # A cycle keeps its order for every seed: the engine packs prompts
    # into its prefill budget in arrival order, so another order of the
    # same lengths is other work (PERF.md: 54-62 s for the same requests).
    order = np.resize(np.arange(drawn) if "length_cycle" in params
                      else rng.permutation(drawn), n)
    prefixes = ([rng.integers(0, vocab, sp["tokens"], dtype=np.int64)
                 for _ in range(sp["count"])] if sp else [])
    out = []
    for i in order:
        prompt = rng.integers(0, vocab, int(p_len[i]), dtype=np.int64)
        shared = None
        if which[i] >= 0 and len(prompt) > sp["tokens"]:
            prompt[:sp["tokens"]] = prefixes[int(which[i])]
            shared = int(which[i])
        out.append({"prompt": [int(t) for t in prompt],
                    "max_new_tokens": int(o_len[i]),
                    "shared_prefix": shared})
    return out


def poisson_schedule(params: dict, seed: int, seconds: float) -> tuple:
    """Due times of an open loop at ``rate_per_s``, in seconds from the
    start of the window: (warm-in due times, all negative; window due
    times in [0, seconds)). The window's gaps are drawn from ``pool_seed``,
    scaled to span the window exactly and permuted by ``--seed``: every
    seed offers the same number of requests and the same gaps."""
    rate = float(params["rate_per_s"])
    fixed = rng_for(int(params["pool_seed"]), 4)
    n = max(1, int(round(rate * seconds)))
    gaps = fixed.exponential(1.0 / rate, n)
    gaps *= seconds / gaps.sum()
    gaps = rng_for(seed, 5).permutation(gaps)
    window = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    warm_s = float(params.get("warm_in_s", 0.0))
    m = int(round(rate * warm_s))
    warm = (-np.sort(fixed.uniform(0.0, warm_s, m))[::-1] if m else
            np.zeros((0,)))
    return [float(t) for t in warm], [float(t) for t in window]


def lateness_summary(lateness_s: list) -> dict:
    """How late the generator ran: actual send minus due time."""
    if not lateness_s:
        return {"n": 0, "p50_ms": None, "max_ms": None}
    a = np.asarray(lateness_s) * 1e3
    return {"n": int(a.size), "p50_ms": float(np.median(a)),
            "max_ms": float(a.max())}


def percentile(values: list, q: float) -> float:
    """Linear-interpolated percentile; the tail of ALL the values."""
    return float(np.percentile(np.asarray(values, np.float64), q))
