"""Training-quality observability: tensor stats, fingerprints, provenance.

Rounds 1-16 built a deep observability stack for *performance* — metrics,
causal traces, goodput ledgers, xray hardware attribution — but the repo
was blind to training *quality*: a NaN, a gradient explosion, or silent
cross-replica numeric drift surfaced only as a bad loss number, if at
all. This module is the quality layer's substrate; everything in it is
pure math over pytrees so every consumer (the jitted step, the health
engine, the CLI, tests) shares one implementation:

* **In-graph summary stats** (:func:`tree_stats`, :func:`step_summary`,
  :func:`global_norm`) — per-subtree grad/param/update L2 norms, RMS,
  absmax, update-to-param ratio and non-finite counts as cheap ``jnp``
  reductions. Computed INSIDE the jitted step (a handful of scalars per
  subtree, fused by XLA into the backward it already runs); fetched by
  ``training/audit.py`` only at the configured cadence, so numerics adds
  zero per-step host syncs.
* **Fingerprints** (:func:`fingerprint`, :func:`diff_fingerprints`,
  :func:`diff_fingerprint_logs`) — per-subtree reduced digests (L2, sum,
  absmax + ``chunks`` positional partial sums) cheap enough to record
  every step. Two recorded runs (or two live trees) bisect to the FIRST
  step and the FIRST parameter subtree that diverged — the acceptance
  harness ROADMAP items 1-2 (ZeRO update sharding, quantized DCN
  exchange) need for their "same loss curve / parity" claims.
* **Non-finite provenance** (:func:`first_nonfinite`,
  :func:`nonfinite_provenance`) — when the in-graph flag trips, a
  checked re-run (per-layer ``capture_intermediates`` sweep over a host
  shadow, or ``jax_debug_nans``) names the first layer/op that produced
  the NaN/Inf instead of letting it surface 40 layers later as a bad
  loss.
* **Parity harness** (:class:`ParityHarness`, :func:`compare_trees`) —
  runs a reference and a candidate step function side by side on the
  same batches and reports max-ulp / rel-err per subtree per step; the
  deterministic twin of fingerprint diffing for changes you can rerun.
* **Loss-health detectors** (:class:`LossHealth`) — EWMA loss-spike,
  plateau and grad-explosion detection over the per-step record ring
  (:func:`note_step`). The health engine ticks these into typed alerts
  (``numerics.loss_spike`` / ``numerics.loss_plateau`` /
  ``numerics.grad_explosion`` / ``numerics.nonfinite``).

Cross-replica: :func:`replica_divergence` (promoted here from
``training/local_sgd.py`` so gossip/DiLoCo and the fingerprint path share
one implementation) and :func:`fingerprint` over stacked ``[R, ...]``
trees give per-replica digests whose spread IS the divergence signal.
"""

from __future__ import annotations

import json
import threading
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from serverless_learn_tpu.telemetry.health import EwmaMad

DEFAULT_CHUNKS = 4
DEFAULT_DEPTH = 1


# -- subtree grouping ---------------------------------------------------------


def _subtree_name(path, depth: int) -> str:
    """Dotted name of the first ``depth`` path entries ("dense_0",
    "block_2.attn"). Leaves above the depth fold into their parent."""
    parts = []
    for entry in path[:depth]:
        if hasattr(entry, "key"):
            parts.append(str(entry.key))
        elif hasattr(entry, "idx"):
            parts.append(str(entry.idx))
        elif hasattr(entry, "name"):
            parts.append(str(entry.name))
        else:
            parts.append(str(entry))
    return ".".join(parts) if parts else "root"


def subtrees(tree, depth: int = DEFAULT_DEPTH) -> Dict[str, List[Any]]:
    """Group a pytree's leaves by their ``depth``-level subtree name,
    in deterministic (flatten) order."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out: Dict[str, List[Any]] = {}
    for path, leaf in flat:
        out.setdefault(_subtree_name(path, depth), []).append(leaf)
    return out


# -- in-graph stats -----------------------------------------------------------


def global_norm(tree) -> jnp.ndarray:
    """sqrt(sum of squares) over all float leaves, in f32 — the single
    grad-norm implementation (train_step's metric and the numerics
    summary both call this)."""
    leaves = [l for l in jax.tree_util.tree_leaves(tree)
              if jnp.issubdtype(jnp.asarray(l).dtype, jnp.floating)
              or jnp.issubdtype(jnp.asarray(l).dtype, jnp.complexfloating)]
    if not leaves:
        return jnp.float32(0.0)
    return jnp.sqrt(sum(jnp.sum(jnp.square(jnp.asarray(l, jnp.float32)))
                        for l in leaves))


def _sub_stats(leaves: List[Any]) -> Dict[str, jnp.ndarray]:
    """L2 / RMS / absmax / non-finite count over one subtree's leaves
    (f32 accumulation; jit-safe)."""
    sq = jnp.float32(0.0)
    amax = jnp.float32(0.0)
    bad = jnp.int32(0)
    n = 0
    for l in leaves:
        x = jnp.asarray(l, jnp.float32)
        finite = jnp.isfinite(x)
        bad = bad + jnp.sum(~finite).astype(jnp.int32)
        # Non-finite values must not poison the norms the detectors
        # baseline on — the flag carries the incident, the norms stay
        # comparable across steps.
        x = jnp.where(finite, x, 0.0)
        sq = sq + jnp.sum(jnp.square(x))
        amax = jnp.maximum(amax, jnp.max(jnp.abs(x)) if x.size else 0.0)
        n += int(np.prod(x.shape)) if x.shape else 1
    l2 = jnp.sqrt(sq)
    return {"l2": l2, "rms": l2 / np.sqrt(max(n, 1)),
            "absmax": amax, "nonfinite": bad}


def tree_stats(tree, depth: int = DEFAULT_DEPTH
               ) -> Dict[str, Dict[str, jnp.ndarray]]:
    """Per-subtree {l2, rms, absmax, nonfinite} (jit-safe)."""
    return {name: _sub_stats(leaves)
            for name, leaves in subtrees(tree, depth).items()}


def fingerprint(tree, depth: int = DEFAULT_DEPTH,
                chunks: int = DEFAULT_CHUNKS
                ) -> Dict[str, Dict[str, jnp.ndarray]]:
    """Per-subtree reduced digest: {l2, sum, absmax, c0..c(chunks-1)}.

    The positional chunk sums split each subtree's concatenated
    elements into ``chunks`` contiguous ranges — a divergence confined
    to one weight block moves one chunk sum, so two digests disagreeing
    localizes *where* in the subtree, not just *that*. Cheap enough
    (a handful of f32 reductions) to compute inside the jitted step
    every step and record every cadence.

    Reduced leaf by leaf (``_leaf_digest``): no float32 copy of a whole
    subtree is made, which for one expert layer of a served MoE model
    would be gigabytes beside its weights."""
    k = max(chunks, 1)
    out: Dict[str, Dict[str, jnp.ndarray]] = {}
    for name, leaves in subtrees(tree, depth).items():
        sizes = [int(np.prod(np.shape(l))) for l in leaves]
        n = sum(sizes)
        width = (n + (-n) % k) // k     # elements a chunk covers
        sq, total, amax = jnp.float32(0), jnp.float32(0), jnp.float32(0)
        parts = jnp.zeros((k,), jnp.float32)
        start = 0
        for leaf, m in zip(leaves, sizes):
            # The chunks' ranges in this leaf's own element indices.
            bounds = np.clip(np.arange(k + 1) * width - start, 0, m)
            s2, s1, mx, p = _leaf_digest(leaf, bounds.astype(np.int32))
            sq, total, amax = sq + s2, total + s1, jnp.maximum(amax, mx)
            parts = parts + p
            start += m
        digest = {"l2": jnp.sqrt(sq), "sum": total, "absmax": amax}
        for i in range(k):
            digest[f"c{i}"] = parts[i]
        out[name] = digest
    return out


@jax.jit
def _leaf_digest(leaf, bounds):
    """One leaf's share of a subtree's digest: its sum of squares, sum,
    absmax and, per chunk, the sum over its elements ``bounds[c]`` to
    ``bounds[c + 1]``; non-finite elements count as 0. One fused pass,
    compiled once a leaf shape."""
    v = jnp.ravel(leaf).astype(jnp.float32)
    v = jnp.where(jnp.isfinite(v), v, 0.0)
    i = jnp.arange(v.shape[0])
    parts = jnp.stack([
        jnp.sum(jnp.where((i >= bounds[c]) & (i < bounds[c + 1]), v, 0.0))
        for c in range(bounds.shape[0] - 1)])
    return (jnp.sum(jnp.square(v)), jnp.sum(v),
            jnp.max(jnp.abs(v), initial=0.0), parts)


def weight_version(tree, depth: int = DEFAULT_DEPTH,
                   chunks: int = DEFAULT_CHUNKS) -> Optional[str]:
    """Compact weight-identity string for the serving plane (round 23):
    the :func:`fingerprint` digest, floats rendered at 6 significant
    digits (stable across re-loads of the same checkpoint, insensitive
    to last-ulp noise), hashed to 12 hex chars. This is the version tag
    replicas stamp into registration, pings, waterfalls and route
    decisions — same weights => same tag, everywhere."""
    import hashlib

    if tree is None:
        return None
    fp = fingerprint(tree, depth=depth, chunks=chunks)
    blob = json.dumps(
        {name: {k: f"{float(v):.6g}" for k, v in sorted(digest.items())}
         for name, digest in sorted(fp.items())}, sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()[:12]


def step_summary(params, grads, updates, loss=None,
                 depth: int = DEFAULT_DEPTH,
                 chunks: int = DEFAULT_CHUNKS,
                 with_fingerprint: bool = True) -> Dict[str, jnp.ndarray]:
    """The in-graph numerics output of one optimizer step: a FLAT dict of
    f32/i32 scalars (flat so the step's replicated out_sharding covers it
    and a host fetch is one small transfer).

    Keys: ``grad/<sub>/{l2,rms,absmax}``, ``param/<sub>/{l2,rms,absmax}``,
    ``update/<sub>/l2``, ``ratio/<sub>`` (update L2 / param L2),
    ``fp/<sub>/{l2,sum,absmax,c*}`` and the global rollups
    ``grad_norm``, ``param_norm``, ``update_norm``, ``update_ratio``,
    ``nonfinite_total`` (grads + params + loss)."""
    out: Dict[str, jnp.ndarray] = {}
    bad = jnp.int32(0)
    p_stats = tree_stats(params, depth)
    g_stats = tree_stats(grads, depth)
    u_stats = tree_stats(updates, depth)
    for name, st in g_stats.items():
        for k in ("l2", "rms", "absmax"):
            out[f"grad/{name}/{k}"] = st[k]
        # Per-subtree non-finite counts ride along: the incident record
        # can then name the bad subtree straight from the in-graph
        # stats, before (and independent of) the provenance sweep.
        out[f"grad/{name}/nonfinite"] = st["nonfinite"]
        bad = bad + st["nonfinite"]
    for name, st in p_stats.items():
        for k in ("l2", "rms", "absmax"):
            out[f"param/{name}/{k}"] = st[k]
        out[f"param/{name}/nonfinite"] = st["nonfinite"]
        bad = bad + st["nonfinite"]
    for name, st in u_stats.items():
        out[f"update/{name}/l2"] = st["l2"]
        p_l2 = p_stats.get(name, {}).get("l2")
        if p_l2 is not None:
            out[f"ratio/{name}"] = st["l2"] / jnp.maximum(p_l2, 1e-12)
    if with_fingerprint:
        for name, digest in fingerprint(params, depth, chunks).items():
            for k, v in digest.items():
                out[f"fp/{name}/{k}"] = v
    out["grad_norm"] = global_norm(grads)
    out["param_norm"] = global_norm(params)
    out["update_norm"] = global_norm(updates)
    out["update_ratio"] = (out["update_norm"]
                           / jnp.maximum(out["param_norm"], 1e-12))
    if loss is not None:
        bad = bad + jnp.sum(~jnp.isfinite(
            jnp.asarray(loss, jnp.float32))).astype(jnp.int32)
    out["nonfinite_total"] = bad
    return out


@jax.jit
def replica_divergence(params) -> jax.Array:
    """Max over leaves of max |p_r - mean_r p| — 0 iff replicas agree.

    Promoted here (round 17) from ``training/local_sgd.py`` so the
    gossip/DiLoCo gauge and the fingerprint path share one
    implementation. Jitted into ONE program: leaves are dp-sharded
    [R, ...], so each mean is a cross-device reduction — dispatched
    eagerly op-by-op, a large stateful model (ResNet batch_stats)
    serializes dozens of collectives on the CPU test backend and trips
    XLA:CPU's hardcoded 40 s collective-rendezvous abort."""
    leaves = jax.tree_util.tree_leaves(params)
    divs = [jnp.max(jnp.abs(l - l.mean(0, keepdims=True))) for l in leaves]
    return jnp.max(jnp.stack([jnp.asarray(d, jnp.float32) for d in divs]))


# -- fingerprint diffing / bisection ------------------------------------------


def diff_fingerprints(fa: Dict[str, dict], fb: Dict[str, dict],
                      rtol: float = 1e-5, atol: float = 1e-6
                      ) -> Optional[dict]:
    """Compare two per-subtree digests; None when they agree within
    tolerance, else the worst-offending {subtree, field, a, b, rel_err}."""
    worst = None
    for name in sorted(set(fa) | set(fb)):
        da, db = fa.get(name), fb.get(name)
        if da is None or db is None:
            return {"subtree": name, "field": "(missing)",
                    "a": None if da is None else "present",
                    "b": None if db is None else "present",
                    "rel_err": float("inf")}
        for field in sorted(set(da) | set(db)):
            va, vb = float(da.get(field, 0.0)), float(db.get(field, 0.0))
            denom = max(abs(va), abs(vb), 1e-30)
            err = abs(va - vb)
            if err <= atol + rtol * denom:
                continue
            rel = err / denom
            if worst is None or rel > worst["rel_err"]:
                worst = {"subtree": name, "field": field,
                         "a": va, "b": vb, "rel_err": rel}
    return worst


def _fp_records(records: Sequence[dict]) -> Dict[int, dict]:
    """step -> fingerprint dict from mixed JSONL records (accepts both
    ``numerics_fingerprint`` records and ``numerics_stats`` records that
    embed an ``fp`` section)."""
    out: Dict[int, dict] = {}
    for rec in records:
        if rec.get("event") not in ("numerics_fingerprint",
                                    "numerics_stats"):
            continue
        fp = rec.get("fp")
        step = rec.get("step")
        if isinstance(fp, dict) and isinstance(step, int):
            out[step] = fp  # last record per step wins (re-runs append)
    return out


def diff_fingerprint_logs(records_a: Sequence[dict],
                          records_b: Sequence[dict],
                          rtol: float = 1e-5, atol: float = 1e-6) -> dict:
    """Bisect two recorded fingerprint trails to the first step and the
    first parameter subtree that diverged.

    Returns {"diverged": bool, "first_divergent_step", "subtree",
    "field", "a", "b", "rel_err", "steps_compared",
    "last_agreeing_step"}. Steps present in only one trail are skipped
    (different cadences still compare on the common grid)."""
    fa, fb = _fp_records(records_a), _fp_records(records_b)
    common = sorted(set(fa) & set(fb))
    last_ok = None
    for step in common:
        worst = diff_fingerprints(fa[step], fb[step], rtol=rtol, atol=atol)
        if worst is not None:
            return {"diverged": True, "first_divergent_step": step,
                    "last_agreeing_step": last_ok,
                    "steps_compared": len(common), **worst}
        last_ok = step
    return {"diverged": False, "steps_compared": len(common),
            "last_agreeing_step": common[-1] if common else None,
            "only_a": len(set(fa) - set(fb)),
            "only_b": len(set(fb) - set(fa))}


def load_records(path: str) -> List[dict]:
    """Read a JSONL trail (tolerates a torn final line, flight dumps)."""
    out: List[dict] = []
    with open(path) as f:
        head = f.read(1)
        f.seek(0)
        if head == "{":
            try:
                obj = json.load(f)
                if isinstance(obj, dict):
                    if obj.get("event") == "flight_dump":
                        return [r for r in obj.get("events", [])
                                if isinstance(r, dict)]
                    return [obj]
            except json.JSONDecodeError:
                f.seek(0)
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line))
            except json.JSONDecodeError:
                continue
    return out


# -- non-finite provenance ----------------------------------------------------


def first_nonfinite(tree, depth: int = DEFAULT_DEPTH) -> Optional[dict]:
    """First (flatten-order) leaf holding a NaN/Inf, on HOST values:
    {"path", "subtree", "nan", "inf", "shape"}; None when clean."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in flat:
        x = np.asarray(leaf)
        if not np.issubdtype(x.dtype, np.floating):
            continue
        finite = np.isfinite(x)
        if finite.all():
            continue
        return {"path": jax.tree_util.keystr(path),
                "subtree": _subtree_name(path, depth),
                "nan": int(np.isnan(x).sum()),
                "inf": int(np.isinf(x).sum()),
                "shape": list(x.shape)}
    return None


def nonfinite_provenance(module, params, batch, model_state=None,
                         depth: int = DEFAULT_DEPTH) -> dict:
    """Name the first layer/op that produced a non-finite value.

    Two passes over HOST-safe values (call with a host shadow or a
    live-but-undonated state — never a reference a later jitted step
    may have consumed):

    1. params themselves — a NaN weight names its subtree directly;
    2. a ``capture_intermediates=True`` forward sweep — every
       submodule's output is checked and the earliest (execution-order
       for sequential stacks) non-finite intermediate is named, with
       its RMS/absmax so the report distinguishes overflow (huge finite
       inputs -> inf) from 0/0-style NaNs.

    Returns {"first", "kind", "param", "intermediates", "activations"}.
    ``first`` is the best single answer ("params:dense_1" or
    "intermediates/dense_1"); None fields mean that pass was clean."""
    report: dict = {"first": None, "kind": None, "param": None,
                    "intermediates": [], "activations": {}}
    bad_param = first_nonfinite(params, depth)
    if bad_param is not None:
        report["param"] = bad_param
        report["first"] = f"params:{bad_param['subtree']}"
        report["kind"] = "nan" if bad_param["nan"] else "inf"
    if module is None:
        return report
    try:
        x = (next(iter(batch.values())) if isinstance(batch, dict)
             else batch)
        variables = {"params": params, **(model_state or {})}
        _, inter = module.apply(
            variables, jnp.asarray(x),
            capture_intermediates=True, mutable=["intermediates"])
        flat = jax.tree_util.tree_flatten_with_path(
            inter.get("intermediates", {}))[0]
        rows = []
        for path, leaf in flat:
            arr = np.asarray(jax.device_get(leaf))
            if not np.issubdtype(arr.dtype, np.floating):
                continue
            # Module path only: drop the "__call__" markers and tuple
            # indices flax's capture adds; the whole-module output (no
            # module path at all) is named "__root__" and attributed
            # LAST — it is downstream of everything, so it being bad
            # carries no localization.
            name = "/".join(
                str(e.key) for e in path
                if hasattr(e, "key") and str(e.key) != "__call__")
            name = name or "__root__"
            finite = np.isfinite(arr)
            row = {"layer": name,
                   "nan": int(np.isnan(arr).sum()),
                   "inf": int(np.isinf(arr).sum()),
                   "rms": float(np.sqrt(np.mean(
                       np.square(np.where(finite, arr, 0.0))))),
                   "absmax": float(np.abs(
                       np.where(finite, arr, 0.0)).max(initial=0.0))}
            report["activations"][name] = {
                "rms": row["rms"], "absmax": row["absmax"]}
            if row["nan"] or row["inf"]:
                rows.append(row)
        # NaN/Inf propagates FORWARD: every layer after the faulting one
        # is also non-finite, so the earliest bad layer (name order
        # tracks execution order for the sequential stacks flax emits:
        # dense_0 < dense_1 < head-by-depth; the root output last) is
        # the origin.
        rows.sort(key=lambda r: (r["layer"] == "__root__", r["layer"]))
        report["intermediates"] = rows
        if rows and report["first"] is None:
            report["first"] = f"intermediates:{rows[0]['layer']}"
            report["kind"] = "nan" if rows[0]["nan"] else "inf"
    except Exception as e:  # a broken model must not mask the incident
        report["sweep_error"] = f"{type(e).__name__}: {e}"
    return report


# -- parity harness -----------------------------------------------------------


def max_ulp_diff(a: np.ndarray, b: np.ndarray) -> int:
    """Max distance in units-in-the-last-place between two same-shape
    float arrays (0 = bitwise identical up to signed zero)."""
    a = np.asarray(a)
    b = np.asarray(b, a.dtype)
    if a.dtype == np.float64:
        ai = a.view(np.int64)
        bi = b.view(np.int64)
        bias = np.int64(1) << 63
    else:
        a = a.astype(np.float32)
        b = b.astype(np.float32)
        ai = a.view(np.int32)
        bi = b.view(np.int32)
        bias = np.int32(1) << 31
    # Map the sign-magnitude float ordering onto a monotone integer
    # line so |ai' - bi'| counts representable floats between a and b.
    ai = np.where(ai < 0, bias - ai, ai).astype(np.int64)
    bi = np.where(bi < 0, np.int64(bias) - bi, bi).astype(np.int64)
    both = np.isfinite(a) & np.isfinite(b)
    if not both.any():
        return 0 if (np.isfinite(a) == np.isfinite(b)).all() else 1 << 62
    return int(np.abs(ai - bi)[both].max(initial=0))


def compare_trees(a, b, depth: int = DEFAULT_DEPTH) -> Dict[str, dict]:
    """Per-subtree {max_abs_err, max_rel_err, max_ulp} between two HOST
    trees with the same structure."""
    sa, sb = subtrees(a, depth), subtrees(b, depth)
    out: Dict[str, dict] = {}
    for name in sorted(set(sa) | set(sb)):
        la, lb = sa.get(name, []), sb.get(name, [])
        if len(la) != len(lb):
            out[name] = {"error": "structure mismatch"}
            continue
        max_abs = 0.0
        max_rel = 0.0
        max_ulp = 0
        for x, y in zip(la, lb):
            xa = np.asarray(jax.device_get(x), np.float64)
            ya = np.asarray(jax.device_get(y), np.float64)
            err = np.abs(xa - ya)
            max_abs = max(max_abs, float(err.max(initial=0.0)))
            denom = np.maximum(np.maximum(np.abs(xa), np.abs(ya)), 1e-30)
            max_rel = max(max_rel, float((err / denom).max(initial=0.0)))
            max_ulp = max(max_ulp, max_ulp_diff(
                np.asarray(jax.device_get(x)),
                np.asarray(jax.device_get(y))))
        out[name] = {"max_abs_err": max_abs, "max_rel_err": max_rel,
                     "max_ulp": max_ulp}
    return out


class ParityHarness:
    """Run a reference and a candidate step fn side by side and report
    max-ulp / rel-err per parameter subtree per step.

    The opt-in acceptance harness for numeric refactors (ZeRO update
    sharding, quantized exchange): drive both implementations with the
    SAME batches, compare params after every step, and get the first
    step + subtree any tolerance is exceeded at — deterministic, unlike
    comparing two separately-recorded fingerprint trails.

        with ParityHarness(ref_step, cand_step, s_ref, s_cand) as h:
            for batch in batches:
                h.step(batch)
        report = h.report(rtol=1e-5)

    ``params_of`` extracts the compared tree from a state (default
    ``.params``); ``get`` defaults to ``jax.device_get``. Both step fns
    must take (state, batch) and return (state, metrics)."""

    def __init__(self, ref_step: Callable, cand_step: Callable,
                 ref_state, cand_state,
                 params_of: Callable = lambda s: s.params,
                 depth: int = DEFAULT_DEPTH,
                 cand_batch: Optional[Callable] = None):
        self.ref_step = ref_step
        self.cand_step = cand_step
        self.ref_state = ref_state
        self.cand_state = cand_state
        self.params_of = params_of
        self.depth = depth
        self.cand_batch = cand_batch or (lambda b: b)
        self.steps: List[dict] = []

    def __enter__(self) -> "ParityHarness":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def step(self, batch) -> dict:
        self.ref_state, _ = self.ref_step(self.ref_state, batch)
        self.cand_state, _ = self.cand_step(self.cand_state,
                                            self.cand_batch(batch))
        cmp = compare_trees(jax.device_get(self.params_of(self.ref_state)),
                            jax.device_get(self.params_of(self.cand_state)),
                            self.depth)
        rec = {"step": len(self.steps) + 1, "subtrees": cmp}
        self.steps.append(rec)
        return rec

    def report(self, rtol: float = 1e-5, atol: float = 1e-6) -> dict:
        """Summary over all driven steps: worst subtree, first step any
        subtree exceeded rtol/atol, per-subtree worst errors."""
        worst: Dict[str, dict] = {}
        first_bad = None
        for rec in self.steps:
            for name, c in rec["subtrees"].items():
                if "error" in c:
                    continue
                w = worst.setdefault(name, {"max_abs_err": 0.0,
                                            "max_rel_err": 0.0,
                                            "max_ulp": 0})
                for k in w:
                    w[k] = max(w[k], c[k])
                if (first_bad is None
                        and c["max_abs_err"] > atol
                        and c["max_rel_err"] > rtol):
                    first_bad = {"step": rec["step"], "subtree": name,
                                 **c}
        return {"steps": len(self.steps), "subtrees": worst,
                "within_tolerance": first_bad is None,
                "first_exceeded": first_bad}


# -- loss-health detectors ----------------------------------------------------


class LossHealth:
    """EWMA spike/plateau/explosion detection over per-step
    (loss, grad_norm) pairs. Pure detector math — the health engine owns
    an instance and translates findings into typed alerts; tests drive
    ``update`` with fabricated series.

    * **loss_spike** — modified z of the new loss against the EWMA
      baseline (:class:`~serverless_learn_tpu.telemetry.health.EwmaMad`)
      above ``spike_z`` fires a warning; above ``2 x spike_z`` (or a
      non-finite loss) it escalates to critical.
    * **loss_plateau** — best-seen loss not improved by
      ``plateau_min_rel`` in ``plateau_window`` steps (after one full
      window of warmup) fires a warning; resolves on the next
      improvement.
    * **grad_explosion** — grad-norm z above ``explode_z`` is critical
      (a norm that detaches from its own history by that much is how
      divergence starts; the spike detector would call it a warning a
      few steps too late)."""

    def __init__(self, spike_z: float = 6.0, plateau_window: int = 50,
                 plateau_min_rel: float = 1e-3, explode_z: float = 8.0,
                 min_samples: int = 12):
        self.spike_z = spike_z
        self.explode_z = explode_z
        self.plateau_window = max(2, int(plateau_window))
        self.plateau_min_rel = plateau_min_rel
        self._loss = EwmaMad(min_samples=min_samples)
        self._grad = EwmaMad(min_samples=min_samples)
        self._best_loss: Optional[float] = None
        self._best_step: Optional[int] = None
        self._n = 0

    def update(self, step: int, loss: Optional[float],
               grad_norm: Optional[float] = None) -> Dict[str, Optional[dict]]:
        """One step's verdicts: {"loss_spike": finding|None,
        "loss_plateau": ..., "grad_explosion": ..., "nonfinite": ...}.
        A None value means that detector is calm this step."""
        out: Dict[str, Optional[dict]] = {
            "loss_spike": None, "loss_plateau": None,
            "grad_explosion": None, "nonfinite": None}
        self._n += 1
        if loss is not None and not np.isfinite(loss):
            out["nonfinite"] = {"severity": "critical", "value": float("nan"),
                                "threshold": 0.0,
                                "message": f"loss is non-finite at step "
                                           f"{step}"}
            return out  # a NaN loss must not poison the baselines
        if loss is not None:
            z = self._loss.update(float(loss))
            if z is not None and z > self.spike_z:
                sev = "critical" if z > 2 * self.spike_z else "warning"
                out["loss_spike"] = {
                    "severity": sev, "value": float(loss),
                    "threshold": self.spike_z,
                    "message": f"loss {loss:.6g} spiked at step {step} "
                               f"(z={z:.1f}, ewma="
                               f"{self._loss.ewma:.6g})"}
            improved = (self._best_loss is None
                        or loss < self._best_loss
                        * (1 - self.plateau_min_rel))
            if improved:
                self._best_loss = float(loss)
                self._best_step = step
            elif (self._best_step is not None
                  and self._n > self.plateau_window
                  and step - self._best_step >= self.plateau_window):
                out["loss_plateau"] = {
                    "severity": "warning", "value": float(loss),
                    "threshold": float(self.plateau_window),
                    "message": f"loss has not improved by "
                               f"{self.plateau_min_rel:g} rel in "
                               f"{step - self._best_step} steps "
                               f"(best {self._best_loss:.6g} at step "
                               f"{self._best_step})"}
        if grad_norm is not None:
            if not np.isfinite(grad_norm):
                out["nonfinite"] = {
                    "severity": "critical", "value": float("nan"),
                    "threshold": 0.0,
                    "message": f"grad norm is non-finite at step {step}"}
                return out
            gz = self._grad.update(float(grad_norm))
            if gz is not None and gz > self.explode_z:
                out["grad_explosion"] = {
                    "severity": "critical", "value": float(grad_norm),
                    "threshold": self.explode_z,
                    "message": f"grad norm {grad_norm:.6g} exploded at "
                               f"step {step} (z={gz:.1f}, ewma="
                               f"{self._grad.ewma:.6g})"}
        return out


# -- per-step record ring + last report (the /numerics read side) -------------

# Module-level ring of per-step numerics records: the training auditor
# publishes here (and to the JSONL sink); the health engine's numerics
# tick and the /numerics endpoint read it without plumbing a handle
# through the training stack — the same pattern health.note_round uses
# for DiLoCo round records.
_steps_lock = threading.Lock()
_steps: deque = deque(maxlen=512)
_last_report: Optional[dict] = None


def note_step(record: dict):
    """Publish one per-step numerics record ({"step", "loss",
    "grad_norm", "nonfinite", ...}); bounded, thread-safe."""
    with _steps_lock:
        _steps.append(dict(record))


def recent_steps(n: int = 64) -> List[dict]:
    with _steps_lock:
        return list(_steps)[-n:]


def clear_steps():
    global _last_report
    with _steps_lock:
        _steps.clear()
        _last_report = None


def set_last_report(report: dict):
    """The auditor stamps its newest host-fetched summary here (floats
    only — never device references; a donated buffer must not be
    reachable from a scrape)."""
    global _last_report
    with _steps_lock:
        _last_report = dict(report)


def endpoint_payload() -> dict:
    """The `/numerics` endpoint body: newest summary + recent ring."""
    with _steps_lock:
        report = dict(_last_report) if _last_report else None
        recent = list(_steps)[-16:]
    return {"enabled": report is not None, "last": report,
            "recent_steps": recent}


# -- self-check ---------------------------------------------------------------


def self_check() -> dict:
    """CI smoke (`slt numerics --self-check`, mirrors doctor/goodput):
    stat math is exact on fabricated tensors, a seeded NaN is named,
    fingerprint bisection finds a seeded divergence, and the loss-spike
    detector fires on a fabricated series. Never raises."""
    report: dict = {"ok": False, "checks": []}

    def check(name: str, ok: bool, detail: str = ""):
        report["checks"].append({"check": name, "ok": bool(ok),
                                 **({"detail": detail} if detail else {})})
        return ok

    try:
        rng = np.random.default_rng(0)
        tree = {"dense_0": {"kernel": rng.normal(size=(8, 4)).astype(
            np.float32), "bias": np.zeros((4,), np.float32)},
            "head": {"kernel": rng.normal(size=(4, 2)).astype(np.float32)}}
        stats = jax.device_get(tree_stats(tree))
        want = float(np.sqrt((np.asarray(tree["dense_0"]["kernel"]) ** 2)
                             .sum()))
        got = float(stats["dense_0"]["l2"])
        check("stats_exact", abs(got - want) <= 1e-5 * max(want, 1.0),
              f"l2 got={got:.6g} want={want:.6g}")
        gn = float(jax.device_get(global_norm(tree)))
        want_gn = float(np.sqrt(sum(
            (np.asarray(l) ** 2).sum()
            for l in jax.tree_util.tree_leaves(tree))))
        check("global_norm_exact", abs(gn - want_gn) <= 1e-5 * want_gn,
              f"got={gn:.6g} want={want_gn:.6g}")

        bad = jax.tree_util.tree_map(np.array, tree)
        bad["head"]["kernel"] = bad["head"]["kernel"].copy()
        bad["head"]["kernel"][1, 1] = np.nan
        hit = first_nonfinite(bad)
        check("nan_named", hit is not None
              and hit["subtree"] == "head" and hit["nan"] == 1,
              f"hit={hit}")

        fa = [{"event": "numerics_fingerprint", "step": s,
               "fp": jax.device_get(jax.tree_util.tree_map(
                   float, fingerprint(tree)))} for s in range(6)]
        fb = [dict(r, fp={k: dict(v) for k, v in r["fp"].items()})
              for r in fa]
        for r in fb:
            if r["step"] >= 3:
                r["fp"]["head"] = dict(r["fp"]["head"],
                                       sum=r["fp"]["head"]["sum"] + 1.0)
        d = diff_fingerprint_logs(fa, fb)
        check("bisect_finds_seeded_divergence",
              d["diverged"] and d["first_divergent_step"] == 3
              and d["subtree"] == "head",
              f"diff={d}")

        lh = LossHealth(spike_z=4.0, min_samples=4)
        fired = None
        for i in range(12):
            v = lh.update(i, 2.0 - 0.01 * i)
            assert not any(v.values()), v
        fired = lh.update(12, 50.0)["loss_spike"]
        check("loss_spike_fires", fired is not None
              and fired["severity"] == "critical", f"finding={fired}")

        ident = compare_trees(tree, tree)
        check("parity_identical_zero_ulp",
              all(c["max_ulp"] == 0 for c in ident.values()),
              f"{ident}")
        report["ok"] = all(c["ok"] for c in report["checks"])
    except Exception as e:
        check("exception", False, f"{type(e).__name__}: {e}")
    return report
