"""The comparison that decides ``correct``: numbers and their limits.

Each number compared is a plain float with a limit of its own, read from
the configuration file (``limits``), where the readings it was set from
are recorded. ``correct`` is true when every number is at or under its
limit; a number that is not finite fails.
"""

from __future__ import annotations

import math

import numpy as np

# The control: the reference put in the program's place, computed in the
# nearest precision below the one the configuration states.
CONTROL_PRECISION = {"float32": "bfloat16", "bfloat16": "fp8"}

# Leaves whose reference gradient is under this share of the median
# leaf's move under Adam by round-off alone; they are left out of the
# change comparison by this rule, never by name.
NEGLIGIBLE_GRADIENT = 1e-3


def _leaf_norms(tree: list) -> dict:
    """{"layer.leaf": L2 norm} of a list (one dict per layer) of arrays."""
    return {f"{i}.{k}": float(np.linalg.norm(np.asarray(v, np.float64)))
            for i, layer in enumerate(tree) for k, v in layer.items()}


def worst_norm_gap(prog: list, ref: list, keep=None) -> tuple:
    """(worst gap, its leaf): per leaf, the gap between the program's norm
    and the reference's, against the reference's norm of that leaf or of
    the median leaf, whichever is larger."""
    p, r = _leaf_norms(prog), _leaf_norms(ref)
    median = float(np.median(list(r.values())))
    worst, where = 0.0, None
    for name, rn in r.items():
        if keep is not None and name not in keep:
            continue
        gap = abs(p[name] - rn) / max(rn, median, 1e-30)
        if not math.isfinite(gap):
            return float("inf"), name
        if gap >= worst:
            worst, where = gap, name
    return worst, where


def with_limits(values: dict, limits: dict, notes: dict = None) -> dict:
    """Pair each compared number with its limit. A number the limits do
    not name is not compared; it is kept in the notes with its reading."""
    notes = dict(notes or {})
    uncompared = {k: v for k, v in values.items() if k not in limits}
    if uncompared:
        notes["uncompared"] = uncompared
    return {"numbers": {k: {"value": v, "limit": limits[k]}
                        for k, v in values.items() if k in limits},
            "notes": notes}


def train_values(prog: dict, ref: dict) -> tuple:
    """(numbers, notes) of a training cell. ``prog``/``ref``: ``losses``
    (one per followed step), ``grads`` (first step, per leaf), ``change``
    (parameters after the followed steps minus before)."""
    out = {}
    for i, (lp, lr) in enumerate(zip(prog["losses"], ref["losses"])):
        out[f"loss{i + 1}_gap"] = abs(lp - lr) / abs(lr)
    out["grad_norm_gap"], grad_leaf = worst_norm_gap(prog["grads"],
                                                     ref["grads"])
    g = _leaf_norms(ref["grads"])
    g_median = float(np.median(list(g.values())))
    moved = {k for k, v in g.items() if v >= NEGLIGIBLE_GRADIENT * g_median}
    out["change_norm_gap"], change_leaf = worst_norm_gap(
        prog["change"], ref["change"], keep=moved)
    return out, {"grad_leaf": grad_leaf, "change_leaf": change_leaf,
                 "leaves_left_out": len(g) - len(moved),
                 "losses": prog["losses"], "ref_losses": ref["losses"]}


def serve_values(gaps_by_request: list, never_came: int,
                 wrong_length: int) -> tuple:
    """(numbers, notes) of a serving cell: the widest gap by which a
    served (greedy) token's reference logit lies below the reference's
    best, over every served token of the sampled requests; the requests
    that never answered, and the replies of another length than asked."""
    widest = max((float(np.max(g)) for g in gaps_by_request if len(g)),
                 default=float("inf"))
    return ({"served_logit_gap": widest,
             "requests_unanswered": float(never_came),
             "replies_wrong_length": float(wrong_length)},
            {"tokens_compared": int(sum(len(g) for g in gaps_by_request)),
             "requests_compared": len(gaps_by_request)})


def verdict(numbers: dict) -> bool:
    return all(math.isfinite(n["value"]) and n["value"] <= n["limit"]
               for n in numbers.values())
