"""Readers' helper: the engine's request spans of the window."""

import statistics


def between_ms(run, a, b):
    """Median over the window's finished requests of mark ``b`` minus mark
    ``a`` (``None`` = the span's start), in milliseconds."""
    vals = []
    for rec in run["record"].get("spans", []):
        marks = rec.get("marks_s") or {}
        if b not in marks:
            continue
        t_a = 0.0 if a is None else marks.get(a)
        if t_a is None:
            continue
        vals.append(1e3 * (marks[b] - t_a))
    return statistics.median(vals) if vals else None


def prefill_tokens(rec) -> tuple:
    """(prompt tokens computed, prompt tokens taken from the prefix trie)
    of one request span, from its waterfall's prefill chunks."""
    computed = hit = 0
    for phase in (rec.get("waterfall") or {}).get("phases", []):
        for chunk in phase.get("chunks", []):
            computed += chunk.get("tokens", 0)
            hit += chunk.get("prefix_hit_tokens", 0)
    return computed, hit
