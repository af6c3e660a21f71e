"""Prompt tokens served from the prefix trie over prompt tokens, across
the requests that finished in the window (the request waterfalls'
prefill chunks: ``prefix_hit_tokens`` over hit plus computed tokens)."""

from chipbench.spans import prefill_tokens


def read(run, entry):
    computed = hit = 0
    for rec in run["record"].get("spans", []):
        c, h = prefill_tokens(rec)
        computed, hit = computed + c, hit + h
    if computed + hit == 0:
        return None
    return 100.0 * hit / (computed + hit)
