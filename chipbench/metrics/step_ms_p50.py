"""Median device duration of the train-step program in the trace (the
``XLA Modules`` event of the jitted ``step_fn``). A median: per-layer
only; the end-to-end rate is taken over the whole window."""

import statistics


def read(run, entry):
    spans = [d for name, ds in run["trace"]["modules"].items()
             if "step_fn" in name for d in ds]
    if not spans:
        return None
    return 1e3 * statistics.median(spans)
