"""Of the expert weights the window's decode steps could have streamed,
the share they did: the sum of the ``sched_iter`` records'
``experts_read`` (per harvested decode step and expert layer, the
distinct experts its live rows were routed to, from the engine's own
counter) over the records' decode steps x ``moe_layers`` x
``n_experts`` (the engine's ``kv_stats``). A step that reads every
expert reads 100; routing that sends a step's rows to fewer experts
reads less, and the step streams that much less.

Its ``notes`` also say how many of the window's decode row-steps had a
context past the model's attention window (``decode_past_window_share``:
from the requests that arrived, each step of a reply that is not its
first token; nothing where the architecture has no window).

Nothing to read (and no number) where the engine has no expert layer,
or its records carry no ``experts_read`` (a program from before the
engine counted them)."""

from chipbench.sched_records import iterations


def _past_window(record, window):
    past = steps = 0
    for r in record.get("finished", []):
        p, n = len(r["item"]["prompt"]), len(r["new_tokens"])
        steps += max(n - 1, 0)
        past += max(n - 1 - max(window - p, 0), 0)
    return 100.0 * past / steps if steps else None


def read(run, entry):
    kv = run["record"]["counters"].get("kv_stats") or {}
    records = [rec for rec in iterations(run) if "experts_read" in rec]
    steps = sum(rec["decode_steps"] for rec in records)
    slots = kv.get("moe_layers", 0) * kv.get("n_experts", 0)
    if not slots or not steps:
        return None
    window = getattr(run["cell"].sizes, "window", 0)
    if window:
        run.setdefault("notes", {})["decode_past_window_share"] = \
            _past_window(run["record"], window)
    return 100.0 * sum(rec["experts_read"] for rec in records) / (
        steps * slots)
