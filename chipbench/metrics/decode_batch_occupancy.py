"""Rows that still owed tokens over rows of compute paid, across the
decode chunks dispatched in the window (the engine's exact counters)."""


def read(run, entry):
    c = run["record"]["counters"]
    if not c.get("dispatched_rows"):
        return None
    return 100.0 * c["decoded_rows"] / c["dispatched_rows"]
