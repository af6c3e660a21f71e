"""Prompt tokens sent to prefill programs per second of the window
(``sched_iter`` records: the sum of ``prefill_tokens`` over the window's
length). In a saturated closed loop this is the rate at which work
enters, the same number as the end-to-end rate seen from the other
side."""

from chipbench.sched_records import iterations


def read(run, entry):
    records = iterations(run)
    if not records:
        return None
    return (sum(rec["prefill_tokens"] for rec in records)
            / run["record"]["window_s"])
