"""Median host time of one scheduler iteration: its duration less the
time blocked on the device (``harvest_wait``) and on an empty queue
(``queue_idle``), from the ``sched_iter`` records of the window."""

import statistics

from chipbench.sched_records import iterations


def read(run, entry):
    records = iterations(run)
    if not records:
        return None
    return 1e3 * statistics.median(
        rec["dur_s"] - rec["phases_s"]["harvest_wait"]
        - rec["phases_s"]["queue_idle"] for rec in records)
