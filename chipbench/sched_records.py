"""Readers' helper: the scheduler's ``sched_iter`` records of the window
(one per working iteration of the engine's dispatch loop, in the same
sink as the request spans; ``PERF.md`` section 3 lists the fields)."""


def iterations(run) -> list:
    return [rec for rec in run["record"].get("spans", [])
            if rec.get("event") == "sched_iter"]


def share(records, part: str, whole: str):
    """100 x (sum of ``part``) / (sum of ``whole``) over the records;
    ``None`` where there is nothing to divide by."""
    total = sum(rec[whole] for rec in records)
    if not total:
        return None
    return 100.0 * sum(rec[part] for rec in records) / total
