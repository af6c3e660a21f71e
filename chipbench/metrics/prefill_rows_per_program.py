"""Row-chunks a prefill program carried, averaged over the window's
prefill programs: the ``rows`` of the ``sched_iter`` records'
``programs`` entries named ``jit_pre_*`` (whole window, no trace
needed). How full the engine packs its prefill programs: up to
``max_slots``, at one row a slot for a model with recurrent state."""

import statistics

from chipbench import program_records


def read(run, entry):
    pre = program_records.prefill_entries(run)
    if not pre:
        return None
    run.setdefault("notes", {})["prefill_programs"] = len(pre)
    return statistics.fmean(p["rows"] for p in pre)
