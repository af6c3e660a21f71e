"""Share of the engine's slots that hold no request, over the window's
iterations (``sched_iter`` records: ``slots_free`` over ``max_slots``,
the census taken after admission)."""

from chipbench.sched_records import iterations, share


def read(run, entry):
    return share(iterations(run), "slots_free", "max_slots")
