"""Share of the engine's slots held by requests that are admitted but
whose prompt is not yet complete, over the window's iterations
(``sched_iter`` records: ``slots_prefilling`` over ``max_slots``, the
census taken after admission and before the prefill step)."""

from chipbench.sched_records import iterations, share


def read(run, entry):
    return share(iterations(run), "slots_prefilling", "max_slots")
