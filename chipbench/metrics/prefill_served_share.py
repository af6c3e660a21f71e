"""Of the rows waiting for prompt tokens, the share an iteration fed:
rows in the iteration's prefill program over slots mid-prefill, across
the window's iterations with any slot mid-prefill (``sched_iter``
records: ``prefill_rows`` over ``slots_prefilling``)."""

from chipbench.sched_records import iterations, share


def read(run, entry):
    return share([rec for rec in iterations(run) if rec["slots_prefilling"]],
                 "prefill_rows", "slots_prefilling")
