"""Live decode rows over the engine's slots, across the iterations of
the window that dispatched a decode chunk (``sched_iter`` records:
``decode_rows`` over ``max_slots``). Where ``decode_batch_occupancy``
divides by the rows of the compacted bucket and reads 100 at one row of
eight, this divides by what the chunk could have carried."""

from chipbench.sched_records import iterations, share


def read(run, entry):
    return share([rec for rec in iterations(run) if rec["decode_steps"]],
                 "decode_rows", "max_slots")
