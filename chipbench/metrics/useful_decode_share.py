"""Tokens the decode chunks of the window produced that a reply needed,
over the tokens those chunks could have produced: (``tokens_out`` -
``requests_finished``) over ``decode_steps`` x ``max_slots``, summed over
the window's ``sched_iter`` records (``decode_steps`` is the chunks
dispatched times the chunk's steps). A reply's first token comes out of
its last prefill program, not out of a chunk, so one token per finished
reply is taken off: the share cannot pass 100.

``decode_slot_occupancy`` counts a row as live while the host believes it
is; a row whose reply is already complete on the device, and whose last
chunk has not been harvested yet, decodes padding and still counts there.
This share leaves it out: it is what late discovery of finished rows
costs (``PERF.md`` section 5)."""

from chipbench.sched_records import iterations


def read(run, entry):
    records = iterations(run)
    could = sum(rec["decode_steps"] * rec["max_slots"] for rec in records)
    if not could:
        return None
    made = sum(rec["tokens_out"] - rec["requests_finished"]
               for rec in records)
    return 100.0 * made / could
