"""Of the least bytes the window's decode steps had to move, the share
that is recurrent state read and written: 2 x ``state_bytes_per_slot``
(what one slot holds beside the paged pool, from the engine's own
``kv_stats``) x the live rows, over the least bytes of a decode step at
the window's mean rows and context (the cell's architecture counts them,
``arch/<name>.py`` ``decode_step_cost``: the weights once, every live
row's state and its keys and values). Rows are the ``sched_iter``
records': rows decoded over chunks dispatched.

It says what a smaller or fused state could win at most, and it moves
with how full the slots are: the weights are paid once a step, the state
once a row. Nothing to read (and no number) where the engine holds no
state per slot: a model whose every layer attends, or a program from
before the engine knew such state."""

from chipbench.sched_records import iterations


def read(run, entry):
    c = run["record"]["counters"]
    per_slot = (c.get("kv_stats") or {}).get("state_bytes_per_slot")
    records = iterations(run)
    chunks = sum(rec["decode_steps"] for rec in records) / c["chunk_size"]
    if not per_slot or not chunks:
        return None
    cell = run["cell"]
    rows = sum(rec["decode_rows"] for rec in records) / chunks
    cost = cell.arch.decode_step_cost(cell.sizes, rows,
                                      c["mean_context_arrived"],
                                      run["record"])
    run.setdefault("notes", {})["state_bytes_per_slot"] = per_slot
    return 100.0 * rows * 2 * per_slot / cost["bytes"]
