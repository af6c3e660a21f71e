"""Roofline share of the decode programs in the traced part of the
window: the least time the chip could take for a decode chunk (the bytes
a step has to read over peak bandwidth, or its FLOPs over peak, whichever
is larger, times the chunk's steps) over the device time of the
decode-chunk programs (``jit_chunk`` modules) that the trace holds WHOLE.

What is left out, and why: a chunk that is running when the trace stops
is recorded up to that instant only, and one that is running when it
starts from its first instant on (``trace_reduce.whole_events``). A 1.5 s
trace holds three or four chunks of 0.376 s, and the reader that counted
every event as a whole chunk read 78 where none was cut and up to 102
where one was. A trace with no whole chunk gives nothing.

What a step reads and computes is the cell's architecture's to count
(``arch/<name>.py`` ``decode_step_cost``; the dense decoder: every weight
once, each live row's keys and values once). It is handed the run's
record too, so that an architecture whose step reads only what its rows
touched can count that. Rows per step and context are the window's means
(engine counters, client records): every chunk of a window ran the same
(nb, W) program on the chip, and a row more or less moves the least time
by 0.4 %. No Pallas kernel runs in decode today: this is the serving
cells' roofline."""

from chipbench import flops, trace_reduce


def read(run, entry):
    spans = trace_reduce.whole_events(run["trace"], "chunk")
    c = run["record"]["counters"]
    if not spans or not c.get("chunks_run"):
        return None
    cell = run["cell"]
    rows = c["decoded_rows"] / c["chunks_run"]
    cost = cell.arch.decode_step_cost(cell.sizes, rows,
                                      c["mean_context_arrived"],
                                      run["record"])
    peak = flops.peaks(run["device"]["kind"])
    t_step, _ = flops.least_seconds(cost["flops"], cost["bytes"], peak)
    run.setdefault("notes", {})["decode_chunks_whole"] = len(spans)
    return 100.0 * len(spans) * c["chunk_size"] * t_step / sum(spans)
