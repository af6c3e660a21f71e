"""Roofline share of the decode programs in the traced part of the
window: the least time the chip could take for the decode steps that ran
(the bytes a step has to read over peak bandwidth, or its FLOPs over peak,
whichever is larger) over the device time of the decode-chunk programs
(``jit_chunk`` modules). What a step reads and computes is the cell's
architecture's to count (``arch/<name>.py`` ``decode_step_cost``; the
dense decoder: every weight once, each live row's keys and values once).
Rows per step and context are the window's means (engine counters, client
records). No Pallas kernel runs in decode today: this is the serving
cells' roofline."""

from chipbench import flops


def read(run, entry):
    mods = run["trace"]["modules"]
    spans = [d for name, ds in mods.items() if "chunk" in name for d in ds]
    c = run["record"]["counters"]
    if not spans or not c.get("chunks_run"):
        return None
    cell = run["cell"]
    rows = c["decoded_rows"] / c["chunks_run"]
    cost = cell.arch.decode_step_cost(cell.sizes, rows,
                                      c["mean_context_arrived"])
    peak = flops.peaks(run["device"]["kind"])
    t_step, _ = flops.least_seconds(cost["flops"], cost["bytes"], peak)
    least = len(spans) * c["chunk_size"] * t_step
    return 100.0 * least / sum(spans)
