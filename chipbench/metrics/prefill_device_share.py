"""Share of the device's serving time that the window's prefill programs
take: each of the window's ``programs`` entries (``sched_iter`` records)
counted at the mean device time of the trace's whole events of its kind
(``jit_pre_*`` or ``jit_chunk_*``), the prefill part over the whole.
The trace holds some 1.5 s; the entries are the whole window's, so the
share is the window's mix at the traced programs' times. Nothing where a
kind the window launched has no whole event in the trace; 0 where the
window launched no prefill program."""

from chipbench import program_records


def read(run, entry):
    launched = program_records.entries(run)
    part = {}
    for kind in (program_records.PREFILL, program_records.CHUNK):
        n = sum(p["prog"].startswith(kind) for p in launched)
        if not n:
            part[kind] = 0.0
            continue
        mean = program_records.mean_whole_seconds(run, kind)
        if mean is None:
            return None
        part[kind] = n * mean
    total = sum(part.values())
    if not total:
        return None
    return 100.0 * part[program_records.PREFILL] / total
