"""Device milliseconds a prompt token costs in prefill: the device time
of the trace's whole ``jit_pre_*`` events over the prompt tokens of the
``programs`` entries lined up with them (``program_records.aligned``:
the window's launches matched to the trace's events by name and clock).
A program's time follows its (nb, W) bucket, not its rows, so this falls
as programs carry more rows. Nothing where the launches and the events
cannot be lined up, or no whole prefill event was traced."""

from chipbench import program_records


def read(run, entry):
    pairs = program_records.aligned(run)
    if not pairs:
        return None
    whole = [(p["tokens"], e - s) for p, (s, e, name, w) in pairs
             if w and name.startswith(program_records.PREFILL)]
    tokens = sum(t for t, _ in whole)
    if not tokens:
        return None
    run["notes"]["prefill_events_whole"] = len(whole)
    return 1e3 * sum(d for _, d in whole) / tokens
