"""Share of the window the training loop spent blocked on its
``Prefetcher`` (the program's goodput ledger, phase ``data_wait``)."""


def read(run, entry):
    rec = run["record"]
    if "data_wait_s" not in rec["counters"]:
        return None
    return 100.0 * rec["counters"]["data_wait_s"] / rec["window_s"]
