"""Programs compiled (or fetched from the compile cache) while the
window was open, by JAX's own compile events. Has to read 0."""


def read(run, entry):
    return run["record"]["counters"]["compiles_in_window"]
