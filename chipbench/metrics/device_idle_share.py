"""1 - (union of the ``XLA Ops`` intervals) / (traced window), averaged
over the chips used."""


def read(run, entry):
    if run["traced_s"] <= 0:
        return None
    return 100.0 * (1.0 - run["trace"]["busy_s"] / run["traced_s"])
