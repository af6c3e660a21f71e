"""Median engine-side time from ``submit`` to slot admission (the request
span's ``admit`` mark)."""

from chipbench.spans import between_ms


def read(run, entry):
    return between_ms(run, None, "admit")
