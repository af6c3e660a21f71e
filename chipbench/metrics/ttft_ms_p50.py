"""Median engine-side time from ``submit`` to the first token reaching
the host (the request span's ``first_token`` mark). The wire does not
stream, so no client sees this; it is the scheduler's number."""

from chipbench.spans import between_ms


def read(run, entry):
    return between_ms(run, None, "first_token")
