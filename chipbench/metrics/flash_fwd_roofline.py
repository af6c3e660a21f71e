"""Roofline share of the flash-attention FORWARD kernel (``flash_fwd``)
in the traced steps; a forward that remat runs again is a call like any
other. See ``chipbench/flash_kernels.py``."""

from chipbench import flash_kernels


def read(run, entry):
    return flash_kernels.roofline(run, forward=True, backward=False)
