"""Roofline share of the flash-attention BACKWARD kernels in the traced
steps: one backward call is one ``flash_bwd_dq`` and one ``flash_bwd_dkv``
event, and its least time is the architecture's ``bwd_flops`` and
``bwd_bytes`` for both. See ``chipbench/flash_kernels.py``."""

from chipbench import flash_kernels


def read(run, entry):
    return flash_kernels.roofline(run, forward=False, backward=True)
