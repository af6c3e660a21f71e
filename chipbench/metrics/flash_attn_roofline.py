"""Roofline share of the Pallas flash-attention kernels in the traced
steps, forward and backward together: the least time the chip could take
for the calls that ran (from shapes, the cell's architecture's
``flash_attention_cost``: the larger of FLOPs over peak and bytes over
peak bandwidth, per call) over the kernels' device time. The kernels are
told apart by name (``chipbench/flash_kernels.py``); ``flash_fwd_roofline``
and ``flash_bwd_roofline`` give the two halves."""

from chipbench import flash_kernels


def read(run, entry):
    return flash_kernels.roofline(run, forward=True, backward=True)
