"""Roofline share of the Pallas flash-attention kernels in the traced
steps: the least time the chip could take for the calls that ran (from
shapes, the cell's architecture's ``flash_attention_cost``: the larger of
FLOPs over peak and bytes over peak bandwidth, per call) over the
kernels' device time.

The kernels are the ``tpu_custom_call`` custom-calls of the ``XLA Ops``
line (they carry the name of the flax module that calls them, ``attn``).
The forward kernel returns the row statistics beside its output, a
float32 result the backward kernels do not have; the backward's two
kernels (dq; dk and dv) together make one backward call."""

import re

from chipbench import flops

KERNEL = re.compile(r"custom-call\(.*tpu_custom_call", re.S)


def _is_forward(text: str) -> bool:
    return "f32[" in text.split(" custom-call(", 1)[0]


def read(run, entry):
    trace = run["trace"]
    fwd_s = bwd_s = fwd_n = bwd_n = 0.0
    for name, text in trace["op_text"].items():
        if not KERNEL.search(text):
            continue
        if _is_forward(text):
            fwd_s += trace["ops"][name]
            fwd_n += trace["op_counts"][name]
        else:
            bwd_s += trace["ops"][name]
            bwd_n += trace["op_counts"][name]
    if fwd_n + bwd_n == 0:
        return None
    cell = run["cell"]
    cost = cell.arch.flash_attention_cost(
        cell.sizes, cell.traffic["sequences_per_step"],
        cell.traffic["tokens_per_sequence"])
    peak = flops.peaks(run["device"]["kind"])
    t_fwd, _ = flops.least_seconds(cost["fwd_flops"], cost["fwd_bytes"], peak)
    t_bwd, _ = flops.least_seconds(cost["bwd_flops"], cost["bwd_bytes"], peak)
    least = fwd_n * t_fwd + (bwd_n / 2.0) * t_bwd
    return 100.0 * least / (fwd_s + bwd_s)
