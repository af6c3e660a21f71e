"""Readers' helper: the Pallas flash-attention kernels of a reduced
trace, told apart by NAME, and their roofline share.

The kernels are the ``tpu_custom_call`` custom-calls of the ``XLA Ops``
line. Since PR 26 the program names them through ``pallas_call(name=)``,
and the name is the HLO instruction's: ``flash_fwd.<n>``,
``flash_bwd_dq.<n>``, ``flash_bwd_dkv.<n>``. One backward call is one dq
and one dk/dv event; a forward that the step recomputes (remat) is a
forward call: it ran. Result types say nothing (the dk/dv kernel's two
results are float32 like the forward's row statistics: the reader that
went by them counted dk/dv calls as forward calls), so a
``tpu_custom_call`` of any other name is not guessed at: the share is
left out and the name goes into the line's ``notes``.
"""

from __future__ import annotations

from chipbench import flops
from chipbench.trace_reduce import KERNEL_CALL, kernel_name

FORWARD = ("flash_fwd",)
BACKWARD = ("flash_bwd_dq", "flash_bwd_dkv")


def kernels(trace: dict) -> dict:
    """{kernel name: [calls, seconds]} of every ``tpu_custom_call`` in the
    reduced trace, whatever it is called."""
    out: dict = {}
    for op, text in trace["op_text"].items():
        if KERNEL_CALL.search(text):
            entry = out.setdefault(kernel_name(op), [0.0, 0.0])
            entry[0] += trace["op_counts"][op]
            entry[1] += trace["ops"][op]
    return out


def roofline(run: dict, forward: bool, backward: bool):
    """100 x (least time of the flash calls that ran) / (their device
    time), over the forward kernel, the backward pair, or both. ``None``
    where no kernel ran, where a kernel of another name ran, or where the
    dq and dk/dv events do not pair."""
    found = kernels(run["trace"])
    if not found:
        return None
    notes = run.setdefault("notes", {})
    notes["flash_kernels"] = {k: {"calls": n, "seconds": s}
                              for k, (n, s) in sorted(found.items())}
    unknown = sorted(set(found) - set(FORWARD + BACKWARD))
    if unknown:
        notes["flash_kernels_unknown"] = unknown
        return None
    n_fwd, s_fwd = found.get("flash_fwd", (0.0, 0.0))
    n_dq, s_dq = found.get("flash_bwd_dq", (0.0, 0.0))
    n_dkv, s_dkv = found.get("flash_bwd_dkv", (0.0, 0.0))
    if n_dq != n_dkv:
        notes["flash_kernels_unpaired"] = [n_dq, n_dkv]
        return None
    cell = run["cell"]
    cost = cell.arch.flash_attention_cost(
        cell.sizes, cell.traffic["sequences_per_step"],
        cell.traffic["tokens_per_sequence"])
    peak = flops.peaks(run["device"]["kind"])
    t_fwd, _ = flops.least_seconds(cost["fwd_flops"], cost["fwd_bytes"], peak)
    t_bwd, _ = flops.least_seconds(cost["bwd_flops"], cost["bwd_bytes"], peak)
    least = took = 0.0
    if forward:
        least, took = least + n_fwd * t_fwd, took + s_fwd
    if backward:
        least, took = least + n_dq * t_bwd, took + s_dq + s_dkv
    return 100.0 * least / took if took else None
