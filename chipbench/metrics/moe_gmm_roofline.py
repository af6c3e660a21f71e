"""Roofline share of the expert layers' grouped-product kernel: 100 x
the least time of the ``moe_gmm`` calls the traced programs made over
the device time of the trace's ``moe_gmm`` events (the
``tpu_custom_call`` events named by ``pallas_call(name="moe_gmm")``).

A call's least time is the larger of its FLOPs (2 m k n) over the
chip's peak and its bytes over the chip's bandwidth: the weights of the
experts it read, k x n each, and its m routed rows in and out, at the
operands' 2 bytes (bfloat16). Which of the two binds, per kind of
program, goes into the line's ``notes``. The calls are counted from the
serving programs the trace holds (device 0's ``XLA Modules`` events),
each launch making 3 calls (gate, up, down) per expert layer and step:

* a decode chunk ``jit_chunk_n{nb}w{W}``: ``chunk_size`` steps of
  m = nb x top_k rows, each reading the window's mean distinct experts
  per step and expert layer (the engine's ``experts_read``, through
  the architecture's ``experts_per_step``);
* a prefill program ``jit_pre_n{nb}t{T}w{W}``: m = nb x T x top_k rows
  (padding rows route and compute too), reading every held expert where
  nb x T >= 128 tokens (the chance that uniform routing misses one of
  128 experts is then below 3e-4). A smaller program's calls are left
  out of the least time and counted in ``notes``: their device time
  stays in the denominator, so the share reads low by their part.

The reduced trace keys op events by instruction name, and a name
repeats across programs, so a kernel event cannot be placed inside its
program. The launch at either end of the trace is recorded only in
part: its calls are counted at the share of the median whole launch of
the same program that the trace holds of it (the calls of a launch
spread evenly over it: one per product, layer and step), and nothing
where the trace holds no whole launch of that program (counted in
``notes``; the share then reads low). ``notes`` also gives the calls
counted beside the calls traced. Nothing where no ``moe_gmm`` event
ran (a program without the kernel), where the architecture has no
routed experts, or where no serving program was traced."""

import re
import statistics

from chipbench import flops, trace_reduce

KERNEL = "moe_gmm"
PRODUCTS = 3               # gate, up, down
MIN_PREFILL_TOKENS = 128
BYTES_PER_EL = 2
PROGRAM = re.compile(r"^jit_(pre|chunk)_n(\d+)(?:t(\d+))?w\d+")


def _kernel(trace) -> tuple:
    """(calls, device seconds) of the kernel's events."""
    calls = secs = 0.0
    for op, text in trace["op_text"].items():
        if (trace_reduce.KERNEL_CALL.search(text)
                and trace_reduce.kernel_name(op) == KERNEL):
            calls += trace["op_counts"][op]
            secs += trace["ops"][op]
    return calls, secs


def _launches(trace) -> tuple:
    """([(program name, share of the launch the trace holds)], names of
    cut launches with no whole launch to measure them by)."""
    first, last = trace["recorded"]
    gap = trace_reduce.MIN_GAP_S
    events = [(s, e, name, s > first + gap and e < last - gap)
              for s, e, name in trace["module_events"]
              if PROGRAM.match(name)]
    whole = {}
    for s, e, name, held in events:
        if held:
            whole.setdefault(name, []).append(e - s)
    out, unmeasured = [], []
    for s, e, name, held in events:
        if held:
            out.append((name, 1.0))
        elif name in whole:
            out.append((name, min(1.0, (e - s)
                                  / statistics.median(whole[name]))))
        else:
            unmeasured.append(name)
    return out, unmeasured


def _layer_least(sz, m: int, experts: float, peak) -> tuple:
    """(least seconds of one expert layer's three calls over m rows that
    read ``experts`` experts, the bounds that bind them)."""
    total, bounds = 0.0, set()
    for k, n in ((sz.d_model, sz.expert_d_ff), (sz.d_model, sz.expert_d_ff),
                 (sz.expert_d_ff, sz.d_model)):
        t, bound = flops.least_seconds(
            2.0 * m * k * n,
            BYTES_PER_EL * (experts * k * n + m * k + m * n), peak)
        total += t
        bounds.add(bound)
    return total, "/".join(sorted(bounds))


def read(run, entry):
    trace = run["trace"]
    calls, secs = _kernel(trace)
    cell = run["cell"]
    sz = cell.sizes
    per_step = getattr(cell.arch, "experts_per_step", None)
    if not secs or per_step is None or not getattr(sz, "n_moe", 0) \
            or not trace.get("recorded"):
        return None
    launches, unmeasured = _launches(trace)
    c = run["record"]["counters"]
    peak = flops.peaks(run["device"]["kind"])
    least = counted = 0.0
    small, bound = 0, {}
    for name, share in launches:
        kind, nb, t = PROGRAM.match(name).groups()
        nb = int(nb)
        if kind == "chunk":
            rows, steps = nb, c["chunk_size"]
            experts = per_step(sz, rows, run["record"]) / sz.n_moe
        else:
            rows, steps = nb * int(t), 1
            if rows < MIN_PREFILL_TOKENS:
                small += 1
                continue
            experts = sz.n_experts
        t_layer, bound[kind] = _layer_least(sz, rows * sz.top_k, experts,
                                            peak)
        least += share * steps * sz.n_moe * t_layer
        counted += share * steps * sz.n_moe * PRODUCTS
    run.setdefault("notes", {})["moe_gmm"] = {
        "calls_traced": calls, "calls_counted": round(counted, 3),
        "seconds": secs, "bound": bound,
        "small_prefill_left_out": small,
        "cut_unmeasured": len(unmeasured)}
    if not counted:
        return None
    return 100.0 * least / secs
