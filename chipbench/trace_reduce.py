"""From a profiler trace (``.xplane.pb``) to numbers.

Read with ``jax.profiler.ProfileData`` alone. On a TPU the device planes
are named ``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per
executed HLO operation (a Pallas kernel is one such event), and ``XLA
Modules`` one event per launched program. Busy time is the union of the
``XLA Ops`` intervals ONLY: a module's span covers its program by
construction, so counting it would read every gap inside a program as
busy (the fault of the program's own ``telemetry/xray.py`` reduction).
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MIN_GAP_S = 1e-6   # shorter than this is the trace's own granularity
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def _events(line) -> list:
    """[(start_s, end_s, name)] sorted by start."""
    out = [(ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9,
            ev.name) for ev in line.events]
    out.sort()
    return out


def union_seconds(intervals: list) -> tuple:
    """(covered seconds, [(gap_start, gap_end)]) of sorted intervals."""
    busy, gaps, cur_s, cur_e = 0.0, [], None, None
    for s, e, *_ in intervals:
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s <= cur_e:
            cur_e = max(cur_e, e)
        else:
            busy += cur_e - cur_s
            gaps.append((cur_e, s))
            cur_s, cur_e = s, e
    if cur_e is not None:
        busy += cur_e - cur_s
    return busy, gaps


def module_name(event_name: str) -> str:
    """``jit_step_fn(123456789)`` -> ``jit_step_fn``."""
    return re.sub(r"\(\d+\)$", "", event_name).strip()


def op_name(event_name: str) -> str:
    """An op event is named by its whole HLO text, ``%fusion.3 = bf16[..]
    fusion(..)``; the instruction's name is enough to read."""
    return event_name.split(" = ", 1)[0].lstrip("%").strip()


def _host_spans(profile) -> list:
    """[(start_s, end_s, name)] of what the host's Python threads were
    doing: the ``python`` lines of the ``/host:CPU`` plane, which hold the
    program's ``TraceAnnotation``s among the interpreter's own calls.
    Runtime threads are left out: they only say that the chip was waited
    for."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            if line.name.startswith("python"):
                out.extend(_events(line))
    out.sort()
    return out


def _is_frame(name: str) -> bool:
    """The Python tracer names an interpreter frame ``$file.py:line
    function``; a ``TraceAnnotation`` has the name the program gave it."""
    return name.startswith("$")


def _host_label(host: list, gap: tuple) -> str:
    """Name the gap by the annotations that cover its midpoint, outermost
    first (``sched.iter > sched.harvest > np.asarray(jax.Array)``): what
    the program says it was doing, down to the runtime's own scope under
    it, not the innermost interpreter frame (``$array.py:631 _value``). A
    gap that no annotation covers takes the shortest frame that does; one
    that nothing covers, the span that overlaps it most."""
    mid = 0.5 * (gap[0] + gap[1])
    covering = sorted((e - s, n) for s, e, n in host if s <= mid <= e)
    annotated = [n for _, n in reversed(covering) if not _is_frame(n)]
    if annotated:
        return " > ".join(dict.fromkeys(annotated))
    if covering:
        return covering[0][1]
    best, name = 0.0, "no host span"
    for s, e, n in host:
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > best:
            best, name = ov, n
    return name


def reduce_trace(path: str, top: int = 10) -> dict:
    """The reduced trace every per-layer reader gets.

    ``busy_s``/``span_s``: mean over device planes of the union of op
    intervals and of the span from the first op's start to the last op's
    end. ``ops``: {name: seconds}, summed over devices and divided by
    their number, keyed by the HLO instruction's name (``op_text`` keeps
    one whole HLO line per name, for readers that look for a kernel's
    call target). ``modules``: {program name: [durations in seconds]} of
    device 0; ``module_events``: the same events as (start, end, program
    name), and ``recorded``: the first and the last instant device 0's
    lines hold, for ``whole_events``. ``gaps``: the longest idle gaps of
    device 0 as (label, seconds), the label naming the programs either
    side and the host span that covers the gap.
    """
    profile = load(path)
    devices = []
    for plane in profile.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        if OPS_LINE not in lines:
            continue
        devices.append((plane.name, _events(lines[OPS_LINE]),
                        _events(lines[MODULES_LINE])
                        if MODULES_LINE in lines else []))
    if not devices:
        raise ValueError(
            f"{path}: no '/device:TPU:<n>' plane with an '{OPS_LINE}' line "
            f"(planes: {[p.name for p in profile.planes]})")
    devices.sort()
    n = len(devices)
    busy = span = 0.0
    ops = defaultdict(float)
    op_counts = defaultdict(float)
    op_text = {}
    for _, op_events, _ in devices:
        if not op_events:
            continue
        b, _ = union_seconds(op_events)
        busy += b
        span += max(e for _, e, _ in op_events) - op_events[0][0]
        for s, e, name in op_events:
            short = op_name(name)
            ops[short] += (e - s) / n
            op_counts[short] += 1.0 / n
            op_text.setdefault(short, name)
    _, op_events, mod_events = devices[0]
    module_events = [(s, e, module_name(name)) for s, e, name in mod_events]
    modules = defaultdict(list)
    for s, e, name in module_events:
        modules[name].append(e - s)
    both = op_events + mod_events          # each sorted by start
    recorded = (min(s for s, _, _ in both),
                max(e for _, e, _ in both)) if both else None
    _, gaps = union_seconds(op_events)
    gaps = sorted((g for g in gaps if g[1] - g[0] >= MIN_GAP_S),
                  key=lambda g: g[0] - g[1])[:top]
    host = _host_spans(profile)
    labelled = []
    for g in gaps:
        before = [nm for s, e, nm in module_events if e <= g[0] + 1e-9]
        after = [nm for s, e, nm in module_events if s >= g[1] - 1e-9]
        inside = [nm for s, e, nm in module_events
                  if s < g[0] and e > g[1]]
        where = (f"inside {inside[0]}" if inside else
                 f"{before[-1] if before else 'start'} -> "
                 f"{after[0] if after else 'end'}")
        labelled.append((f"{where} | host: {_host_label(host, g)}",
                         g[1] - g[0]))
    return {
        "n_devices": n,
        "busy_s": busy / n,
        "span_s": span / n,
        "ops": dict(ops),
        "op_counts": dict(op_counts),
        "op_text": op_text,
        "modules": dict(modules),
        "module_events": module_events,
        "recorded": recorded,
        "gaps": labelled,
    }


def whole_events(reduced: dict, program: str) -> list:
    """Durations of device 0's launches of the programs whose name holds
    ``program`` that the trace holds WHOLE. A launch that is running when
    the trace stops is recorded up to that instant only, and one that is
    running when it starts from its first instant on (on the v5e 0.376 s
    decode chunks were found as 0.094 s ending with the trace and as
    0.321 s beginning with it), so an event that touches the first or
    the last recorded instant is cut. Both are left out: a sum over
    events that counts a cut one as a whole launch reads too high."""
    if not reduced.get("recorded"):
        return []
    first, last = reduced["recorded"]
    return [e - s for s, e, name in reduced["module_events"]
            if program in name and s > first + MIN_GAP_S
            and e < last - MIN_GAP_S]


CONTAINER_OPS = ("while", "conditional", "call")


KERNEL_CALL = re.compile(r"custom-call\(.*tpu_custom_call", re.S)


def kernel_name(op: str) -> str:
    """A Pallas kernel's own name (``pallas_call(name=)``) from its HLO
    instruction's: ``flash_fwd.37`` -> ``flash_fwd``."""
    return re.sub(r"(\.\d+)+$", "", op)


def top_ops(reduced: dict, top: int = 10) -> list:
    """The operations that took most device time. A loop's or a branch's
    own event spans its body's events, which are listed themselves: it is
    left out here (the busy union counts either once). A Pallas kernel
    is called from many places under one name (``flash_fwd.37``,
    ``flash_fwd.42``: the forward kernel of two layers): its calls are
    summed under the name, so that the list tells the kernels apart and
    not the layers."""
    leaf = defaultdict(float)
    for n, s in reduced["ops"].items():
        if n.startswith(CONTAINER_OPS):
            continue
        kernel = KERNEL_CALL.search(reduced["op_text"].get(n, ""))
        leaf[kernel_name(n) if kernel else n] += s
    return [[name, secs] for name, secs in
            sorted(leaf.items(), key=lambda kv: -kv[1])[:top]]
