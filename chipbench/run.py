"""The benchmark's one command.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: it takes the chip, builds the cell's system from the seed,
warms every shape the cell uses (set-up), measures for ``--seconds``,
checks what the timed path produced against the plain reference, and
prints one JSON object as its last line. It fails where JAX finds no TPU
or fewer chips than the cell asks for: a CPU number is never a device
metric.
"""

from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()  # set-up is counted from here

import argparse
import importlib
import importlib.util
import json
import os
import shutil
import sys
import threading

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


class NoChip(SystemExit):
    pass


def place_caches(root: str) -> None:
    """The compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says,
    else to a fixed directory inside the checkout (the path is part of the
    cache's key); small programs are cached too. JAX may already have been
    imported, and it reads the environment only then: the directory is
    set through its configuration, which holds either way."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(chips: int, require_chip: bool) -> dict:
    import jax

    devices = jax.devices()
    d = devices[0]
    if require_chip and (d.platform != "tpu" or len(devices) < chips):
        raise NoChip(f"this cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devices)} x {d.platform} ({d.device_kind})")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak_bytes() -> int:
    import jax

    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


class CompileCounter:
    """Counts programs compiled or fetched from the cache while armed."""

    def __init__(self):
        import jax

        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.armed and event in COMPILE_EVENTS:
            self.count += 1


class Tracer:
    """Brackets part of the window in one profiler trace. Off (``--trace
    0``) every method does nothing. ``units``: stop after so many units of
    work (training steps); ``seconds``: stop after so long."""

    def __init__(self, on: bool, trace_dir: str, spec: dict,
                 compiles: CompileCounter):
        self.on, self.dir, self.spec = on, trace_dir, spec
        self.compiles = compiles
        self.running = False
        self.t_start = self.t_stop = None
        self._lock = threading.Lock()
        self._timer = None

    def window_opens(self) -> None:
        self.compiles.armed = True
        if not self.on:
            return
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        jax.profiler.start_trace(self.dir)
        self.running = True
        self.t_start = time.perf_counter()
        if "seconds" in self.spec:
            self._timer = threading.Timer(self.spec["seconds"], self._stop)
            self._timer.daemon = True
            self._timer.start()

    def after_unit(self, n: int) -> None:
        if self.on and "units" in self.spec and n >= self.spec["units"]:
            self._stop()

    def _stop(self) -> None:
        import jax

        with self._lock:
            if not self.running:
                return
            self.t_stop = time.perf_counter()
            jax.profiler.stop_trace()
            self.running = False

    def window_closes(self) -> None:
        self.compiles.armed = False
        if self._timer is not None:
            self._timer.cancel()
        self._stop()


def load_metric_reader(name: str, bench_dir: str):
    """``<bench_dir>/metrics/<name up to its first dot>.py`` -> its
    ``read``. One file serves ``x.train``, ``x.tput`` and ``x.lat``."""
    stem = name.split(".", 1)[0]
    path = os.path.join(bench_dir, "metrics", stem + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"per-layer metric {name!r} has no reader {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_driver(kind: str):
    return importlib.import_module(
        "chipbench.drivers." + kind.split("_", 1)[0])


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             root: str = _ROOT, require_chip: bool = True,
             stderr=sys.stderr) -> dict:
    """Everything but argument parsing and printing; returns the result
    line as a dict. ``require_chip=False`` is for the harness's own tests
    on the CPU: the line then carries no device metric a reader would
    take for a chip's (the platform is in it)."""
    from chipbench import checks, trace_reduce
    from chipbench.cell import load_cell

    cell = load_cell(workload, root)
    place_caches(root)
    device = device_info(cell.chips, require_chip)
    compiles = CompileCounter()
    trace_dir = os.path.join(root, ".chipbench_trace", workload)
    tracer = Tracer(trace, trace_dir, cell.traffic.get("trace", {}),
                    compiles)
    driver = load_driver(cell.kind)
    record = driver.run(cell, seed, seconds, tracer)
    setup_s = record["t_window_start"] - _T_PROCESS
    record["counters"]["compiles_in_window"] = compiles.count
    device["memory_peak_bytes"] = memory_peak_bytes()
    print(f"window closed: {record['end_to_end']} setup_s={setup_s:.3f} "
          f"peak={device['memory_peak_bytes']}", file=stderr, flush=True)
    # Only now the reference: the program's state is gone, the peak read.
    verdict = driver.check(cell, seed, record)
    numbers = verdict["numbers"]
    correct = checks.verdict(numbers)
    e2e = dict(record["end_to_end"], setup_s=setup_s)
    result = {"correct": correct, "attempted": record["attempted"],
              "failed": record["failed"]}
    # What a reader has to say beside its number (the counts it divided,
    # why it left the number out) goes into the line's ``notes``.
    reader_notes: dict = {}
    if not trace:
        result["metrics"] = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in cell.metrics("end_to_end")}
    else:
        reduced = trace_reduce.reduce_trace(
            trace_reduce.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        # The traced window by the host's clock, or the span of the
        # device's own events where that is longer (an operation that was
        # running when the trace stopped is recorded to its end).
        traced_s = max(tracer.t_stop - tracer.t_start, reduced["span_s"])
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = traced_s
        run = {"cell": cell, "record": record, "trace": reduced,
               "traced_s": traced_s, "end_to_end": e2e, "device": device,
               "notes": reader_notes}
        metrics = {}
        for m in cell.metrics("per_layer"):
            value = load_metric_reader(m["name"], cell.bench_dir)(run, m)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        result["metrics"] = metrics
        result["breakdown"] = {
            "device_ops": trace_reduce.top_ops(reduced),
            "idle_gaps": [[n, s] for n, s in reduced["gaps"]]}
    result["device"] = device
    result["window_s"] = record["window_s"]
    result["notes"] = {**verdict["notes"], **record["counters"],
                       **reader_notes}
    result["checked"] = numbers
    for name, n in numbers.items():
        print(f"checked {name}: value {n['value']!r} limit {n['limit']!r}",
              file=stderr)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
