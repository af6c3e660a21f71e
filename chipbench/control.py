"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 chipbench/control.py --workload <cell> --seeds 12 \\
        --control-seeds 3 --seconds 15 --first-seed 1000003

One process, one set-up: for each seed the cell's driver runs the timed
path at the cell's own size and load for a short window (the trainer with
its compiled step, or the server with its warmed programs, is kept from
seed to seed), and the reference is compared with what it produced: the
LOWER reading of each number is the largest over these seeds. For the
first ``--control-seeds`` seeds the control (the reference in the next
precision down, in the program's place) and the faults that need a run
are read too: the UPPER reading is the smallest of those. The benchmark's
own runs never run this. Prints one JSON line per seed and a summary.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--first-seed", type=int, default=1000003)
    args = ap.parse_args(argv)

    from chipbench import run
    from chipbench.cell import load_cell

    cell = load_cell(args.workload)
    run.place_caches(_ROOT)
    device = run.device_info(cell.chips, require_chip=True)
    tracer = run.Tracer(False, "", {}, run.CompileCounter())
    driver = run.load_driver(cell.kind)
    session: dict = {}
    lower: dict = {}
    upper: dict = {}
    try:
        for i in range(args.seeds):
            seed = args.first_seed + 7919 * i
            record = driver.run(cell, seed, args.seconds, tracer, session)
            if hasattr(driver, "release"):
                driver.release(session)
            gc.collect()
            if i < args.control_seeds:
                readings = driver.control_readings(cell, seed, record)
            else:
                readings = {"program": driver.check(cell, seed, record)}
                readings["program"] = {
                    **{k: n["value"] for k, n in
                       readings["program"]["numbers"].items()},
                    **readings["program"]["notes"].get("uncompared", {})}
            for who, values in readings.items():
                for name, v in values.items():
                    if who == "program":
                        lower[name] = max(lower.get(name, 0.0), v)
                    else:
                        key = f"{who}.{name}"
                        upper[key] = min(upper.get(key, float("inf")), v)
            print(json.dumps({"seed": seed, "readings": readings,
                              "e2e": record["end_to_end"]}), flush=True)
    finally:
        if "server" in session:
            session["server"].stop()
    print(json.dumps({"workload": args.workload, "device": device,
                      "seeds": args.seeds, "lower": lower, "upper": upper}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
