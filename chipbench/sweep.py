"""Find the knee of an open-loop traffic mix, once, on the chip.

    python3 chipbench/sweep.py --workload <cell> --rates 0.3,0.45,0.6,0.8 --seconds 40

One process and one set-up: the server is built and warmed once (a
``session``), then the mix is offered at each rate in turn for
``--seconds``. The cell may be any whose configuration is the one wanted;
``--traffic`` names the open-loop traffic file to use in place of the
cell's own. Prints one JSON line per rate: the requests due, the median
and 95th percentile of the normalised latency, the tokens per second
completed and how late the generator ran. The knee is the highest rate
whose latency does not take off; a cell then offers about four fifths of
it, written into its traffic file as ``rate_per_s``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=1000003)
    args = ap.parse_args(argv)

    from chipbench import run
    from chipbench.cell import _read_json, load_cell
    from chipbench.drivers import serve

    cell = load_cell(args.workload)
    cell.traffic = _read_json(os.path.join(
        _ROOT, "chipbench", "workloads", args.traffic + ".json"))
    cell.traffic_name = args.traffic
    run.place_caches(_ROOT)
    run.device_info(cell.chips, require_chip=True)
    tracer = run.Tracer(False, "", {}, run.CompileCounter())
    session: dict = {}
    try:
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            cell.traffic["rate_per_s"] = rate
            record = serve.run(cell, args.seed + i, args.seconds, tracer,
                               session)
            c = record["counters"]
            print(json.dumps({
                "rate_per_s": rate, "due": record["attempted"],
                "failed": record["failed"],
                "norm_latency_p50": c["norm_latency_p50"],
                "norm_latency_p95":
                    record["end_to_end"]["serve_norm_latency_p95"],
                "completed_tokens_per_s": c["completed_tokens_per_s"],
                "requests_arrived": c["requests_arrived"],
                "generator_lateness": c["generator_lateness"]}), flush=True)
    finally:
        if "server" in session:
            session["server"].stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
