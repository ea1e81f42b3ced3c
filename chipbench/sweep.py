"""Sweep of offered rates, to find a cell's knee once.

    python3 chipbench/sweep.py --workload <cell> --rates 1.5,2,2.5 --seconds 20 --seed 1

For each rate, one window of the cell's traffic at that rate (everything
else as in the mix), then one JSON line: requests due and sent, median and
90th-percentile latency from when each was due, output tokens per second
over the window, and the mean service seconds.  Below the knee the
latency stays near the service time and every due request is sent inside
the window; above it the backlog grows and the tail climbs with the
window.  Cells are then fixed at about four fifths of the knee (tails)
and above it (throughput); the benchmark itself never searches for a rate.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    here = pathlib.Path(__file__).resolve().parent
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
        p for p in sys.path if pathlib.Path(p or ".").resolve() != here]

    from repro import compile_cache

    compile_cache.enable()
    import numpy as np

    from chipbench import harness, readers

    base = harness.load_cell(args.workload)
    for rate in (float(r) for r in args.rates.split(",")):
        cell = dataclasses.replace(base, mix=dict(
            base.mix, rate_per_s=rate, drain=False))
        served = harness.serve(cell, args.seed, args.seconds, False,
                               t_process=time.time())
        served.weights = None
        reqs = served.requests
        sent = [r for r in reqs if math.isfinite(r.sent)]
        run = harness.Run(cell, args.seed, args.seconds, "", reqs, 0.0)
        lat = readers.latencies(run)
        print(json.dumps({
            "workload": args.workload, "rate_per_s": rate,
            "due": len(reqs), "sent": len(sent),
            "latency_p50_s": readers.percentile(lat, 50),
            "latency_p90_s": readers.percentile(lat, 90),
            "tokens_per_s": harness.read_metric("tokens_per_s", run),
            "service_s": float(np.mean([r.done - r.sent for r in sent]))}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
