"""Benchmark harness — one module per paper table/figure (DESIGN.md §5).

Prints ``name,value,derived,units`` CSV (the first three columns keep the
historical ``name,us_per_call,derived`` layout; ``units`` is appended so
values no longer need ``* 1e8``-style scale hacks — rows default to
``units="us"``).  Modules:
  bench_factors    RQ2 / Fig.10+12: measured cold-start anatomy & factors
  bench_qos        RQ1 / Fig.11: QoS impact of cold starts
  bench_csl        Table 4: latency-reduction techniques (real, measured)
  bench_csf        Table 5: frequency-reduction techniques (simulated)
  bench_tradeoffs  §6: energy/accuracy Pareto + predictor study
  bench_serving    serving microbenchmarks + compile-time (scan vs unroll)
  bench_fleet      fleet replay: predictive autoscaling vs fixed TTL + the
                   sim-vs-fleet calibration loop (virtual clock)
  bench_tiers      warmth-tier ladder Pareto sweep: graded demotion
                   schedules vs binary fixed-TTL keep-alive
  bench_simcore    simulator replay throughput (events/sec vs function
                   count; writes BENCH_simcore.json — the perf trajectory)
  bench_batchsim   batch-vs-scalar sweep throughput: the vectorized-grid
                   50x gate on a dense 64-cell grid + the batch-vs-sim
                   tolerance spot-check (writes BENCH_batchsim.json)
  bench_learn      learned predictors: trained transformer forecaster vs
                   histogram Pareto gate + DQN keep-alive schedule vs
                   fixed TTL (writes BENCH_learn.json)
  bench_topology   edge–cloud offloading Pareto sweep: greedy/probabilistic
                   routing vs always_local/always_cloud baselines
                   (writes BENCH_topology.json)

The simulated modules are thin declarations over the scenario registry
(``repro.experiments``); run any cell directly with
``python -m repro.experiments run/sweep``.

CLI:
  python -m benchmarks.run [--list] [--only MODULE]... [--json PATH] [MODULE]

Exits nonzero when any module raises (its row is tagged ERROR), so CI and
scripts can gate on the whole harness.
"""
import argparse
import json
import sys
import time
import traceback

from benchmarks import (bench_batchsim, bench_csf, bench_csl, bench_factors,
                        bench_fleet, bench_learn, bench_platforms, bench_qos,
                        bench_serving, bench_simcore, bench_tiers,
                        bench_topology, bench_tradeoffs)
from benchmarks.emit import csv_emit

MODULES = [
    ("factors", bench_factors),
    ("qos", bench_qos),
    ("csl", bench_csl),
    ("csf", bench_csf),
    ("tradeoffs", bench_tradeoffs),
    ("platforms", bench_platforms),
    ("serving", bench_serving),
    ("fleet", bench_fleet),
    ("tiers", bench_tiers),
    ("simcore", bench_simcore),
    ("batchsim", bench_batchsim),
    ("learn", bench_learn),
    ("topology", bench_topology),
]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m benchmarks.run")
    ap.add_argument("module", nargs="?", default=None,
                    help="run only this module (positional back-compat)")
    ap.add_argument("--list", action="store_true", dest="list_modules",
                    help="print module names and exit")
    ap.add_argument("--only", action="append", default=[], metavar="MODULE",
                    help="run only the named module(s); repeatable")
    ap.add_argument("--json", metavar="PATH",
                    help="also write every row as a JSON list")
    ap.add_argument("--budget-s", type=float, default=None, metavar="SECONDS",
                    help="fail if any single module's wall time exceeds this "
                         "(guards CI duration against e.g. a ballooning "
                         "stress tier)")
    args = ap.parse_args(argv)

    if args.list_modules:
        for name, mod in MODULES:
            doc = (mod.__doc__ or "").strip().splitlines()[0]
            print(f"{name:12s} {doc}")
        return 0

    only = set(args.only)
    if args.module:
        only.add(args.module)
    known = {name for name, _ in MODULES}
    if only - known:
        print(f"unknown module(s): {', '.join(sorted(only - known))} "
              f"(try --list)", file=sys.stderr)
        return 2

    rows = []
    print("name,value,derived,units")

    def emit(name: str, value: float, derived: str = "", *,
             units: str = "us"):
        csv_emit(name, value, derived, units=units)
        rows.append({"name": name, "value": value, "units": units,
                     "derived": derived})

    failed = []
    walls = {}
    for name, mod in MODULES:
        if only and name not in only:
            continue
        t0 = time.perf_counter()
        try:
            mod.run(emit)
            walls[name] = time.perf_counter() - t0
            emit(f"_module/{name}/wall", walls[name] * 1e6, "ok")
        except Exception:
            traceback.print_exc()
            walls[name] = time.perf_counter() - t0
            emit(f"_module/{name}/wall", walls[name] * 1e6, "ERROR")
            failed.append(name)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=1)
    # per-module wall summary (slowest first) — the CI-duration ledger
    for name in sorted(walls, key=walls.get, reverse=True):
        print(f"module wall: {name:12s} {walls[name]:8.2f}s", file=sys.stderr)
    if args.budget_s is not None:
        over = {n: w for n, w in walls.items() if w > args.budget_s}
        for n, w in over.items():
            print(f"FAIL: module {n} took {w:.1f}s, over the "
                  f"--budget-s {args.budget_s:.0f}s per-module cap",
                  file=sys.stderr)
            if n not in failed:
                failed.append(n)
    if failed:
        print(f"FAILED modules: {', '.join(failed)}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    sys.exit(main())
