"""Render the EXPERIMENTS.md tables from the sweep JSONs.

Modes:
  dryrun              the launch-plane dry-run sweep (dryrun_results.json)
  scenarios PATH      rows written by ``python -m repro.experiments
                      run/sweep --json PATH`` — the scenario registry's
                      machine-readable output (no stdout scraping)
  bench PATH          rows written by ``python -m benchmarks.run --json``
"""
import json
import sys


def load(path):
    with open(path) as f:
        return json.load(f)


def dryrun_table(recs, mesh):
    rows = [r for r in recs if r["mesh"] == mesh]
    out = [f"| arch | shape | status | compile_s | peak GiB/dev | HLO GFLOP/dev | coll GiB/dev |",
           "|---|---|---|---|---|---|---|"]
    for r in rows:
        if r["status"] == "ok":
            peak = r["bytes_per_device"]["peak"] / 2**30
            out.append(
                f"| {r['arch']} | {r['shape']} | ok | {r['compile_s']:.1f} | "
                f"{peak:.2f} | {r['hlo_flops'] / 1e9:.1f} | "
                f"{r['collective_bytes_total'] / 2**30:.2f} |")
        else:
            out.append(f"| {r['arch']} | {r['shape']} | {r['status']} | — | — | — | — |")
    return "\n".join(out)


def scenario_table(recs):
    """Markdown table from experiments-CLI JSON rows (run or sweep)."""
    out = ["| scenario | driver | p50 ms | p95 ms | p99 ms | cold % | "
           "idle GB-s | cost $ |",
           "|---|---|---|---|---|---|---|---|"]
    for r in recs:
        if "compare" in r:
            a, b = r["compare"]
            verdict = ("identical" if r["identical"]
                       else "DRIFT: " + ", ".join(r["drift"]))
            out.append(f"| {r['scenario']['name']} | {a} vs {b} | "
                       f"{verdict} | | | | | |")
            continue
        s = r["summary"]
        out.append(
            f"| {r['scenario']['name']} | {r['driver']} | "
            f"{s['latency_p50_s'] * 1e3:.1f} | {s['latency_p95_s'] * 1e3:.1f} | "
            f"{s['latency_p99_s'] * 1e3:.1f} | "
            f"{s['cold_start_frequency'] * 100:.2f} | "
            f"{s['idle_gb_s']:.1f} | {s['cost_usd']:.4f} |")
    return "\n".join(out)


def bench_table(recs):
    """Markdown table from ``python -m benchmarks.run --json`` rows."""
    out = ["| name | value | units | derived |", "|---|---|---|---|"]
    for r in recs:
        out.append(f"| {r['name']} | {r['value']:.1f} | {r['units']} | "
                   f"{r['derived']} |")
    return "\n".join(out)


if __name__ == "__main__":
    which = sys.argv[1]
    if which == "scenarios":
        recs = load(sys.argv[2] if len(sys.argv) > 2
                    else "experiments_results.json")
        print(scenario_table(recs))
    elif which == "bench":
        recs = load(sys.argv[2] if len(sys.argv) > 2
                    else "bench_results.json")
        print(bench_table(recs))
    elif which == "dryrun":
        recs = load("dryrun_results.json")
        print("### single pod (16×16 = 256 chips)\n")
        print(dryrun_table(recs, "16x16"))
        print("\n### multi-pod (2×16×16 = 512 chips)\n")
        print(dryrun_table(recs, "2x16x16"))
