"""Readings that set a cell's limits: the program, its control, and faults.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 --seconds 12 [--control] [--fault F]

For each seed, in one process: a run of the cell's window at its own load
for ``--seconds`` (long enough to serve as many requests as a run
compares), then the comparison that decides a run's ``correct``
(``harness.compare``) on the sample a run draws:

  program  the served tokens and last logits under the float32 reference,
           as a benchmark run reads them;
  control  with ``--control``, the reference computed with float8 operands
           (the next precision below the configuration's bfloat16) in the
           program's place: at each served position the token it puts
           first, and its last logits;
  fault    with ``--fault``, the program with that fault planted in its
           decode step (``FAULTS``); its readings are the program's.

Each number is printed with its limit and whether it holds, and
``correct`` as a run would report it.  The limit lies above the program's
largest reading over a dozen seeds or more and below the smallest reading
of the control and of each fault.  One JSON line per seed.  The
benchmark's own runs never run any of this.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
FAULTS = ("state_unchanged", "token_altered")


@contextlib.contextmanager
def planted(fault: str):
    """The program's decode step, broken: it returns the cache it was given
    (``state_unchanged``), or it puts token 7 first at every step
    (``token_altered``)."""
    from repro.models import lm

    decode = lm.lm_decode_step

    def broken(p, cfg, caches, token, pos, *, window=None):
        logits, new = decode(p, cfg, caches, token, pos, window=window)
        if fault == "state_unchanged":
            return logits, caches
        return logits.at[:, 7].add(1e4), new

    lm.lm_decode_step = broken
    try:
        yield
    finally:
        lm.lm_decode_step = decode


def _reading(checks) -> dict:
    out = {c.name: c.value for c in checks}
    out["correct"] = all(c.ok for c in checks)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", choices=FAULTS)
    args = ap.parse_args(argv)
    here = pathlib.Path(__file__).resolve().parent
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
        p for p in sys.path if pathlib.Path(p or ".").resolve() != here]

    from repro import compile_cache

    compile_cache.enable()
    from chipbench import harness

    cell = harness.load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        with planted(args.fault) if args.fault else contextlib.nullcontext():
            served = harness.serve(cell, seed, args.seconds, False,
                                   t_process=time.time())
        program = harness.compare(cell, seed, served)
        line = {"workload": args.workload, "seed": seed, "fault": args.fault,
                "sent": len(harness.sent(served.requests)),
                "limits": {c.name: c.limit for c in program},
                "program": _reading(program)}
        if args.control:
            line["control"] = _reading(
                harness.compare(cell, seed, served, fp8=True))
        served.weights = None
        gc.collect()
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
