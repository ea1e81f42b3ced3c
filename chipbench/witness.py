"""Witness of the decode-cache fault: served tokens against two readings.

    python3 chipbench/witness.py --config granite-3-2b --prompt 512 --seeds 1,2,3

Serves ``--requests`` requests of ``--prompt`` tokens and 16 greedy tokens
of the configuration file ``configs/<config>.json`` through the router,
with the benchmark's weights, as a cell's window does, then reads two
witnesses that share no cache with the served path:

  reference  the plain float32 reference of the configuration: the
             benchmark's ``logit_gap`` and ``logit_err`` (``check.py``);
  forward    the program's own full forward pass (``lm.lm_forward``) over
             the prompt and the served tokens, without a decode cache: the
             widest served-token gap under its logits, and its largest
             difference from the reference's.

A sound served path reads about the rounding of its precision under both;
a path that loses decode keys and values reads well above it under both.
One JSON line per seed.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
HERE = pathlib.Path(__file__).resolve().parent


def load_model(name: str) -> dict:
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--prompt", type=int, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--requests", type=int, default=4)
    args = ap.parse_args(argv)
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
        p for p in sys.path if pathlib.Path(p or ".").resolve() != HERE]

    from repro import compile_cache

    compile_cache.enable()
    import jax
    import numpy as np

    from chipbench import check, harness
    from repro.models import lm

    model = load_model(args.config)
    cfg = harness.program_config(model)
    mix = {"loop": "open", "rate_per_s": 1.0, "schedule_seed": 1,
           "prompt_tokens": args.prompt, "output_tokens": 16, "ttl_s": 1e6,
           "drain": True}
    cell = harness.Cell(f"{args.config}.witness", args.config, "witness", 1,
                        model, mix, [])
    forward = jax.jit(lambda p, t: lm.lm_forward(
        p, cfg, {"tokens": t}, window=cfg.sliding_window)[0][0])
    for seed in (int(s) for s in args.seeds.split(",")):
        served = harness.serve(cell, seed, float(args.requests), False,
                               t_process=time.time())
        ref, fwd_gap, diff = [], [], []
        for r in harness.sent(served.requests)[: args.requests]:
            ref.append(check.readings(served.weights, model, r.prompt[0],
                                      r.tokens, r.logits))
            seq = np.concatenate([r.prompt[0], r.tokens[:-1]]).astype(np.int32)
            rows = np.arange(args.prompt - 1, len(seq))
            want = check.reference(model).logits_at(served.weights, model,
                                                    seq, rows)
            got = np.asarray(forward(served.weights, seq[None]))[rows]
            fwd_gap.append(float(check.token_gaps(got, r.tokens).max()))
            diff.append(float(np.abs(got - want).max()))
        print(json.dumps({
            "config": args.config, "prompt": args.prompt, "seed": seed,
            "device": jax.devices()[0].device_kind,
            "logit_gap": max(g for g, _ in ref),
            "logit_err": max(e for _, e in ref),
            "forward_gap_max": max(fwd_gap),
            "forward_vs_reference_max_abs": max(diff),
            "each": ref}), flush=True)
        served.weights = None
    return 0


if __name__ == "__main__":
    sys.exit(main())
