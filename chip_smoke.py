#!/usr/bin/env python3
"""On-chip smoke test: the serving path at granite-3-2b's published widths
and the batch simulator's device program, on one TPU.

    python3 chip_smoke.py

Everything runs in this one process (a chip belongs to one process at a
time).  Phases, in order; any failure raises and exits non-zero:

  device    JAX's first device must be a TPU.  There is no CPU fallback.
  serving   A ServerlessRouter on a snapshot directory made for this run
            serves granite-3-2b at full width (40 layers, d_model 2048,
            bfloat16, max_seq 128, 8 greedy decode steps) through
            router -> EnginePool -> EngineBackend -> InferenceEngine:
            a COLD start (init + compile), a warm hit, and a start after
            scale-to-zero that restores the bfloat16 snapshot.  Each
            request prints its per-phase seconds.  The restored engine
            must produce the cold engine's tokens, and its logits must be
            finite.
  batchsim  The 64-cell batch_dense64 grid under driver="batch" as one
            device program; two sampled cells must agree with the scalar
            simulator within batchsim.spot_check's tolerances.

The last line of standard output, printed only when every phase passed,
is {"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}.
"""
from __future__ import annotations

import json
import math
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "granite-3-2b"
MAX_SEQ = 128
DECODE_STEPS = 8
TTL_S = 5.0          # keep-alive: the warm hit lands inside it, the
                     # scale-to-zero request after it
SEED = 0
SWEEP = "batch_dense64"
SPOT_CELLS = 2


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {msg}")


def device_phase():
    import jax

    dev = jax.devices()[0]
    _check(dev.platform == "tpu",
           f"no TPU: JAX's first device is {dev.platform!r}")
    print(f"[device] {dev.platform} {dev.device_kind} "
          f"x{len(jax.devices())}", flush=True)
    return dev


def _request_line(tag, rec, engine, dev) -> str:
    phases = {p.value: s for p, s in (rec.startup.seconds.items()
                                      if rec.startup else ())}
    stats = dev.memory_stats() or {}
    return (f"[serving] {tag}: {'COLD' if rec.cold else 'warm'} "
            f"path={engine.last_start} "
            f"runtime_init={phases.get('runtime_init', 0.0)!r}s "
            f"deps_load={phases.get('deps_load', 0.0)!r}s "
            f"code_init={phases.get('code_init', 0.0)!r}s "
            f"execute={rec.end - rec.start!r}s "
            f"param_bytes={engine.package_bytes()} "
            f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")


def serving_phase(dev, *, smoke=False):
    """``smoke=True`` serves the reduced config: the CPU rehearsal."""
    import numpy as np

    from repro.models import registry
    from repro.serving.engine import SnapshotStore, StartPath
    from repro.serving.router import FunctionDef, ServerlessRouter

    arch, max_seq, decode_steps, ttl_s = ARCH, MAX_SEQ, DECODE_STEPS, TTL_S
    cfg = registry.build_arch(arch, smoke=smoke, max_seq=max_seq).cfg
    print(f"[serving] {cfg.name}: layers={cfg.num_layers} "
          f"d_model={cfg.d_model} heads={cfg.num_heads}/{cfg.num_kv_heads} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
          f"param_dtype={cfg.param_dtype} max_seq={max_seq} "
          f"decode_steps={decode_steps}", flush=True)
    prompt = np.random.default_rng(SEED).integers(
        0, cfg.vocab_size, (1, max_seq)).astype(np.int32)

    with tempfile.TemporaryDirectory(prefix="chip_smoke_snapshots_") as snap:
        router = ServerlessRouter(ttl_s=ttl_s, use_snapshots=True,
                                  store=SnapshotStore(snap),
                                  memory_budget_gb=16.0)
        router.register(FunctionDef(arch, arch, max_seq=max_seq,
                                    decode_steps=decode_steps, smoke=smoke,
                                    memory_gb=8.0))

        def invoke(tag):
            out, rec = router.invoke(arch, prompt)
            (replica,) = router.pool.replicas.values()
            print(_request_line(tag, rec, replica.engine, dev), flush=True)
            return out, rec, replica.engine

        cold_out, cold, engine = invoke("request 1")
        _check(cold.cold and engine.last_start == StartPath(False, False),
               f"first request was not a full cold start: {engine.last_start}")
        _, warm, _ = invoke("request 2")
        _check(not warm.cold, "second request missed the warm replica")
        time.sleep(ttl_s + 1.0)                      # scale to zero
        restored_out, restored, engine = invoke("request 3")
        _check(restored.cold
               and engine.last_start == StartPath(True, True),
               f"third request did not restore the snapshot: "
               f"{engine.last_start}")
        _check(np.array_equal(restored_out, cold_out),
               f"restored tokens {restored_out.tolist()} != cold tokens "
               f"{cold_out.tolist()}")
        again, stats = engine.serve(prompt, decode_steps=decode_steps)
        _check(np.array_equal(again, cold_out),
               "repeat serve on the restored engine changed the tokens")
        _check(stats.logits.shape == (1, cfg.vocab_size)
               and bool(np.isfinite(stats.logits).all()),
               f"logits of shape {stats.logits.shape} are not all finite")
        print(f"[serving] tokens {cold_out.tolist()} identical after "
              f"restore; logits finite, shape {stats.logits.shape}",
              flush=True)


def batchsim_phase():
    from repro.core import batchsim
    from repro.experiments import registry, runner

    sweep, spot_cells = SWEEP, SPOT_CELLS
    cells = registry.get_sweep(sweep).scenarios()
    t0 = time.perf_counter()
    rows = list(runner.run_sweep(sweep, "batch"))
    wall = time.perf_counter() - t0
    _check(len(rows) == len(cells), f"{len(rows)} of {len(cells)} cells ran")
    for sc, s in rows:
        _check(math.isfinite(s["cold_start_frequency"])
               and math.isfinite(s["idle_gb_s"]) and s["requests"] > 0,
               f"{sc.name}: degenerate summary {s}")
    requests = sum(s["requests"] for _, s in rows)
    print(f"[batchsim] {sweep}: {len(rows)} cells, {requests:.0f} requests, "
          f"driver=batch wall={wall!r}s (compile included)", flush=True)
    sampled = cells[::max(len(cells) // spot_cells, 1)][:spot_cells]
    for r in batchsim.spot_check(sampled, trace_fn=runner.build_trace):
        print(f"[batchsim] spot {r.name}: "
              f"cold_rate sim={float(r.cold_rate_sim)!r} "
              f"batch={float(r.cold_rate_batch)!r} "
              f"idle_gb_s sim={float(r.idle_gb_s_sim)!r} "
              f"batch={float(r.idle_gb_s_batch)!r} "
              f"{'ok' if r.ok else 'FAIL'}", flush=True)
        _check(r.ok, f"{r.name} outside batchsim's spot-check tolerances")


def main() -> int:
    from repro import compile_cache

    print(f"[setup] compile cache: {compile_cache.enable()}", flush=True)
    dev = device_phase()
    serving_phase(dev)
    batchsim_phase()
    import jax

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
