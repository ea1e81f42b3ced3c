"""Each cell's driving code at smoke size on the CPU.

The harness refuses any platform but a TPU; these tests steer that check
(and the compile cache, which tests never turn on) from here, and swap in
the configuration cut to the program's smoke size and a prompt as long as
its window.
Everything else is the run as the chip runs it: router, engine, the
benchmark's weights, the window, the reference comparison, the readers.
"""
import dataclasses
import io
import json
import os
import subprocess
import sys
from contextlib import redirect_stdout

import jax
import pytest

from chipbench import check, control, harness, run

WORKLOADS = [w["name"] for w in json.loads(
    (harness.ROOT / "BENCHMARK.json").read_text())["workloads"]]
SEED = 2 ** 31 + 11


@pytest.fixture(autouse=True)
def _fewer_checked(monkeypatch):
    monkeypatch.setattr(harness, "CHECKED_REQUESTS", 3)


def smoke_model(model: dict) -> dict:
    """The configuration at the program's smoke size (``reduced``: two
    layers, d_model 256, float32, window 64)."""
    from repro.config import reduced

    small = dataclasses.asdict(reduced(harness.program_config(model)))
    keep = ("name", "source")
    return dict(model, **{
        k: small[k] for k in model if k in small and k not in keep})


def smoke_cell(name: str) -> harness.Cell:
    cell = harness.load_cell(name)
    model = smoke_model(cell.model)
    mix = dict(cell.mix, rate_per_s=20.0,
               prompt_tokens=min(cell.mix["prompt_tokens"],
                                 model["sliding_window"] or 64))
    return dataclasses.replace(cell, model=model, mix=mix)


class _AsTPU:
    """The CPU device, reported as a TPU to the harness's device check."""

    platform = "tpu"

    def __init__(self, dev):
        self._dev = dev

    def __getattr__(self, name):
        return getattr(self._dev, name)


@pytest.mark.parametrize("name", WORKLOADS)
def test_cell_runs_through_the_router(name, monkeypatch):
    from repro import compile_cache

    cell = smoke_cell(name)
    real = jax.devices()
    monkeypatch.setattr(jax, "devices", lambda *a: [_AsTPU(d) for d in real])
    monkeypatch.setattr(harness, "load_cell", lambda n: cell)
    monkeypatch.setattr(compile_cache, "enable", lambda: "")
    old = jax.config.jax_persistent_cache_min_compile_time_secs
    out = io.StringIO()
    try:
        with redirect_stdout(out):
            rc = run.main(["--workload", name, "--seed", str(SEED),
                           "--seconds", "1.5", "--trace", "0"])
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", old)
    assert rc == 0
    line = json.loads(out.getvalue().splitlines()[-1])
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 1 and line["failed"] == 0
    e2e = {m.name for m in cell.metrics if m.kind == "end_to_end"}
    assert set(line["metrics"]) == e2e
    assert all(v["value"] > 0 for v in line["metrics"].values())


@pytest.mark.parametrize("fault", control.FAULTS)
def test_broken_timed_path_is_not_correct(fault):
    cell = smoke_cell(WORKLOADS[0])
    with control.planted(fault):
        result, checks = harness.run_cell(cell, SEED, 1.5, False,
                                          t_process=0.0)
    assert result["correct"] is False
    failed = [c for c in checks if not c.ok]
    assert failed and all(c.name.startswith("logit_") for c in failed)
    assert any(c.value > 3 * c.limit for c in failed)


def test_control_reads_above_the_limit():
    """The reference computed in float8 (the next precision below the
    configuration's bfloat16) in the program's place is not correct."""
    cell = smoke_cell(WORKLOADS[0])
    served = harness.serve(cell, SEED, 1.5, False, t_process=0.0)
    program = harness.compare(cell, SEED, served)
    control = harness.compare(cell, SEED, served, fp8=True)
    assert all(c.ok for c in program), program
    assert not all(c.ok for c in control)
    (err,) = [c for c in control if c.name == "logit_err"]
    assert err.value > err.limit


def test_no_tpu_no_result():
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", WORKLOADS[0],
         "--seed", "0", "--seconds", "10", "--trace", "0"],
        cwd=harness.ROOT, capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr
