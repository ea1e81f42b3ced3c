"""The one-off chip tools (control readings, rate sweep, fault witness)
at smoke size on the CPU, with the compile cache left off as in every
test."""
import io
import json
from contextlib import redirect_stdout

import pytest

from chipbench import control, harness, sweep, witness
from chipbench.tests.test_chipbench_cells import (WORKLOADS, smoke_cell,
                                                  smoke_model)


def _lines(main, argv, monkeypatch):
    from repro import compile_cache

    cell = smoke_cell(WORKLOADS[0])
    monkeypatch.setattr(harness, "load_cell", lambda name: cell)
    monkeypatch.setattr(compile_cache, "enable", lambda: "")
    out = io.StringIO()
    with redirect_stdout(out):
        assert main(argv) == 0
    return [json.loads(x) for x in out.getvalue().splitlines()]


def test_control_separates_from_program(monkeypatch):
    (line,) = _lines(control.main, ["--workload", WORKLOADS[0], "--seeds",
                                    "4", "--seconds", "1", "--control"],
                     monkeypatch)
    assert line["sent"] >= 2
    assert line["program"]["correct"] and not line["control"]["correct"]
    assert line["control"]["logit_err"] > 100 * line["program"]["logit_err"]


def test_fault_reads_above_the_program(monkeypatch):
    (line,) = _lines(control.main, ["--workload", WORKLOADS[0], "--seeds",
                                    "4", "--seconds", "1", "--fault",
                                    "token_altered"], monkeypatch)
    assert "control" not in line and not line["program"]["correct"]
    assert line["program"]["logit_gap"] > 1.0


def test_sweep_finds_the_backlog(monkeypatch):
    lines = _lines(sweep.main, ["--workload", WORKLOADS[0], "--rates",
                                "5,500", "--seconds", "1"], monkeypatch)
    low, high = lines
    assert low["sent"] == low["due"] and high["sent"] < high["due"]
    assert high["latency_p90_s"] > low["latency_p90_s"]


@pytest.mark.parametrize("prompt,fault", [(64, False), (32, True)])
def test_witness_reads_the_decode_cache(prompt, fault, monkeypatch):
    real = witness.load_model
    monkeypatch.setattr(witness, "load_model",
                        lambda name: smoke_model(real(name)))
    (line,) = _lines(witness.main, ["--config", "h2o-danube-1.8b",
                                    "--prompt", str(prompt), "--seeds", "1",
                                    "--requests", "2"], monkeypatch)
    # the program's own full forward pass agrees with the reference
    assert line["forward_vs_reference_max_abs"] < 1e-4
    assert (line["logit_gap"] > 0.1) == fault
    assert (line["logit_err"] > 0.1) == fault
