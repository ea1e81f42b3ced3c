"""The reader of ``moe_decode_experts_read``, on the spans a run of the
DeepSeek cell's driving code records at smoke size on the CPU."""
import copy
import dataclasses
import time

import pytest

from chipbench import harness, program_spans
from chipbench.tests.test_chipbench_cells import SEED, smoke_cell

CELL = "deepseek-v2-lite-ep4.doc-qa-8k"
NAME = "moe_decode_experts_read"


@pytest.fixture(scope="module")
def run():
    from repro import spans

    spans.reset()
    cell = smoke_cell(CELL)
    served = harness.serve(cell, SEED, 1.5, False, t_process=time.time())
    # the reader reads a traced run's window; the trace itself is not read
    return harness.Run(cell, SEED, 1.5, "cpu", served.requests,
                       served.setup_s, trace=object())


def test_metric_is_in_the_cell():
    assert NAME in {m.name for m in harness.load_cell(CELL).metrics}


def test_experts_read_per_layer_and_step(run):
    """Sums of the window's ``engine.decode`` counters: at the smoke size
    one MoE layer holds 1 of 4 experts, so a step reads 0 or 1."""
    w = program_spans.window(run)
    decodes = [s.attrs for spans in w.values() for s in spans
               if s.name == "engine.decode"]
    steps = run.cell.mix["output_tokens"]
    assert len(decodes) == len(harness.sent(run.requests))
    assert all(a["moe_decode_layers"] == steps for a in decodes)
    assert all(a["moe_decode_dropped"] == 0 for a in decodes)
    got = harness.read_metric(NAME, run)
    assert got == pytest.approx(
        sum(a["moe_decode_experts_read"] for a in decodes)
        / (steps * len(decodes)), rel=1e-12)
    assert 0 <= got <= 1


def test_nothing_to_read(run, monkeypatch):
    """No trace, no program spans, or a program without the counters (as
    before they were added): no value, and no error."""
    assert harness.read_metric(NAME, dataclasses.replace(run, trace=None)) \
        is None
    bare = [copy.copy(s) for s in program_spans.records()]
    for s in bare:
        s.attrs = {k: v for k, v in s.attrs.items()
                   if not k.startswith("moe_decode_")}
    monkeypatch.setattr(program_spans, "records", lambda: bare)
    assert harness.read_metric(NAME, run) is None
    monkeypatch.setattr(program_spans, "records", lambda: [])
    assert harness.read_metric(NAME, run) is None
