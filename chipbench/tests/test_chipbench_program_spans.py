"""The readers of the program's spans (``program_spans`` and the four
metrics on it), on the spans a run of the cell's driving code records at
smoke size on the CPU."""
import math
import time

import pytest

from chipbench import harness, program_spans
from chipbench.tests.test_chipbench_cells import SEED, WORKLOADS, smoke_cell

METRICS = ("decode_dispatch_ms", "device_waits_per_token", "router_ms",
           "engine_start_s")


@pytest.fixture(scope="module")
def run():
    from repro import spans

    spans.reset()
    cell = smoke_cell(WORKLOADS[0])
    t_process = time.time()
    served = harness.serve(cell, SEED, 1.5, False, t_process=t_process)
    return harness.Run(cell, SEED, 1.5, "cpu", served.requests,
                       served.setup_s)


def _window_trees(run):
    """The window's requests rebuilt from the records here, the warm-up
    requests skipped by count."""
    from repro import spans

    recs = spans.records()
    roots = [s for s in recs if s.name == "router.request"]
    assert len(roots) == harness.WARMUP_REQUESTS + len(harness.sent(
        run.requests))
    return [[s for s in recs if s.request == r.id]
            for r in roots[harness.WARMUP_REQUESTS:]]


@pytest.mark.parametrize("name", METRICS)
def test_metric_is_in_the_cell(name):
    assert name in {m.name for m in harness.load_cell(WORKLOADS[0]).metrics}


def test_waits_per_token_is_exact(run):
    steps = run.cell.mix["output_tokens"]
    assert harness.read_metric("device_waits_per_token", run) == (
        (steps + 3) / steps)


def test_decode_dispatch_is_the_mean_step_dispatch(run):
    trees = _window_trees(run)
    steps = [s.seconds for t in trees for s in t
             if s.name == "engine.step_dispatch"]
    assert len(steps) == len(trees) * run.cell.mix["output_tokens"]
    got = harness.read_metric("decode_dispatch_ms", run)
    assert got == pytest.approx(1e3 * sum(steps) / len(steps), rel=1e-12)
    assert got > 0


def test_router_time_excludes_engine_and_start(run):
    got = harness.read_metric("router_ms", run)
    trees = _window_trees(run)
    own = []
    for t in trees:
        root = t[-1]
        own.append(root.seconds - sum(
            s.seconds for s in t
            if s.parent == root.id and s.name in ("engine.run", "pool.start")))
    assert got == pytest.approx(1e3 * sum(own) / len(own), rel=1e-12)
    mean_latency = sum(r.done - r.sent for r in run.served) / len(run.served)
    assert 0 < got < 1e3 * mean_latency


def test_engine_start_is_inside_setup(run):
    from repro import spans

    (start,) = [s for s in spans.records() if s.name == "engine.start"]
    got = harness.read_metric("engine_start_s", run)
    assert got == start.seconds
    assert 0 < got < run.setup_s


def test_engine_start_leaves_out_earlier_runs(run, monkeypatch):
    """An engine started by an earlier run in the same process, before this
    run's warm-up requests, is not part of this run's set-up."""
    from repro import spans

    recs = spans.records()
    earlier = []
    for name in ("engine.start", "router.request"):
        s = spans.Span(name, {})
        s.id = s.request = -1
        s.parent, s.start_ns, s.end_ns = 0, 0, 10 ** 9
        earlier.append(s)
    earlier[0].parent = -1
    monkeypatch.setattr(program_spans, "records", lambda: earlier + recs)
    (start,) = [s for s in recs if s.name == "engine.start"]
    assert harness.read_metric("engine_start_s", run) == start.seconds


@pytest.mark.parametrize("name", METRICS)
def test_nothing_to_read_without_program_spans(name, run, monkeypatch):
    """A program without ``repro.spans`` (or with an empty record) gives
    no value, and no error."""
    monkeypatch.setattr(program_spans, "records", lambda: [])
    assert harness.read_metric(name, run) is None


@pytest.mark.parametrize("name", METRICS)
def test_nothing_to_read_when_the_ring_lost_the_window(name, run,
                                                      monkeypatch):
    from repro import spans

    recs = spans.records()
    first = _window_trees(run)[0][0]
    monkeypatch.setattr(program_spans, "records",
                        lambda: recs[recs.index(first) + 1:])
    assert harness.read_metric(name, run) is None


def test_requests_not_sent_are_not_counted(run):
    unsent = [harness.Request(10 ** 6, 0.0, None)]
    assert not math.isfinite(unsent[0].sent)
    more = harness.Run(run.cell, run.seed, run.seconds, run.device_kind,
                       run.requests + unsent, run.setup_s)
    for name in METRICS:
        assert harness.read_metric(name, more) == harness.read_metric(
            name, run)
