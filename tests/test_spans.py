"""The program's spans (``repro.spans``): the tree one routed request
leaves, the numbers built from it, the ring, the profiler's trace, and the
device clock offset read from a recorded TPU trace."""
import collections
import glob
import json
import pathlib
from collections import Counter

import jax
import numpy as np
import pytest

from repro import spans
from repro.core.lifecycle import Phase
from repro.serving.engine import SnapshotStore
from repro.serving.router import FunctionDef, ServerlessRouter

STEPS = 16
TRACE = (pathlib.Path(__file__).resolve().parents[1] / "chipbench" / "tests"
         / "data" / "smoke_tpu.xplane.pb")


def _serve(router, n):
    """``n`` requests through the router; their results and span trees."""
    spans.reset()
    results = [router.invoke("f", np.ones((1, 16), np.int32))
               for _ in range(n)]
    recs = spans.records()
    roots = [s for s in recs if s.name == "router.request"]
    trees = [[s for s in recs if s.request == r.id] for r in roots]
    return results, trees


@pytest.fixture(scope="module")
def router():
    r = ServerlessRouter(ttl_s=1e6, use_snapshots=False)
    r.register(FunctionDef(name="f", arch="xlstm-125m", max_seq=16,
                           decode_steps=STEPS))
    return r


@pytest.fixture(scope="module")
def served(router):
    """A cold request, then a warm one."""
    return _serve(router, 2)


def _children(tree, parent):
    return Counter(s.name for s in tree if s.parent == parent.id)


def _one(tree, name):
    (s,) = [s for s in tree if s.name == name]
    return s


def test_request_tree(served):
    (_, cold_rec), (_, warm_rec) = served[0]
    cold, warm = served[1]
    assert cold_rec.cold and not warm_rec.cold
    for tree, is_cold in ((cold, True), (warm, False)):
        root = tree[-1]                      # the root closes last
        assert root.name == "router.request" and root.parent == 0
        assert root.attrs == {"function": "f", "cold": is_cold}
        assert {s.request for s in tree} == {root.id}
        ids = {s.id for s in tree}
        assert all(s.parent in ids for s in tree if s is not root)
        want = Counter({"router.route": 1, "engine.run": 1, "router.settle": 1})
        if is_cold:
            want["pool.start"] = 1
        assert _children(tree, root) == want
        run = _one(tree, "engine.run")
        assert run.attrs == {"prompt_tokens": 16, "decode_steps": STEPS}
        assert _children(tree, run) == Counter({
            "engine.upload": 1, "engine.prefill_run": 1, "engine.decode": 1,
            "engine.logits_fetch": 1})
        assert _children(tree, _one(tree, "engine.decode")) == Counter({
            "engine.token_fetch": STEPS, "engine.step_dispatch": STEPS,
            "engine.final_wait": 1})
        # a child lies inside its parent
        by_id = {s.id: s for s in tree}
        for s in tree:
            if s is not root:
                p = by_id[s.parent]
                assert p.start_ns <= s.start_ns <= s.end_ns <= p.end_ns


def test_cold_start_tree(served):
    cold = served[1][0]
    start = _one(cold, "engine.start")
    assert start.parent == _one(cold, "pool.start").id
    assert _children(cold, start) == Counter({
        "engine.start.build": 1, "engine.start.weights": 1,
        "engine.start.compile": 1})
    assert _children(cold, _one(cold, "engine.start.weights")) == Counter({
        "engine.start.weights.compile": 1, "engine.start.weights.run": 1})
    compile_ = _one(cold, "engine.start.compile")
    assert compile_.attrs == {"executable_hit": False, "cache_hits": 0,
                              "cache_misses": 0}


def test_serve_stats_and_breakdown_are_span_durations(router, served):
    cold = served[1][0]
    rec = served[0][0][1]
    assert rec.startup.seconds == {
        Phase.PROVISION: 0.0,
        Phase.RUNTIME_INIT: _one(cold, "engine.start.build").seconds,
        Phase.DEPS_LOAD: _one(cold, "engine.start.weights").seconds,
        Phase.CODE_INIT: _one(cold, "engine.start.compile").seconds}
    replica = next(iter(router.pool.replicas.values()))
    spans.reset()
    out, stats = replica.engine.serve(np.ones((1, 16), np.int32),
                                      decode_steps=STEPS)
    tree = spans.records()
    assert out.shape == (1, STEPS) and stats.tokens == STEPS
    assert stats.prefill_s == _one(tree, "engine.prefill_run").seconds
    assert stats.decode_s == _one(tree, "engine.decode").seconds
    assert stats.run_s == _one(tree, "engine.run").seconds
    # served alone, the engine's run is its own root
    assert {s.request for s in tree} == {_one(tree, "engine.run").id}


def test_snapshot_start_reads_then_puts(tmp_path):
    r = ServerlessRouter(ttl_s=0.0, use_snapshots=True,
                         store=SnapshotStore(str(tmp_path)))
    r.register(FunctionDef(name="f", arch="xlstm-125m", max_seq=16,
                           decode_steps=2))
    _, trees = _serve(r, 2)          # TTL 0: both requests start a replica
    weights = [_one(t, "engine.start.weights") for t in trees]
    assert [_children(t, w) for t, w in zip(trees, weights)] == [
        Counter({"engine.start.weights.compile": 1,
                 "engine.start.weights.run": 1}),
        Counter({"engine.start.weights.read": 1,
                 "engine.start.weights.put": 1})]
    assert [t for t in trees[0] if t.name == "engine.start.save"]
    assert _one(trees[1], "engine.start.compile").attrs["executable_hit"]


def test_ring_is_bounded(monkeypatch):
    monkeypatch.setattr(spans, "_ring", collections.deque(maxlen=8))
    monkeypatch.setattr(spans, "_totals", {})
    for i in range(20):
        with spans.span("engine.upload", i=i):
            pass
    recs = spans.records()
    assert [s.attrs["i"] for s in recs] == list(range(12, 20))
    assert spans.totals()["engine.upload"][0] == 20


def test_no_program_span_takes_a_harness_name(served):
    from chipbench import tracing

    assert not spans.NAMES & set(tracing.SPANS)
    recorded = {s.name for tree in served[1] for s in tree}
    assert recorded <= spans.NAMES
    assert set(spans.WAITS) <= spans.NAMES


def test_spans_in_the_profiler_trace(router, tmp_path):
    router.invoke("f", np.ones((1, 16), np.int32))      # warm, compiled
    spans.reset()
    with jax.profiler.trace(str(tmp_path)):
        router.invoke("f", np.ones((1, 16), np.int32))
    recorded = Counter(s.name for s in spans.records())
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    from jax.profiler import ProfileData

    traced = Counter(
        ev.name for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:") for line in plane.lines
        for ev in line.events if ev.name in spans.NAMES)
    assert traced == recorded
    assert traced["engine.step_dispatch"] == STEPS


def test_device_offset_of_the_recorded_trace():
    off = spans.device_offset_ns(str(TRACE))
    assert off.linked >= 200 and off.linked <= off.modules
    assert 1.3e6 <= off.ns <= 1.5e6


def test_idle_attributed_to_the_innermost_span():
    run = (0, 100, "engine.run")
    inner = [(10, 40, "engine.decode"), (20, 30, "engine.token_fetch"),
             (200, 300, "engine.run")]
    busy = [(5, 15), (25, 50), (90, 95), (250, 260)]
    got = spans.idle_by_span(busy, [run] + inner)
    # run 0-100: idle 0-5, 15-25, 50-90, 95-100; second run 200-300:
    # idle 200-250, 260-300
    assert got == {"engine.run": 5 + 40 + 5 + 50 + 40,
                   "engine.decode": 5, "engine.token_fetch": 5}


def _drifting(offsets):
    """Requests of one decode step, each run 100 ns long and 200 apart,
    whose device clock reads its host time less ``offsets[k]``; on the
    device's clock each run is idle 10 in itself, 30 in the token fetch and
    20 in the dispatch, then its program runs 35 and the run is idle 5
    more.  The router settles for 30 after each run."""
    host, links, busy = [], [], []
    for k, off in enumerate(offsets):
        t = 200 * k
        host += [(t, t + 100, "engine.run"),
                 (t + 10, t + 40, "engine.token_fetch"),
                 (t + 40, t + 60, "engine.step_dispatch"),
                 (t + 100, t + 130, "router.settle")]
        links.append((t + 60, t + 60 - off))        # starts at its enqueue
        busy.append((t + 60 - off, t + 95 - off))
    return host, links, busy


def test_offset_is_taken_per_run():
    host, links, _ = _drifting([150, 133, 133])
    assert spans.lead_ns(links) == 150
    assert spans.lead_ns(links, 200, 300) == 133
    assert spans.lead_ns(links, 100, 200) is None
    moved, offsets = spans.device_clock(host, links)
    assert offsets == [150, 133, 133]
    assert moved == [(s - o, e - o, n) for (s, e, n), o in
                     zip(host, [150] * 4 + [133] * 8)]


def test_idle_split_holds_when_the_offset_drifts():
    """One offset for the whole trace, its largest lead, moves the later
    runs too far and misattributes their idle; per run it is exact."""
    host, links, busy = _drifting([150, 133, 133])
    truth = {"engine.run": 3 * 15, "engine.token_fetch": 3 * 30,
             "engine.step_dispatch": 3 * 20}
    moved, _ = spans.device_clock(host, links)
    assert spans.idle_by_span(busy, moved) == truth
    whole = [(s - 150, e - 150, n) for s, e, n in host]
    assert spans.idle_by_span(busy, whole) != truth


def test_a_run_that_enqueued_nothing_takes_the_whole_offset():
    host, links, _ = _drifting([150, 133])
    host.append((1000, 1100, "engine.run"))
    moved, offsets = spans.device_clock(host, links)
    assert offsets == [150, 133, 150]
    # between runs, an end moves with the nearer run
    assert spans.device_clock(host + [(500, 800, "router.request")],
                              links)[0][-1] == (500 - 133, 800 - 150,
                                                "router.request")


def test_command_line_reads_the_recorded_trace(capsys):
    assert spans.main([str(TRACE)]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["linked"] >= 200 and 1.3 <= out["offset_ms"] <= 1.5
    # recorded before the program had spans: nothing to attribute
    assert out["engine_runs"] == 0 and out["idle_by_span_s"] == {}
    assert spans.main([]) == 2
