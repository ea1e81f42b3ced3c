"""The trace reduction, on synthetic intervals and on a recorded TPU trace.

``data/smoke_tpu.xplane.pb`` is a ``--trace 1`` window of the steady
cell's driving code at smoke size on one TPU v5e: a few requests through
the router, with the harness's host spans.
"""
import pathlib

import pytest

from chipbench import tracing

TRACE = pathlib.Path(__file__).with_name("data") / "smoke_tpu.xplane.pb"


def test_merge_clip_overlap_gaps():
    busy = tracing.merge([(5, 10), (0, 3), (2, 4), (9, 12)])
    assert busy == [(0, 4), (5, 12)]
    assert tracing.clip(busy, 3, 6) == [(3, 4), (5, 6)]
    assert tracing.overlap(busy, [(1, 6), (11, 20)]) == 3 + 1 + 1
    assert tracing.gaps(busy, 0, 15) == [(4, 5), (12, 15)]


def test_self_time_subtracts_nested_ops():
    ops = [(0, 100, "while"), (10, 30, "a"), (40, 60, "b"), (45, 50, "c")]
    got = tracing.self_times(ops, 0, 200)
    assert got == {"while": 60, "a": 20, "b": 15, "c": 5}
    assert sum(tracing.self_times(ops, 20, 55).values()) == 35


@pytest.fixture(scope="module")
def reduced():
    return tracing.reduce(str(TRACE))


def test_busy_union_inside_window(reduced):
    assert 0 < reduced.busy_s < reduced.window_s
    (busy,) = reduced.busy
    lo, hi = reduced.window
    assert all(lo <= s < e <= hi for s, e in busy)
    assert all(a[1] < b[0] for a, b in zip(busy, busy[1:]))
    # self times of all ops add up to the union: no op time counted twice
    assert sum(reduced.ops.values()) == pytest.approx(
        sum(e - s for s, e in busy), rel=1e-6)


def test_programs_by_role(reduced):
    serves = len(reduced.spans["engine.serve"])
    assert serves >= 2
    assert len(reduced.programs["prefill"]) == serves
    # 16 decode calls per request, the last one inside the window
    assert len(reduced.programs["decode_step"]) == 16 * serves
    assert reduced.program_seconds("prefill") > 0
    assert reduced.program_seconds("decode_step") > 0
    assert reduced.programs["prefill"] != reduced.programs["decode_step"]


def test_idle_inside_serve_and_by_host_span(reduced):
    spans = reduced.spans["engine.serve"]
    total = sum(e - s for s, e in spans)
    assert 0 < reduced.busy_within(spans) < total
    idle = sum(reduced.idle.values())
    assert idle == pytest.approx(reduced.window_s * 1e9 - reduced.busy_s * 1e9)
    assert set(reduced.idle) <= set(tracing.SPANS) | {"harness"}
    bd = reduced.breakdown()
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert bd["idle_gaps"][0][1] >= bd["idle_gaps"][-1][1]
