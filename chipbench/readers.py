"""Arithmetic shared by the metric readers in ``metrics/``.

Every reader returns None where its run has nothing to read: no traced
window, no program of that role in the trace, no request served.
"""
from __future__ import annotations

import math
from typing import Optional

import numpy as np

from chipbench import counts


def percentile(values, q: float) -> Optional[float]:
    """Linear-interpolated percentile; None when a failed request (inf)
    falls inside it or there is nothing to read."""
    vals = np.asarray(values, float)
    if vals.size == 0:
        return None
    v = float(np.quantile(vals, q / 100.0))
    return v if math.isfinite(v) else None


def latencies(run):
    """Due-to-done seconds of every request sent; a failed one is inf."""
    return [r.done - r.due if r.tokens is not None else math.inf
            for r in run.requests if math.isfinite(r.sent)]


def mean(values) -> Optional[float]:
    vals = [v for v in values if math.isfinite(v)]
    return sum(vals) / len(vals) if vals else None


def roofline_pct(run, role: str) -> Optional[float]:
    """Least time at peak over measured device time of one call of the
    ``role`` program (prefill or decode_step), in percent."""
    if run.trace is None or not run.trace.programs.get(role):
        return None
    m, mix = run.cell.model, run.cell.mix
    p = mix["prompt_tokens"]
    if role == "prefill":
        ideal = [counts.seconds_at_peak(*counts.prefill(m, p), run.device_kind)[0]]
    else:
        ideal = [counts.seconds_at_peak(*counts.decode(m, p + i), run.device_kind)[0]
                 for i in range(mix["output_tokens"])]
    device = run.trace.program_seconds(role)
    return 100.0 * (sum(ideal) / len(ideal)) / device


def serve_mfu_pct(run) -> Optional[float]:
    """Operations of the traced window's served requests over the summed
    length of their ``engine.serve`` spans, over the bf16 peak, percent."""
    if run.trace is None:
        return None
    spans = run.trace.spans.get("engine.serve", [])
    if not spans:
        return None
    m, mix = run.cell.model, run.cell.mix
    p = mix["prompt_tokens"]
    ops = counts.prefill(m, p)[0] + sum(
        counts.decode(m, p + i)[0] for i in range(mix["output_tokens"]))
    span_s = sum(e - s for s, e in spans) / 1e9
    peak = counts.peaks(run.device_kind)["bf16_flops_per_s"]
    return 100.0 * ops * len(spans) / span_s / peak


def idle_in_serve_pct(run) -> Optional[float]:
    if run.trace is None:
        return None
    spans = run.trace.spans.get("engine.serve", [])
    if not spans:
        return None
    total = sum(e - s for s, e in spans)
    return 100.0 * (1.0 - run.trace.busy_within(spans) / total)


def idle_in_window_pct(run) -> Optional[float]:
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
