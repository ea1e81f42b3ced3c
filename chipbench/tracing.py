"""Host spans, the profiler's trace, and its reduction to device metrics.

A ``--trace 1`` run records its host spans with
``jax.profiler.TraceAnnotation``, so that they sit in the profiler's own
trace on the device's clock, and traces the whole window.  ``reduce``
turns the ``.xplane.pb`` into:

  busy      the union of the intervals in which an operation ran on each
            device (line ``XLA Ops``), inside the window, averaged over
            devices;
  spans     the host spans by name, inside the window;
  programs  device executions (line ``XLA Modules``) by role.  Prefill and
            decode are both jitted lambdas, and the host and device clocks
            of a trace differ by a millisecond or two, so a role's program
            is found by count: of the programs that ran as many times as
            the host dispatched the role (``engine.prefill``,
            ``engine.decode_step`` spans), the one with the most device
            time (the argmax, index and convert programs run as often);
  ops       device self seconds by operation name (an op's own time, less
            the ops nested inside it, as a loop holds its body);
  idle      device idle seconds inside the window by what the host was
            doing: the innermost host span around each idle gap.
"""
from __future__ import annotations

import contextlib
import glob
import os
import shutil
import tempfile
from collections import Counter, defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

SPANS = ("window", "client.wait", "router.invoke", "engine.cold_start",
         "engine.serve", "engine.prefill", "engine.decode_step")
ROLES = {"engine.prefill": "prefill", "engine.decode_step": "decode_step"}
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"

Interval = Tuple[float, float]


class Tracer:
    """Host spans and the profiler, switched on by ``--trace 1`` only."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.dir: Optional[str] = None

    def span(self, name: str):
        if not self.enabled:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def wrap(self, name: str, fn):
        import jax

        def call(*args, **kwargs):
            with jax.profiler.TraceAnnotation(name):
                return fn(*args, **kwargs)

        return call

    def start(self) -> None:
        if self.enabled:
            import jax

            self.dir = tempfile.mkdtemp(prefix="chipbench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0        # host spans are enough
            jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> Optional[str]:
        if not self.enabled:
            return None
        import jax

        jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                            recursive=True)
        return path

    def close(self) -> None:
        if self.dir is not None:
            shutil.rmtree(self.dir, ignore_errors=True)
            self.dir = None


class CompileCounter:
    """Counts XLA backend compilations while it is entered."""

    def __init__(self):
        self.count = 0

    def _listen(self, event: str, duration: float, **kwargs) -> None:
        if event == BACKEND_COMPILE:
            self.count += 1

    def __enter__(self):
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._listen)


def merge(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def overlap(merged: List[Interval], spans: List[Interval]) -> float:
    """Length of the merged intervals that lies inside the spans."""
    return sum(e - s for lo, hi in merge(spans) for s, e in clip(merged, lo, hi))


def gaps(merged: List[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for s, e in clip(merged, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


@dataclass
class Reduced:
    window: Interval                                 # ns, trace clock
    busy: List[List[Interval]]                       # merged, per device
    spans: Dict[str, List[Interval]]
    programs: Dict[str, List[Interval]]
    ops: Dict[str, float]                            # ns by op name
    idle: Dict[str, float] = field(default_factory=dict)  # ns by host span

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        per = [sum(e - s for s, e in b) for b in self.busy]
        return sum(per) / len(per) / 1e9 if per else 0.0

    def busy_within(self, spans: List[Interval]) -> float:
        """Device-busy ns inside the spans, averaged over devices."""
        per = [overlap(b, spans) for b in self.busy]
        return sum(per) / len(per) if per else 0.0

    def program_seconds(self, role: str) -> float:
        """Mean device seconds of one execution of the role's program."""
        runs = self.programs[role]
        return sum(e - s for s, e in runs) / len(runs) / 1e9

    def breakdown(self) -> dict:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1])[:10]
        idle = sorted(self.idle.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[n, v / 1e9] for n, v in top],
                "idle_gaps": [[n, v / 1e9] for n, v in idle]}


def _is_device(name: str) -> bool:
    return name.startswith("/device:") and not name.startswith("/device:CPU")


def _short(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``%fusion.12``."""
    return name.split(" = ", 1)[0]


def self_times(events: List[Tuple[float, float, str]], lo: float,
               hi: float) -> Counter:
    """Time of each op inside [lo, hi] less the time of ops nested in it."""
    out: Counter = Counter()
    stack: List[list] = []               # [end, name, nested time, start]

    def close(item):
        end, name, child, start = item
        own = min(end, hi) - max(start, lo) - child
        out[name] += max(own, 0.0)
        if stack:
            stack[-1][2] += min(end, hi) - max(start, lo)

    for s, e, name in sorted(events, key=lambda t: (t[0], -t[1])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        stack.append([e, name, 0.0, s])
    while stack:
        close(stack.pop())
    return out


def reduce(path: str) -> Reduced:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans: Dict[str, List[Interval]] = defaultdict(list)
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in SPANS:
                        spans[ev.name].append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
        elif _is_device(plane.name):
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" in lines:
                devices.append(lines)
    if len(spans.get("window", [])) != 1:
        raise ValueError(f"{path}: expected one 'window' span, found "
                         f"{len(spans.get('window', []))}")
    if not devices:
        raise ValueError(f"{path}: no device plane with an 'XLA Ops' line")
    (window,) = spans.pop("window")
    lo, hi = window
    spans = {k: clip(v, lo, hi) for k, v in spans.items()}

    busy, ops = [], Counter()
    for lines in devices:
        evs = [(ev.start_ns, ev.start_ns + ev.duration_ns, _short(ev.name))
               for ev in lines["XLA Ops"].events]
        evs = [t for t in evs if t[1] > max(t[0], lo) and t[0] < hi]
        busy.append(merge(clip([(s, e) for s, e, _ in evs], lo, hi)))
        ops.update(self_times(evs, lo, hi))

    programs: Dict[str, List[Interval]] = {}
    line = devices[0].get("XLA Modules")
    modules = sorted(
        (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
        for ev in (line.events if line is not None else [])
        if ev.start_ns < hi and ev.start_ns + ev.duration_ns > lo)
    runs: Dict[str, List[Interval]] = defaultdict(list)
    for s, e, key in modules:
        runs[key].append((s, e))
    for name, role in ROLES.items():
        n = len(spans.get(name, []))
        fits = [(sum(e - s for s, e in v), k) for k, v in runs.items()
                if n and abs(len(v) - n) <= max(1, n // 50)]
        if fits:
            key = max(fits)[1]
            programs[role] = runs.pop(key)
    idle: Counter = Counter()
    inner = [(s, e, n) for n, v in spans.items() for s, e in v]
    for g0, g1 in gaps(busy[0], lo, hi):
        mid = (g0 + g1) / 2
        around = [(e - s, n) for s, e, n in inner if s <= mid < e]
        idle[min(around)[1] if around else "harness"] += g1 - g0
    return Reduced(window, busy, spans, programs, dict(ops), dict(idle))
