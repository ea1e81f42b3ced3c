"""Spans of the serving path: one API for its timers, an in-memory record
and the profiler's trace.

``span(name, **attrs)`` is a context manager.  While a profiler records,
it opens a ``jax.profiler.TraceAnnotation`` of the same name and
attributes, so the trace shows the span on the host plane beside the
runtime's own events; always, on leaving, it records the span, timed on
``time.perf_counter_ns``, in a bounded process-wide ring.  A span's parent
is the innermost span open in the same context (``contextvars``), and its
request is the id of the outermost one: every span under one
``router.request`` carries that request's id.  Per-name counts and total
nanoseconds are kept beside the ring for the life of the process.  Spans are
always on; ``records()`` and ``totals()`` read them.

The names, by layer (``NAMES``):

  router.request                  one routed request (function, cold)
    router.route                  arrival, TTL scan, placement, reclaim
    pool.start                    a replica's start (cold requests only)
      engine.start                InferenceEngine.cold_start
        engine.start.build        the model bundle      (runtime_init)
        engine.start.weights      parameters on device  (deps_load)
          .compile / .run         a fresh init: compile, then run
          .read / .put            a snapshot: read to host, then put
        engine.start.compile      prefill and decode executables (code_init;
                                  cache_hits, cache_misses, executable_hit;
                                  attention_kind, prefill_attention,
                                  attention_blocks, cache_bytes_per_token;
                                  experts_held, moe_dispatch)
        engine.start.save         the parameter snapshot written
    engine.run                    InferenceEngine.serve (prompt_tokens,
                                  decode_steps)
      engine.upload               the prompt to the device
      engine.prefill_run          prefill dispatched and waited for
                                  (moe_pairs_held, moe_max_expert_tokens,
                                  moe_dropped, moe_experts_read,
                                  moe_layers)
      engine.decode               the decode loop (ServeStats.decode_s;
                                  the same counters over its steps,
                                  moe_decode_*)
        engine.token_fetch        the previous token to the host, per step
        engine.step_dispatch      index add, decode program, argmax, per step
        engine.final_wait         the last token waited for
      engine.logits_fetch         the last logits to the host
    router.settle                 slot release, TTL, record_execution

Each span that waits on a device result (``WAITS``) is one host round trip.

A profiler trace's device clock is not the host's, and the offset between
them can move within a trace: ``device_offset_ns`` measures it from the
trace itself, ``read_trace`` measures it again inside each ``engine.run``
and moves that run's spans by it, and ``idle_by_span`` attributes the
device's idle time inside ``engine.run`` to the innermost span, on the
device's clock.  From the command line::

    python -m repro.spans <trace.xplane.pb>
"""
from __future__ import annotations

import bisect
import collections
import contextvars
import itertools
import json
import sys
import threading
import time
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax

NAMES = frozenset({
    "router.request", "router.route", "pool.start", "router.settle",
    "engine.start", "engine.start.build", "engine.start.weights",
    "engine.start.weights.compile", "engine.start.weights.run",
    "engine.start.weights.read", "engine.start.weights.put",
    "engine.start.compile", "engine.start.save",
    "engine.run", "engine.upload", "engine.prefill_run", "engine.decode",
    "engine.token_fetch", "engine.step_dispatch", "engine.final_wait",
    "engine.logits_fetch",
})
WAITS = ("engine.prefill_run", "engine.token_fetch", "engine.final_wait",
         "engine.logits_fetch")
# a 51-s window of prompt-4096 requests holds about 85 requests of 40
# spans; the ring keeps several such windows
CAPACITY = 16384

_Trace = jax.profiler.TraceAnnotation
_ids = itertools.count(1)
_open: contextvars.ContextVar[Optional["Span"]] = contextvars.ContextVar(
    "repro_span", default=None)
_lock = threading.Lock()
_ring: collections.deque = collections.deque(maxlen=CAPACITY)
_totals: Dict[str, List[int]] = {}


class Span:
    """One span: a context manager while open, a record once closed.

    ``parent`` is 0 for a root; ``request`` is the id of the root.
    Attributes known only at the end go into ``attrs`` before the span
    closes; they are on the record, not in the profiler's trace."""

    __slots__ = ("name", "attrs", "id", "parent", "request", "start_ns",
                 "end_ns", "_token", "_trace")

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs
        self.start_ns = self.end_ns = 0

    def __enter__(self) -> "Span":
        outer = _open.get()
        self.id = next(_ids)
        self.parent = outer.id if outer is not None else 0
        self.request = outer.request if outer is not None else self.id
        self._token = _open.set(self)
        self._trace = None
        if _Trace.is_enabled():         # a profiler is recording
            self._trace = _Trace(self.name, **self.attrs)
            self._trace.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.end_ns = time.perf_counter_ns()
        if self._trace is not None:
            self._trace.__exit__(*exc)
            self._trace = None
        _open.reset(self._token)
        with _lock:
            _ring.append(self)
            total = _totals.get(self.name)
            if total is None:
                total = _totals[self.name] = [0, 0]
            total[0] += 1
            total[1] += self.end_ns - self.start_ns

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, id={self.id}, parent={self.parent}, "
                f"request={self.request}, {self.seconds * 1e3:.3f} ms, "
                f"{self.attrs})")


def span(name: str, **attrs) -> Span:
    return Span(name, attrs)


def records() -> List[Span]:
    """The closed spans still in the ring, oldest first (a parent closes
    after its children, so it comes after them)."""
    with _lock:
        return list(_ring)


def totals() -> Dict[str, Tuple[int, float]]:
    """Per name since the process started (or ``reset``): count and
    seconds."""
    with _lock:
        return {n: (c, ns / 1e9) for n, (c, ns) in _totals.items()}


def reset() -> None:
    """Empty the ring and the totals."""
    with _lock:
        _ring.clear()
        _totals.clear()


# --------------------------------------------------------------------------- #
# the profiler's trace
# --------------------------------------------------------------------------- #

Interval = Tuple[int, int]


class Offset(NamedTuple):
    ns: int          # a host time less this reads on the device clock
    linked: int      # device programs paired with their enqueue
    modules: int     # device programs in the trace


def _is_device(plane_name: str) -> bool:
    return (plane_name.startswith("/device:")
            and not plane_name.startswith("/device:CPU"))


def _device_lines(pd, name: str):
    for plane in pd.planes:
        if _is_device(plane.name):
            for line in plane.lines:
                if line.name == name:
                    yield line


Link = Tuple[int, int]      # (host enqueue start, device start) of a program


def _links(pd) -> Tuple[List[Link], int]:
    """Each device program paired with its enqueue, by enqueue time, and
    the number of device programs in the trace."""
    enqueued: Dict[int, int] = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == "DoEnqueueProgram":
                        flow = dict(ev.stats).get("_p")
                        if flow is not None:
                            enqueued[flow] = ev.start_ns
    links, modules = [], 0
    for line in _device_lines(pd, "XLA Modules"):
        for ev in line.events:
            modules += 1
            flow = dict(ev.stats).get("_c")
            if flow in enqueued:
                links.append((enqueued[flow], ev.start_ns))
    if not links:
        raise ValueError("no device program is linked to its enqueue")
    return sorted(links), modules


def lead_ns(links: List[Link], lo: int = -2 ** 63,
            hi: int = 2 ** 63) -> Optional[int]:
    """The largest enqueue start less device start of the programs
    enqueued in ``[lo, hi)`` on the host's clock: the least shift that
    puts each of them after its enqueue.  None where none was enqueued."""
    first = bisect.bisect_left(links, (lo, -2 ** 63))
    last = bisect.bisect_left(links, (hi, -2 ** 63))
    return max((h - d for h, d in links[first:last]), default=None)


def device_offset_ns(xplane_path: str) -> Offset:
    """The host-to-device clock offset of a profiler trace.

    Each device program (line ``XLA Modules``) carries the flow id (stat
    ``_c``) of the host ``DoEnqueueProgram`` event that enqueued it (stat
    ``_p``).  No program starts before its enqueue, so the offset is the
    largest enqueue start less device start over the linked pairs: the
    least shift that puts every program after its enqueue.  The offset
    can move within a trace, so ``device_clock`` takes it per
    ``engine.run``."""
    from jax.profiler import ProfileData

    links, modules = _links(ProfileData.from_file(xplane_path))
    return Offset(lead_ns(links), len(links), modules)


def _merge(intervals) -> List[Interval]:
    out: List[list] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def idle_by_span(busy: List[Interval],
                 spans: List[Tuple[int, int, str]]) -> Dict[str, int]:
    """Device idle nanoseconds inside each ``engine.run`` span, by the
    innermost span around each idle instant.

    ``busy`` is the device's busy intervals, ``spans`` the program's spans
    as ``(start, end, name)``, both on one clock."""
    busy = _merge(busy)
    ends = [e for _, e in busy]
    idle: Dict[str, int] = collections.Counter()
    runs = sorted((s, e) for s, e, n in spans if n == "engine.run")
    for lo, hi in runs:
        around = [t for t in spans if t[0] < hi and t[1] > lo]
        t, gaps = lo, []
        for s, e in busy[bisect.bisect_right(ends, lo):]:
            if s >= hi:
                break
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < hi:
            gaps.append((t, hi))
        for g0, g1 in gaps:
            cuts = sorted({g0, g1} | {x for s, e, _ in around
                                      for x in (s, e) if g0 < x < g1})
            for a, b in zip(cuts, cuts[1:]):
                mid = (a + b) / 2
                name = min((e - s, n) for s, e, n in around
                           if s <= mid < e)[1]
                idle[name] += b - a
    return dict(idle)


def device_clock(spans: List[Tuple[int, int, str]], links: List[Link]
                 ) -> Tuple[List[Tuple[int, int, str]], List[int]]:
    """The host spans ``(start, end, name)`` moved onto the device's clock,
    and the offset used for each ``engine.run``.

    Each run, and every span inside it, moves by the offset measured among
    the programs that run enqueued (``lead_ns``): the offset can move
    within a trace (by 0.13–0.18 ms after a trace's first request, on a
    v5e), and one offset for the whole trace then moves idle time from one
    span to the next.  An end outside every run moves by the offset of the nearest run,
    so that a request's spans around its run stay beside it.  A run that
    enqueued nothing moves by the whole trace's offset, and so does every
    span of a trace without runs."""
    whole = lead_ns(links)
    runs = sorted((s, e) for s, e, n in spans if n == "engine.run")
    offsets = []
    for lo, hi in runs:
        off = lead_ns(links, lo, hi)
        offsets.append(whole if off is None else off)
    starts = [lo for lo, _ in runs]

    def at(t: int) -> int:
        k = bisect.bisect_right(starts, t) - 1
        if k >= 0 and (t <= runs[k][1] or k + 1 == len(runs)
                       or t - runs[k][1] <= starts[k + 1] - t):
            return offsets[k]
        return offsets[k + 1] if runs else whole

    return [(s - at(s), e - at(e), n) for s, e, n in spans], offsets


def read_trace(xplane_path: str) -> dict:
    """The attributes of each ``engine.start.compile`` in the trace (the
    prefill attention path and its blocks among them), the offset, and
    the device's idle time inside ``engine.run`` by program span, with the
    host spans put on the device's clock run by run (``device_clock``)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane_path)
    links, modules = _links(pd)
    host = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name in NAMES]
    line = next(_device_lines(pd, "XLA Ops"), None)
    if line is None:
        raise ValueError(f"{xplane_path}: no device plane with 'XLA Ops'")
    busy = [(ev.start_ns, ev.start_ns + ev.duration_ns) for ev in line.events]
    runs = [(s, e) for s, e, n in host if n == "engine.run"]
    moved, offsets = device_clock(host, links)
    idle = idle_by_span(busy, moved)
    out = {
        "offset_ms": lead_ns(links) / 1e6, "linked": len(links),
        "modules": modules, "engine_runs": len(runs),
        "compiles": [dict(ev.stats) for plane in pd.planes
                     if plane.name.startswith("/host:")
                     for line in plane.lines for ev in line.events
                     if ev.name == "engine.start.compile"],
    }
    if offsets:
        offsets.sort()
        out["run_offset_ms"] = {"min": offsets[0] / 1e6,
                                "median": offsets[len(offsets) // 2] / 1e6,
                                "max": offsets[-1] / 1e6}
    out.update({
        "run_s": sum(e - s for s, e in runs) / 1e9,
        "idle_s": sum(idle.values()) / 1e9,
        "idle_uncorrected_s": sum(idle_by_span(busy, host).values()) / 1e9,
        "idle_by_span_s": {n: v / 1e9 for n, v in
                           sorted(idle.items(), key=lambda kv: -kv[1])},
    })
    return out


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print("usage: python -m repro.spans <trace.xplane.pb>",
              file=sys.stderr)
        return 2
    print(json.dumps(read_trace(argv[0]), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
