"""The program's own spans (``repro.spans``), read in the run's process.

The window's requests are the last ``n`` ``router.request`` roots, where
``n`` is the number of requests the window sent: the warm-up requests come
before them, and nothing is served after the window.  Every function
returns None where the program records no spans (a program without
``repro.spans``) or its ring no longer holds the whole window.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Tuple

from chipbench.harness import WARMUP_REQUESTS

# spans that wait on a device result: one host round trip each
WAITS = ("engine.prefill_run", "engine.token_fetch", "engine.final_wait",
         "engine.logits_fetch")


def records() -> list:
    try:
        from repro import spans
    except ImportError:
        return []
    return spans.records()


def _roots(run) -> Optional[Tuple[list, list]]:
    """The ring's records and the window's ``router.request`` roots, or
    None where the ring does not hold the whole window."""
    recs = records()
    n = len([r for r in run.requests if math.isfinite(r.sent)])
    roots = [s for s in recs if s.name == "router.request"]
    if n == 0 or len(roots) < n:
        return None
    if recs[0].request in {s.id for s in roots[-n:]}:
        return None                     # the ring dropped part of the window
    return recs, roots


def window(run) -> Optional[Dict[int, list]]:
    """The spans of each request the window sent, by request id."""
    got = _roots(run)
    if got is None:
        return None
    recs, roots = got
    n = len([r for r in run.requests if math.isfinite(r.sent)])
    out: Dict[int, list] = {s.id: [] for s in roots[-n:]}
    for s in recs:
        if s.request in out:
            out[s.request].append(s)
    return out


def seconds(run, name: str) -> Optional[List[float]]:
    """Durations of the window's spans of one name."""
    w = window(run)
    if w is None:
        return None
    return [s.seconds for spans in w.values() for s in spans if s.name == name]


def waits_per_token(run) -> Optional[float]:
    w = window(run)
    if w is None:
        return None
    flat = [s for spans in w.values() for s in spans]
    tokens = sum(s.attrs.get("decode_steps", 0) for s in flat
                 if s.name == "engine.run")
    waits = sum(s.name in WAITS for s in flat)
    return waits / tokens if tokens else None


def router_seconds(run) -> Optional[List[float]]:
    """Per request, ``router.request`` less its ``engine.run`` and
    ``pool.start`` children."""
    w = window(run)
    if w is None:
        return None
    out = []
    for rid, spans in w.items():
        (root,) = [s for s in spans if s.id == rid]
        inner = sum(s.seconds for s in spans if s.parent == rid
                    and s.name in ("engine.run", "pool.start"))
        out.append(root.seconds - inner)
    return out


def setup_start_seconds(run) -> Optional[float]:
    """The ``engine.start`` spans of the run's warm-up requests, the
    ``WARMUP_REQUESTS`` roots just before the window's: the engine's start
    during set-up, and no earlier run's in the same process."""
    got = _roots(run)
    if got is None:
        return None
    recs, roots = got
    n = len([r for r in run.requests if math.isfinite(r.sent)])
    warm = {s.id for s in roots[:-n][-WARMUP_REQUESTS:]}
    starts = [s.seconds for s in recs
              if s.name == "engine.start" and s.request in warm]
    return sum(starts) if starts else None
