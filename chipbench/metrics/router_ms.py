"""Mean milliseconds of a window request's ``router.request`` span outside
its ``engine.run`` and ``pool.start`` children: routing, placement and
settling on the host."""
from chipbench import program_spans, readers


def read(run):
    v = program_spans.router_seconds(run)
    v = None if v is None else readers.mean(v)
    return None if v is None else 1e3 * v
