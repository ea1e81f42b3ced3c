"""Mean milliseconds per decode step of the window's requests, from the
engine's own ServeStats.decode_s over its step count (host clock; the
loop syncs the host once per token)."""
from chipbench import readers


def read(run):
    v = readers.mean(r.decode_s / r.steps for r in run.served if r.steps)
    return None if v is None else 1e3 * v
