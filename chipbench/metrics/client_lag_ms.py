"""Mean milliseconds by which the client sent a request after it was due
or after the server came free, whichever was later: how late the
generator ran."""
from chipbench import readers


def read(run):
    v = readers.mean(r.lag for r in run.requests)
    return None if v is None else 1e3 * v
