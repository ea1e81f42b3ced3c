"""Mean prefill milliseconds of the window's requests, from the engine's
own ServeStats.prefill_s (host clock, ended by block_until_ready)."""
from chipbench import readers


def read(run):
    v = readers.mean(r.prefill_s for r in run.served)
    return None if v is None else 1e3 * v
