"""Mean milliseconds of host time per decode step spent dispatching it, in
the program's ``engine.step_dispatch`` span (index add, decode program,
argmax; nothing waited for), over the window's requests."""
from chipbench import program_spans, readers


def read(run):
    v = program_spans.seconds(run, "engine.step_dispatch")
    v = None if v is None else readers.mean(v)
    return None if v is None else 1e3 * v
