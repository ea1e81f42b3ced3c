"""Seconds from process start to the window's opening: imports, engine
start (init, compilation or compile-cache hit), the benchmark's weights
and the warm-up requests."""


def read(run):
    return run.setup_s
