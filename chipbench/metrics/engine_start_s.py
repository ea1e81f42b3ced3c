"""Seconds of the engine's start during set-up: the program's
``engine.start`` span (bundle, weights, executables), part of setup_s."""
from chipbench import program_spans


def read(run):
    return program_spans.setup_start_seconds(run)
