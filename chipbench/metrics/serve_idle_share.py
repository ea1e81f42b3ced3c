"""Percent of the engine.serve spans in which no operation ran on the
device (at a fixed offered rate, whole-window idle would rise as the
server got faster, so this counts only time inside serve calls)."""
from chipbench import readers


def read(run):
    return readers.idle_in_serve_pct(run)
