"""Least time of one decode_step call at the chip's peak (counts.py) over its
mean device time in the trace, in percent."""
from chipbench import readers


def read(run):
    return readers.roofline_pct(run, "decode_step")
