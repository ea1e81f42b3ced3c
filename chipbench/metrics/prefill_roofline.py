"""Least time of one prefill call at the chip's peak (counts.py) over its
mean device time in the trace, in percent."""
from chipbench import readers


def read(run):
    return readers.roofline_pct(run, "prefill")
