"""90th percentile, over every request sent in the window, of the seconds
from when it was due to when its tokens came back (a failed request
counts as missing: no value where one lands inside the percentile)."""
from chipbench import readers


def read(run):
    return readers.percentile(readers.latencies(run), 90)
