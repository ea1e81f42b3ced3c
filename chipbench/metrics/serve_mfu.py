"""Model operations of the traced window's prefill and decode calls over
the summed length of their engine.serve spans, over the bf16 peak, in
percent: the whole step's share of the chip."""
from chipbench import readers


def read(run):
    return readers.serve_mfu_pct(run)
