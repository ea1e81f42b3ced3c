"""Least time of one prefill call of latent attention with routed and
shared experts at the chip's peak (counts_mla_moe.py) over its mean device
time in the trace, in percent."""
from chipbench import counts, counts_mla_moe


def read(run):
    if run.trace is None or not run.trace.programs.get("prefill"):
        return None
    m, p = run.cell.model, run.cell.mix["prompt_tokens"]
    ideal = counts.seconds_at_peak(*counts_mla_moe.prefill(m, p),
                                   run.device_kind)[0]
    return 100.0 * ideal / run.trace.program_seconds("prefill")
