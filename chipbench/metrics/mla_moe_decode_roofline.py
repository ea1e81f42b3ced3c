"""Least time of one decode_step call of latent attention with routed and
shared experts at the chip's peak (counts_mla_moe.py: the latent cache
and only the routed experts' weights read) over its mean device time in
the trace, in percent."""
from chipbench import counts, counts_mla_moe


def read(run):
    if run.trace is None or not run.trace.programs.get("decode_step"):
        return None
    m, mix = run.cell.model, run.cell.mix
    ideal = [counts.seconds_at_peak(
        *counts_mla_moe.decode(m, mix["prompt_tokens"] + i),
        run.device_kind)[0] for i in range(mix["output_tokens"])]
    return (100.0 * sum(ideal) / len(ideal)
            / run.trace.program_seconds("decode_step"))
