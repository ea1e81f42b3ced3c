"""Operations of the traced window's prefill and decode calls of latent
attention with routed and shared experts (counts_mla_moe.py) over the
summed length of their engine.serve spans, over the bf16 peak, in percent:
the whole step's share of the chip."""
from chipbench import counts, counts_mla_moe


def read(run):
    if run.trace is None:
        return None
    spans = run.trace.spans.get("engine.serve", [])
    if not spans:
        return None
    m, mix = run.cell.model, run.cell.mix
    p = mix["prompt_tokens"]
    ops = counts_mla_moe.prefill(m, p)[0] + sum(
        counts_mla_moe.decode(m, p + i)[0] for i in range(mix["output_tokens"]))
    span_s = sum(e - s for s, e in spans) / 1e9
    peak = counts.peaks(run.device_kind)["bf16_flops_per_s"]
    return 100.0 * ops * len(spans) / span_s / peak
