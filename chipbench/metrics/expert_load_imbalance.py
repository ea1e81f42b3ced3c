"""The heaviest held expert's tokens over the mean tokens of a held
expert, in the traced window's prefill calls: the program's counters
``moe_max_expert_tokens`` and ``moe_pairs_held`` on its
``engine.prefill_run`` spans, each summed over the MoE layers.  1 is an
even load; the grouped expert matmuls wait on the heaviest group."""
from chipbench import program_spans


def read(run):
    if run.trace is None:
        return None
    w = program_spans.window(run)
    if w is None:
        return None
    calls = [s.attrs for spans in w.values() for s in spans
             if s.name == "engine.prefill_run" and "moe_pairs_held" in s.attrs]
    pairs = sum(a["moe_pairs_held"] for a in calls)
    if not pairs:
        return None
    moe = run.cell.model["moe"]
    held = moe["experts_held"] or moe["num_experts"]
    return sum(a["moe_max_expert_tokens"] for a in calls) * held / pairs
