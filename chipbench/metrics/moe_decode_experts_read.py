"""Held experts whose weights a decode step read, per MoE layer and step,
in the traced window's requests: the program's counters
``moe_decode_experts_read`` over ``moe_decode_layers`` on its
``engine.decode`` spans, each summed over the request's steps and MoE
layers.  Every held expert (16 in DeepSeek-V2-Lite's chip share) where
the step streams them all; near the held experts a token's pairs reach
(about 1.5 there) where it reads only those.  A program without these
counters reads nothing."""
from chipbench import program_spans


def read(run):
    if run.trace is None:
        return None
    w = program_spans.window(run)
    if w is None:
        return None
    calls = [s.attrs for spans in w.values() for s in spans
             if s.name == "engine.decode" and "moe_decode_layers" in s.attrs]
    layers = sum(a["moe_decode_layers"] for a in calls)
    if not layers:
        return None
    return sum(a["moe_decode_experts_read"] for a in calls) / layers
