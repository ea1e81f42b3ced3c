"""Host waits on a device result per served token, over the window's
requests: the program's ``engine.prefill_run``, ``engine.token_fetch``,
``engine.final_wait`` and ``engine.logits_fetch`` spans over the decode
steps of its ``engine.run`` spans."""
from chipbench import program_spans


def read(run):
    return program_spans.waits_per_token(run)
