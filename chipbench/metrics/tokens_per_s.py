"""Output tokens returned in the window over the window's seconds.  The
request still being served when the window closes counts for the share of
its service time that lies inside the window."""


def read(run):
    t = run.cell.mix["output_tokens"]
    tokens = 0.0
    for r in run.served:
        if r.done <= run.seconds:
            tokens += t
        elif r.sent < run.seconds:
            tokens += t * (run.seconds - r.sent) / (r.done - r.sent)
    return tokens / run.seconds
