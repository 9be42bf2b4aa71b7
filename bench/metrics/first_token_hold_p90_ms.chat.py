"""How long an admitted request's first token is held before the caller
sees it: the 90th percentile over the traced window's admissions of (end
of the enclosing ``repro.serve.step`` - end of its own
``repro.serve.admit``), in ms: the admissions queued behind it in the same
step plus the decode block that follows them."""
from bench import program_trace


def read(ctx):
    pt = program_trace.load(ctx)
    if pt is None:
        return None
    holds = program_trace.requests(pt).hold_ns
    return 1e-6 * program_trace.nearest_rank(holds, 0.9) if holds else None
