"""How long a request waits in the engine's queue: the 90th percentile over
the traced window's admissions of (``repro.serve.admit`` start -
``repro.serve.submit`` start), joined on ``uid``, in ms. It is the part of
the time to first token spent behind earlier requests of the step and the
steps before it."""
from bench import program_trace


def read(ctx):
    pt = program_trace.load(ctx)
    if pt is None:
        return None
    waits = program_trace.requests(pt).wait_ns
    return 1e-6 * program_trace.nearest_rank(waits, 0.9) if waits else None
