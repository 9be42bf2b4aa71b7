"""Device-idle ms per engine step in the traced decode window: the host's
own time inside each ``repro.serve.step``, logged by innermost span."""
from bench import program_trace


def read(ctx):
    return program_trace.step_idle_ms(ctx)
