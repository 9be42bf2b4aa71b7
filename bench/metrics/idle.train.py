"""Share of the traced train window in which no operation ran on the device."""
from bench import common


def read(ctx):
    return common.idle(ctx)
