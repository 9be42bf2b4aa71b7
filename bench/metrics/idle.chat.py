"""Share of the traced chat window in which no operation ran on the device."""
from bench import common


def read(ctx):
    return common.idle(ctx)
