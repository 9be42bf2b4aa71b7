"""Roofline share of ``paged_decode`` in the traced serving window: every
decode step of every layer, each slot at its context length then."""
from bench import common


def read(ctx):
    return common.kernel_roofline(ctx, "paged_decode")
