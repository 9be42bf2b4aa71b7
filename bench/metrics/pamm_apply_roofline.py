"""Roofline share of the Pallas kernel ``pamm_apply`` over the traced train window."""
from bench import common


def read(ctx):
    return common.kernel_roofline(ctx, "pamm_apply")
