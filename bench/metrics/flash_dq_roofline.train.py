"""Roofline share of the Pallas kernel ``flash_dq`` over the traced train window."""
from bench import common


def read(ctx):
    return common.kernel_roofline(ctx, "flash_dq") if ctx["kind"] == "train" else None
