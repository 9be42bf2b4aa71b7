"""Roofline share of the Pallas kernel ``flash_fwd`` over the traced train window."""
from bench import common


def read(ctx):
    return common.kernel_roofline(ctx, "flash_fwd") if ctx["kind"] == "train" else None
