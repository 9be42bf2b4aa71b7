"""Roofline share of ``flash_fwd`` in the traced serving window: the
batch-1 prefills of admitted prompts, each at its padded bucket length."""
from bench import common


def read(ctx):
    return common.kernel_roofline(ctx, "flash_fwd") if ctx["kind"] == "serve" else None
