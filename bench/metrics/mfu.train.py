"""Whole train step: model FLOPs (6 per weight and token, causal attention,
nothing recomputed) of the traced window's steps over its length and the
chip's bf16 peak, in percent."""
from bench import common


def read(ctx):
    return common.mfu(ctx) if ctx["kind"] == "train" else None
