"""Whole serving step in the chat cell: model FLOPs of the prompt tokens
prefilled and the tokens decoded in the traced window, over its length and
the bf16 peak, in percent."""
from bench import common


def read(ctx):
    return common.mfu(ctx) if ctx["kind"] == "serve" else None
