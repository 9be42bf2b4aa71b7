"""Mean number of slots decoding per decode step in the traced window:
tokens the engine decoded (stats() decode_tokens) over the decode steps it
ran with any slot active."""


def read(ctx):
    steps = ctx.get("decode_steps")
    return ctx["decode_tokens"] / steps if steps else None
