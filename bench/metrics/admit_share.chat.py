"""Share of the traced window the engine spent admitting: its own
counters of batch-1 prefill and cache-splice seconds (stats() prefill_s
and insert_s, each ending in block_until_ready), over the window."""


def read(ctx):
    a = ctx.get("admit_s")
    return None if a is None else 100.0 * a / ctx["trace"].window_s
