"""How late the open-loop generator handed requests to the engine: the
99th percentile over the window's requests of (submit time - due time), in
ms. A busy engine step delays the submit of a request due during it; this
is the client-side share of the time to first token."""


def read(ctx):
    return ctx.get("late_p99_ms")
