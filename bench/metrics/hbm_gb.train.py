"""Device memory of the compiled train step by the compiler's own account
(memory_analysis: arguments + temporaries + outputs - aliased), in GB."""


def read(ctx):
    return ctx["hbm_bytes"] / 1e9 if ctx["kind"] == "train" else None
