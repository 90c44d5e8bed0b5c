"""Device program executions (the trace's ``XLA Modules`` events) per
tick in the traced window."""


def read(ctx):
    if ctx["ticks"] == 0:
        return None
    return ctx["trace"]["programs"] / ctx["ticks"]
