"""Host time of one tick: the benchmark's own time inside ``step()`` over
the window, divided by the ticks."""


def read(ctx):
    if ctx["ticks"] == 0:
        return None
    return 1e3 * ctx["step_s"] / ctx["ticks"]
