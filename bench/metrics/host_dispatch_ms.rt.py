"""Host self time of ``serving.dispatch``: the main block's lookup, its
operands' transfer and the jitted call, in ms per ``serving.step`` in the
traced window (``bench.hostspans``). Nothing to read without the program's
``serving.*`` spans in the trace summary."""

from bench import hostspans


def read(ctx):
    return hostspans.per_step_ms(ctx["trace"], ("dispatch",))
