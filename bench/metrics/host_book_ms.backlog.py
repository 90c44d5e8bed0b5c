"""Host self time of ``serving.book``: the host replay of each tick's
bookkeeping and events, in ms per ``serving.step`` in the traced window
(``bench.hostspans``). Nothing to read without the program's ``serving.*``
spans in the trace summary."""

from bench import hostspans


def read(ctx):
    return hostspans.per_step_ms(ctx["trace"], ("book",))
