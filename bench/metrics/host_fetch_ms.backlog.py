"""Host self time of ``serving.fetch``: ``device_get`` of the block's outputs
and the wait for its state, the host waiting on the chip, in ms per
``serving.step`` in the traced window (``bench.hostspans``). Nothing to
read without the program's ``serving.*`` spans in the trace summary."""

from bench import hostspans


def read(ctx):
    return hostspans.per_step_ms(ctx["trace"], ("fetch",))
