"""Host self time of the compiled block's preparation (``serving.horizon`` +
``.stage`` + ``.fate``: eligibility, staging the ready hops, the fate
simulation and the block's operands), in ms per ``serving.step`` in the
traced window (``bench.hostspans``). Nothing to read without the program's
``serving.*`` spans in the trace summary."""

from bench import hostspans


def read(ctx):
    return hostspans.per_step_ms(ctx["trace"], ("horizon", "stage", "fate"))
