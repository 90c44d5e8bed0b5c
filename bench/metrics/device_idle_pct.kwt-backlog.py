"""Share of the KWT cell's traced window in which no operation ran on the
chip: 100 * (1 - busy / window), busy from the profiler trace
(bench.devtrace)."""


def read(ctx):
    return 100.0 * (1.0 - ctx["trace"]["busy_s"] / ctx["window_s"])
