"""The fused IMC layer kernels' share of their roofline: the least time the
chip could take for the stream-hops they computed in the traced window
(``work.py`` of the ``kws_paper`` family, bytes or FLOPs, whichever
bounds), over their summed device time in the trace.  Nothing to read
when no IMC kernel ran."""

from bench.families.kws_paper import work


def read(ctx):
    tr = ctx["trace"]
    hops = ctx["counters"]["speech_hops"]
    if tr["imc_calls"] == 0 or tr["imc_kernel_s"] <= 0 or hops == 0:
        return None
    n_layers = len(ctx["model"]["channels"]) - 1
    least, _ = work.imc_least_seconds(ctx["model"], ctx["hop"], hops,
                                      tr["imc_calls"] / n_layers,
                                      ctx["peaks"])
    return 100.0 * least / tr["imc_kernel_s"]
