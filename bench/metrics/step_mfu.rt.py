"""The whole served step's share of the chip's bf16 peak: stream-hops
computed in the traced window times the FLOPs of one stream-hop through
the whole net (``work.py`` of the ``kws_paper`` family), over the window
and the peak."""

from bench.families.kws_paper import work


def read(ctx):
    hops = ctx["counters"]["speech_hops"]
    if hops == 0:
        return None
    flops = hops * work.hop_flops(ctx["model"], ctx["hop"])
    return 100.0 * flops / (ctx["window_s"] * ctx["peaks"]["bf16_flops"])
