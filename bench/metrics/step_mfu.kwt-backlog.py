"""The whole served KWT step's share of the chip's bf16 peak: stream-hops
computed in the traced window times the least FLOPs of one stream-hop
(``work.py`` of the ``kwt`` family), over the window and the peak."""

from bench.families.kwt import work


def read(ctx):
    hops = ctx["counters"]["speech_hops"]
    if hops == 0:
        return None
    flops = hops * work.hop_flops(ctx["model"], least=True)
    return 100.0 * flops / (ctx["window_s"] * ctx["peaks"]["bf16_flops"])
