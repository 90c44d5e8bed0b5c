"""KWT's share of the chip's bf16 peak while the chip runs: the least
FLOPs of the stream-hops computed in the traced window (``work.py`` of the
``kwt`` family) over the device's busy time (``bench.devtrace``) and the
peak.  The host's idle gaps are left out, so this is the encoder's own
efficiency on the chip."""

from bench.families.kwt import work


def read(ctx):
    hops = ctx["counters"]["speech_hops"]
    busy = ctx["trace"]["busy_s"]
    if hops == 0 or busy <= 0:
        return None
    flops = hops * work.hop_flops(ctx["model"], least=True)
    return 100.0 * flops / (busy * ctx["peaks"]["bf16_flops"])
