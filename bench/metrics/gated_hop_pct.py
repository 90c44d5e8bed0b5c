"""Share of the window's hops that the VAD gated (filled without compute),
from the program's ``serving.hops{kind}`` counters.  Nothing to read in a
cell without a VAD."""


def read(ctx):
    if ctx["traffic"].get("vad") is None:
        return None
    c = ctx["counters"]
    total = c["gated_hops"] + c["speech_hops"]
    return 100.0 * c["gated_hops"] / total if total else None
