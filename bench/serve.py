"""What the harness reads from a served ``StreamServer``, whatever its
model family: the server's seed, its registry counters and its per-stream
hop counts.  A family builds the server itself
(``bench/families/<family>/``)."""

from __future__ import annotations


def server_seed(seed: int) -> int:
    """The server's noise-field seed (``PRNGKey`` takes 31 bits)."""
    return seed % (2 ** 31)


def counters(srv) -> dict:
    """The registry counters the per-layer readers use."""
    m = srv.metrics
    return {"speech_hops": m.value("serving.hops", kind="speech"),
            "gated_hops": m.value("serving.hops", kind="gated")}


def per_stream_hops(srv) -> dict:
    """Per stream, its decisions (first window included) and gated hops,
    from ``stats()``."""
    return {sid: {"hops": st["hops"], "gated_hops": st["gated_hops"]}
            for sid, st in srv.stats()["per_stream"].items()}
