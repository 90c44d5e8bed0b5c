"""Model family ``kws_paper``: the paper's binary group-conv KWS net
(arXiv 2205.04665) folded onto the IMC macro, with or without the modelled
silicon.

The three entry points the harness calls (``bench/registry.py``):
``draw`` (``weights.py``), ``build_server`` (``server.py``) and
``stream_reference`` (``reference.py``).  ``work.py`` counts the net's
operations and bytes for this family's per-layer readers.
"""

from .reference import stream_reference
from .server import build_server
from .weights import draw

__all__ = ["build_server", "draw", "stream_reference"]
