"""Model family ``kwt``: the Keyword Transformer (arXiv 2104.00769), a
post-norm ViT over MFCC time frames, served with an MFCC frame ring and the
whole encoder recomputed every hop.

The three entry points the harness calls (``bench/registry.py``):
``draw`` (``weights.py``), ``build_server`` (``server.py``) and
``stream_reference`` (``reference.py``).  ``work.py`` counts the net's
operations and bytes for this family's per-layer readers.
"""

from .reference import stream_reference
from .server import build_server
from .weights import draw

__all__ = ["build_server", "draw", "stream_reference"]
