"""Read the ``serving.*`` host spans back from a profiler trace.

``host_spans(trace_dir)`` loads the newest ``.xplane.pb`` that
``jax.profiler.trace(trace_dir)`` wrote and returns, per span name, the
list of ``(start_ns, end_ns, args)`` in start order (``args``: the span's
arguments, the last value of each key).
"""

from __future__ import annotations

import pathlib
from collections import defaultdict
from typing import Dict, List, Tuple

import jax


def host_spans(trace_dir) -> Dict[str, List[Tuple[int, int, dict]]]:
    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    assert files, f"no .xplane.pb under {trace_dir}"
    prof = jax.profiler.ProfileData.from_file(str(files[-1]))
    out = defaultdict(list)
    for plane in prof.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serving."):
                    out[ev.name].append((ev.start_ns, ev.end_ns,
                                         dict(ev.stats)))
    for evs in out.values():
        evs.sort(key=lambda e: e[0])
    return out


def inside(span, outer) -> bool:
    """Whether ``span`` lies within one of the ``outer`` spans."""
    s, e = span[0], span[1]
    return any(s0 <= s and e <= e0 for s0, e0, _ in outer)
