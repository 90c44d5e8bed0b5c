"""Reduce the program's own spans in a JAX profiler trace.

The program marks each phase of its tick with a ``serving.<phase>`` span
(``repro.obs.span``, a ``jax.profiler.TraceAnnotation``).  The spans land
on the host planes of the same trace as the chip's ops.  From a list of
the trace's planes (``list(ProfileData.planes)``: the property
is an iterator that can be walked once; a plane's lines and a line's
events can be walked again), or objects of the same shape, this module
gives:

* ``spans``: per span name, ``{count, total_s, self_s}``.  A span's self
  time is its duration less the union of its direct ``serving.*`` children
  on the same host line;
* ``idle_gaps``: the longest idle gaps of the chip, each labelled by the
  innermost host span (``serving.*``, or the benchmark's own ``bench.*``)
  that covers the gap's middle: what the host was doing while the chip
  idled;
* ``per_step_ms``: the self time of some phases per ``serving.step``, the
  reduction behind the ``host_*_ms`` readers.

``bench.devtrace`` does not call these yet: the readers find a ``spans``
key in its summary only once it does.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Tuple

from bench.devtrace import (DEVICE_PREFIX, HOST_SPAN_PREFIX, OPS_LINE,
                            _union)

SPAN_PREFIX = "serving."
STEP = SPAN_PREFIX + "step"


def _host_events(planes, prefixes) -> List[Tuple[tuple, int, int, str]]:
    """(line key, start ns, end ns, name) of every host event whose name
    starts with one of ``prefixes``."""
    out = []
    for plane in planes:
        if plane.name.startswith(DEVICE_PREFIX):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(prefixes):
                    out.append(((plane.name, i), ev.start_ns,
                                ev.start_ns + ev.duration_ns, ev.name))
    return out


def spans(planes) -> Dict[str, dict]:
    """``{name: {count, total_s, self_s}}`` of the ``serving.*`` spans."""
    by_line: Dict[tuple, list] = {}
    for key, s, e, name in _host_events(planes, (SPAN_PREFIX,)):
        by_line.setdefault(key, []).append((s, e, name))
    out: Dict[str, dict] = {}
    for evs in by_line.values():
        evs.sort(key=lambda v: (v[0], -v[1]))
        children: List[list] = [[] for _ in evs]
        stack: List[int] = []
        for i, (s, e, _name) in enumerate(evs):
            while stack and evs[stack[-1]][1] <= s:
                stack.pop()
            if stack and evs[stack[-1]][1] >= e:
                children[stack[-1]].append((s, e))
            stack.append(i)
        for (s, e, name), kids in zip(evs, children):
            covered, _ = _union(list(kids))
            row = out.setdefault(name, {"count": 0, "total_s": 0.0,
                                        "self_s": 0.0})
            row["count"] += 1
            row["total_s"] += (e - s) * 1e-9
            row["self_s"] += (e - s - covered) * 1e-9
    return out


def idle_gaps(planes, n: int = 10) -> List[list]:
    """The ``n`` longest gaps between the chip's ops, longest first, as
    ``[label, seconds]``: the innermost ``serving.*`` or ``bench.*`` host
    span covering the gap's middle, or "no host span"."""
    gaps = []
    for plane in planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        for line in plane.lines:
            if line.name == OPS_LINE:
                _, g = _union([(ev.start_ns, ev.start_ns + ev.duration_ns)
                               for ev in line.events])
                gaps.extend(g)
    host = [(s, e, name) for _k, s, e, name in _host_events(
        planes, (SPAN_PREFIX, HOST_SPAN_PREFIX))]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) // 2
        cover = [h for h in host if h[0] <= mid <= h[1]]
        # innermost: the latest start, then the shortest
        what = (max(cover, key=lambda h: (h[0], -h[1]))[2] if cover
                else "no host span")
        out.append([what, (e - s) * 1e-9])
    return out


def per_step_ms(summary: dict, phases: Iterable[str]) -> Optional[float]:
    """Summed self time of ``serving.<phase>`` over ``phases``, in ms per
    ``serving.step``; None without a ``spans`` key or a step."""
    table = summary.get("spans")
    if not table or not table.get(STEP, {}).get("count"):
        return None
    total = sum(table.get(SPAN_PREFIX + p, {}).get("self_s", 0.0)
                for p in phases)
    return 1e3 * total / table[STEP]["count"]
