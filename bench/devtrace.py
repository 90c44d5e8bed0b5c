"""Reduce a JAX profiler trace to device busy time, per-op time and program
counts.

The trace is the ``.xplane.pb`` that ``jax.profiler.stop_trace`` writes.
On a TPU each chip is one plane named ``/device:TPU:<n>``.  Its line
``XLA Ops`` holds one event per operation run on the chip, and its line
``XLA Modules`` one event per program (jitted executable) run.  From them:

* ``busy_s``: the length of the union of all op intervals, averaged over
  the chips (the window's idle share is ``1 - busy_s / window_s``);
* ``ops``: summed device seconds per op, keyed by the HLO instruction name
  (an event's name is the instruction's text, ``%name = shape op(...)``);
* ``imc_kernel_s`` / ``imc_calls``: the fused IMC layer kernels' device
  time and count: the ``custom-call`` instructions that the program's
  ``imc_fused`` wrapper names ``%imc_fused.<n>``;
* ``programs``: program executions, summed over the chips;
* ``breakdown``: the ten ops that took most time (leaf ops: a ``while`` or
  ``cond``/``conditional`` only holds others), and the ten longest idle gaps
  labelled by the benchmark's host span that covers the gap's middle.
"""

from __future__ import annotations

import pathlib
from typing import Dict, List, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
IMC_KERNEL = "%imc_fused"
CONTAINERS = ("%while", "%cond", "%call")
HOST_SPAN_PREFIX = "bench."


def _union(intervals: List[Tuple[int, int]]) -> Tuple[int, list]:
    """Total covered length and the gaps between merged intervals."""
    intervals.sort()
    total, gaps = 0, []
    cur_s, cur_e = None, None
    for s, e in intervals:
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
                gaps.append((cur_e, s))
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total, gaps


def summarize(planes) -> dict:
    """The reduction over ``ProfileData.planes`` (or any objects with the
    same ``name`` / ``lines`` / ``events`` shape)."""
    busy, programs, n_dev = 0.0, 0, 0
    ops: Dict[str, float] = {}
    imc_s, imc_calls = 0.0, 0
    gaps: List[Tuple[int, int]] = []
    spans: List[Tuple[int, int, str]] = []
    for plane in planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_SPAN_PREFIX):
                        spans.append((ev.start_ns, ev.start_ns
                                      + ev.duration_ns, ev.name))
            continue
        lines = {line.name: line for line in plane.lines}
        if OPS_LINE not in lines:
            continue
        n_dev += 1
        iv = []
        for ev in lines[OPS_LINE].events:
            s, d = ev.start_ns, ev.duration_ns
            iv.append((s, s + d))
            name = ev.name.split(" = ")[0]
            ops[name] = ops.get(name, 0.0) + d * 1e-9
            if name.startswith(IMC_KERNEL):
                imc_s += d * 1e-9
                imc_calls += 1
        covered, g = _union(iv)
        busy += covered * 1e-9
        gaps.extend(g)
        if MODULES_LINE in lines:
            programs += sum(1 for _ in lines[MODULES_LINE].events)
    if n_dev == 0:
        raise ValueError("the trace holds no TPU plane with an "
                         f"{OPS_LINE!r} line")
    spans.sort()
    labelled = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:10]:
        mid = (s + e) // 2
        what = "no host span"
        for hs, he, name in spans:
            if hs <= mid <= he:
                what = name
            if hs > mid:
                break
        labelled.append([what, (e - s) * 1e-9])
    top = sorted(((k, v) for k, v in ops.items()
                  if not k.startswith(CONTAINERS)), key=lambda kv: -kv[1])[:10]
    return {"busy_s": busy / n_dev, "devices": n_dev, "ops": ops,
            "imc_kernel_s": imc_s / n_dev, "imc_calls": imc_calls / n_dev,
            "programs": programs / n_dev,
            "breakdown": {"device_ops": [[k, v] for k, v in top],
                          "idle_gaps": labelled}}


def reduce(trace_dir: pathlib.Path) -> dict:
    """``summarize`` of the newest ``.xplane.pb`` under ``trace_dir``."""
    import jax
    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return summarize(jax.profiler.ProfileData.from_file(str(files[-1])).planes)
