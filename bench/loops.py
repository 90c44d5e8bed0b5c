"""The measured window: an open-loop real-time driver and a backlog drain.

Both drive the server only through ``submit`` and ``step`` /
``step_block`` and time on the host clock.  Both keep a mirror of every
stream's buffer: a tick takes one hop from every stream that has one
pending (``step``), or up to one block (``step_block``).  The mirror is
checked against the server's own per-stream counts at the end.
"""

from __future__ import annotations

import contextlib
import time
from typing import Callable, Dict, List

import numpy as np

from bench import traffic as tr

DRAIN_GRACE_S = 60.0      # an answer later than this after the close failed


class Feed:
    """Hands each stream its next hops of audio, in order."""

    def __init__(self, plan: dict, sids: List[str], window: int, hop: int):
        self.plan, self.sids, self.window, self.hop = plan, sids, window, hop
        self.n_warm = len(plan["warmup"]) // hop
        self.sent = np.zeros(len(sids), np.int64)   # hops after window 0

    def first_windows(self, srv) -> None:
        for i, sid in enumerate(self.sids):
            srv.submit(sid, tr.stream_audio(self.plan["bank"],
                                            int(self.plan["offsets"][i]), 0,
                                            self.window))

    def chunk(self, i: int, k: int, n: int) -> np.ndarray:
        """Hops ``k .. k + n`` of stream ``i`` after its first window."""
        hop, warm = self.hop, self.n_warm
        parts = []
        if k < warm:
            m = min(n, warm - k)
            parts.append(self.plan["warmup"][k * hop:(k + m) * hop])
            k, n = k + m, n - m
        if n > 0:
            parts.append(tr.stream_audio(
                self.plan["bank"], int(self.plan["offsets"][i]),
                self.window + (k - warm) * hop, n * hop))
        return parts[0] if len(parts) == 1 else np.concatenate(parts)

    def send(self, srv, i: int, n: int) -> None:
        srv.submit(self.sids[i], self.chunk(i, int(self.sent[i]), n))
        self.sent[i] += n


def collect(events_log: list, keep: set) -> Dict[str, list]:
    """Per kept stream, its (keyword, score) decisions in order."""
    out: Dict[str, list] = {sid: [] for sid in keep}
    for evs in events_log:
        for ev in evs:
            if ev["stream"] in keep:
                out[ev["stream"]].append((ev["keyword"], ev["score"]))
    return out


def warm_up(srv, feed: Feed, step: Callable, per_call: int) -> list:
    """Set-up: every stream's first window (one batched init), then the
    warm-up hops, stepped until drained (``per_call`` hops a call), so that
    every program the window runs is compiled before it opens."""
    feed.first_windows(srv)
    events = [srv.step()]
    n = feed.n_warm
    if n:
        for i in range(len(feed.sids)):
            feed.send(srv, i, n)
        for _ in range(-(-n // per_call)):
            events.append(step())
    return events


def _spans(traced: bool):
    """Host spans in the profiler's trace (``bench.<phase>``), which the
    trace reduction uses to label device idle gaps; none when untraced."""
    if not traced:
        return lambda name: contextlib.nullcontext()
    import jax
    return lambda name: jax.profiler.TraceAnnotation("bench." + name)


def realtime(srv, feed: Feed, seconds: float, period_s: float,
             phases: np.ndarray, traced: bool = False) -> dict:
    """Open loop: stream i's hop k of the window is due at
    ``t0 + phases[i] + k * period``.  Whenever a hop is pending the loop
    calls ``step()``; a hop's latency runs from its due time to the return
    of the call that took it.  After the close no hop is sent, and the loop
    steps until every due hop was taken (or the grace ran out)."""
    n = len(feed.sids)
    span = _spans(traced)
    base = feed.sent.copy()               # hops sent before the window
    sent = np.zeros(n, np.int64)          # window hops sent
    taken = np.zeros(n, np.int64)         # window hops taken
    lat: List[np.ndarray] = []
    events: list = []
    step_s, ticks, late_s = 0.0, 0, 0.0
    backlog_at_close = None
    t0 = time.perf_counter() + 0.01
    t_end = t0 + seconds
    t_stop = t_end + DRAIN_GRACE_S
    while True:
        now = time.perf_counter()
        if now >= t_stop:
            break
        if now >= t_end and backlog_at_close is None:
            backlog_at_close = int((sent - taken).max())
        horizon = min(now, t_end)
        due = np.floor((horizon - t0 - phases) / period_s).astype(
            np.int64) + 1
        due = np.where(horizon >= t0 + phases, due, 0)
        with span("submit"):
            for i in np.nonzero(due > sent)[0]:
                k = int(due[i] - sent[i])
                late_s = max(late_s, now - (t0 + phases[i]
                                            + (due[i] - 1) * period_s))
                feed.send(srv, i, k)
                sent[i] = due[i]
        pend = sent > taken
        if pend.any():
            ts = time.perf_counter()
            with span("step"):
                events.append(srv.step())
            te = time.perf_counter()
            idx = np.nonzero(pend)[0]
            lat.append(te - (t0 + phases[idx] + taken[idx] * period_s))
            taken[idx] += 1
            step_s += te - ts
            ticks += 1
        elif now >= t_end:
            break
        else:
            nxt = float(np.min(t0 + phases + sent * period_s))
            wait = nxt - time.perf_counter()
            if wait > 3e-4:
                with span("wait"):
                    time.sleep(wait - 2e-4)
    t_last = time.perf_counter()
    lat_all = np.concatenate(lat) if lat else np.zeros(0)
    return {"attempted": int(sent.sum()),
            "failed": int((sent - taken).sum()),
            "latency_s": lat_all, "events": events, "ticks": ticks,
            "step_s": step_s, "window_s": t_last - t0,
            "generator_late_s": late_s,
            "taken": base + taken, "backlog_end": backlog_at_close}


def backlog(srv, feed: Feed, seconds: float, block: int, ahead: int,
            traced: bool = False) -> dict:
    """Offline scoring: every stream's buffer is kept at least one block
    ahead, and ``step_block()`` drains them as fast as the server goes.
    The rate counts the hops of every call that started inside the window,
    over the time to the last one's return."""
    span = _spans(traced)
    pending = np.zeros(len(feed.sids), np.int64)   # hops buffered, mirror
    events: list = []
    hops, ticks, step_s = 0, 0, 0.0
    t0 = time.perf_counter()
    t_end = t0 + seconds
    te = t0
    while te < t_end:
        with span("submit"):
            for i in np.nonzero(pending < block)[0]:
                k = ahead - int(pending[i])
                feed.send(srv, i, k)
                pending[i] += k
        ts = time.perf_counter()
        with span("step"):
            events.append(srv.step_block())
        te = time.perf_counter()
        took = np.minimum(pending, block)
        pending -= took
        hops += int(took.sum())
        ticks += int(took.max())
        step_s += te - ts
    return {"attempted": hops, "failed": 0, "hops": hops,
            "events": events, "ticks": ticks, "step_s": step_s,
            "window_s": te - t0,
            "taken": feed.sent - pending}
