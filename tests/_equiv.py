"""Shared differential-equivalence harness for the serving test suite.

Every serving equivalence claim in this repo has the same shape: drive two
differently-configured servers (batched vs sequential admissions, gated vs
ungated, faulted vs rider-emulated, sharded vs single-device oracle,
compiled whole-tick block vs interpreted Python tick) over identical
traffic, then prove the observable record is BIT-identical — the decision
events, the stream/decision/VAD carry state, and the metrics-registry
counters.  These comparison loops used to be copy-pasted per test file;
they live here so the compiled fast path (``repro.serving.compiled``) is
proven against the exact same notion of "equal" as every older claim.

Counter comparison excludes exactly two registry names
(:data:`COUNTER_EXCLUDES`): wall-clock hop timing, which is real time and
can never be equal, and the ``serving.compiled`` block/tick counters,
which are the one deliberate observable difference between a compiled and
an interpreted run.  Everything else — hops, gated hops, decisions,
admissions, sheds, retires, latency histograms — must match cell for cell.
"""

from __future__ import annotations

import jax
import numpy as np

__all__ = [
    "COUNTER_EXCLUDES", "advance_to", "assert_counters_equal",
    "assert_events_equal", "assert_leaves_equal", "assert_server_equal",
    "counter_cells", "per_stream", "pool_devices",
]

# registry names excluded from counter equality: wall time is physical,
# and serving.compiled counts blocks/ticks only the compiled server has
COUNTER_EXCLUDES = ("serving.hop_wall_s", "serving.compiled")


# ---------------------------------------------------------------------------
# events
# ---------------------------------------------------------------------------


def per_stream(events, strip=("device",)):
    """Events grouped per stream id, with ``strip`` tags removed.

    The sharded server tags each event with the device that produced it;
    per-stream equivalence against a single-device oracle compares every
    OTHER field, in per-stream order (global order across streams is a
    scheduling artifact, per-stream order is the contract)."""
    out = {}
    for ev in events:
        e = {k: v for k, v in ev.items() if k not in strip}
        out.setdefault(e.pop("stream"), []).append(e)
    return out


def assert_events_equal(ev_a, ev_b, what="", by_stream=False,
                        strip=("device",)):
    """Assert two event lists are identical, field for field.

    ``by_stream=False`` (the default) demands the exact same global event
    order — right when both sides run the same scheduler.  ``by_stream=
    True`` compares each stream's own event sequence after stripping
    ``strip`` tags — right when a sharded fleet's pools interleave
    differently than the oracle but every stream must still see the same
    decisions.  Returns the per-stream grouping of ``ev_a``."""
    if by_stream:
        pa, pb = per_stream(ev_a, strip), per_stream(ev_b, strip)
        assert pa.keys() == pb.keys(), \
            f"{what}: stream sets differ: {sorted(pa)} vs {sorted(pb)}"
        for sid in pa:
            assert pa[sid] == pb[sid], f"{what}: stream {sid} diverged"
        return pa
    assert ev_a == ev_b, (f"{what}: event lists diverged "
                          f"({len(ev_a)} vs {len(ev_b)} events)")
    return per_stream(ev_a, strip)


# ---------------------------------------------------------------------------
# pytree state
# ---------------------------------------------------------------------------


def assert_leaves_equal(tree_a, tree_b, what=""):
    """Bitwise equality of every array leaf of two pytrees."""
    la = jax.tree_util.tree_leaves(tree_a)
    lb = jax.tree_util.tree_leaves(tree_b)
    assert len(la) == len(lb), \
        f"{what}: leaf count {len(la)} vs {len(lb)}"
    for i, (x, y) in enumerate(zip(la, lb)):
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y),
            err_msg=f"{what}: leaf {i} diverged")


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def counter_cells(srv, exclude=COUNTER_EXCLUDES):
    """The server's registry as a comparable ``{(name, labels): value}``
    dict; histogram cells flatten to ``(count, total, min, max)``."""
    out = {}
    for (name, labels), cell in srv._metrics._cells.items():
        if name in exclude:
            continue
        if hasattr(cell, "count"):          # histogram cell
            out[(name, labels)] = (cell.count, cell.total,
                                   cell.min, cell.max)
        else:
            out[(name, labels)] = cell
    return out


def assert_counters_equal(srv_a, srv_b, what="",
                          exclude=COUNTER_EXCLUDES):
    ca, cb = counter_cells(srv_a, exclude), counter_cells(srv_b, exclude)
    diff = {k: (ca.get(k), cb.get(k))
            for k in set(ca) | set(cb) if ca.get(k) != cb.get(k)}
    assert not diff, f"{what}: counter cells diverged: {diff}"


# ---------------------------------------------------------------------------
# whole-server comparison + lockstep driving
# ---------------------------------------------------------------------------


def assert_server_equal(srv_a, srv_b, what="", counters=True):
    """Full carry-state comparison between two StreamServers: stream
    rings, decision heads, VAD state, and (optionally) every registry
    cell outside :data:`COUNTER_EXCLUDES`."""
    assert_leaves_equal(srv_a._state, srv_b._state, f"{what} [stream]")
    assert_leaves_equal(srv_a._dstate, srv_b._dstate, f"{what} [decision]")
    assert (srv_a._vstate is None) == (srv_b._vstate is None), \
        f"{what}: VAD state presence differs"
    if srv_a._vstate is not None:
        assert_leaves_equal(srv_a._vstate, srv_b._vstate, f"{what} [vad]")
    if counters:
        assert_counters_equal(srv_a, srv_b, what)


def advance_to(srv, ticks):
    """Advance a server to an absolute tick count, via the compiled block
    path when one is attached (``step_block`` never overshoots ``ticks``)
    and the interpreted ``step`` otherwise.  Returns the events."""
    events = []
    if getattr(srv, "_compiled", None) is not None:
        while srv._steps < ticks:
            events.extend(srv.step_block(max_ticks=ticks - srv._steps))
    else:
        while srv._steps < ticks:
            events.extend(srv.step())
    return events


def pool_devices(n):
    """An explicit device list for an ``n``-pool sharded fleet: the first
    ``n`` devices, reusing them in turn when fewer exist, so one CPU
    device hosts ``n`` logical pools (``ShardedStreamServer(devices=n)``
    itself refuses to run on fewer than ``n`` devices)."""
    avail = jax.devices()
    return [avail[d % len(avail)] for d in range(n)]
