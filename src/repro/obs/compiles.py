"""Process-wide tally of JAX traces, compiles and persistent-cache loads.

One ``jax.monitoring`` listener pair, registered on first use, counts:

* ``trace``: a function traced to a jaxpr (a jit cache miss);
* ``compile``: a program built by the backend compiler;
* ``cache_load``: a program loaded from the persistent compilation cache
  instead (JAX reports the backend-compile event for it too; it is counted
  here and not as a ``compile``).

``StreamServer`` reads ``tally()`` around each ``step()`` /
``step_block()`` and books the change into its registry as
``serving.compiles{kind}``, so a compile shows up against the step that
caused it.  In a warmed-up server it stays put.
"""

from __future__ import annotations

import threading
from typing import Tuple

import jax

__all__ = ["KINDS", "tally"]

KINDS = ("trace", "compile", "cache_load")

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_lock = threading.Lock()
_counts = [0, 0, 0]          # in KINDS order
_unmatched_hits = [0]        # cache hits whose compile event is still due
_installed = [False]


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT_EVENT:
        with _lock:
            _counts[2] += 1
            _unmatched_hits[0] += 1


def _on_duration(event: str, _secs: float, **_kw) -> None:
    if event == _TRACE_EVENT:
        with _lock:
            _counts[0] += 1
    elif event == _COMPILE_EVENT:
        with _lock:
            if _unmatched_hits[0]:
                _unmatched_hits[0] -= 1
            else:
                _counts[1] += 1


def install() -> None:
    """Register the listeners (once per process)."""
    with _lock:
        if _installed[0]:
            return
        _installed[0] = True
    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


def tally() -> Tuple[int, int, int]:
    """(traces, compiles, cache loads) so far in this process."""
    return tuple(_counts)
