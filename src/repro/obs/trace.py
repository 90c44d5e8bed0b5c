"""Host spans on the JAX profiler's clock.

``span(name, **args)`` enters ``jax.profiler.TraceAnnotation("serving." +
name, **args)``.  With no profiler session active it records nothing and
costs well under a microsecond; under ``jax.profiler.trace(dir)`` each
span lands on its host thread's line of the same trace that holds the
device's ops, so one timeline shows a tick's host phases next to the
programs they dispatched and the idle gaps between them.  Spans are always
on: a profiled server runs the very program that is served.

The serving stack's spans (``docs/OBSERVABILITY.md`` has the catalogue):

* ``serving.step`` (args ``ticks``, ``slots``; ``device`` in a sharded
  pool) around ``StreamServer.step()`` / ``step_block()``;
* the compiled block (``repro.serving.compiled``): ``horizon``, ``stage``,
  ``vad``, ``fate``, ``dispatch``, ``fetch`` (the host waiting on the
  device) and ``book``;
* the interpreted tick: ``admit``, ``replay``, ``hop``, ``gate``,
  ``decide`` and ``riders``;
* ``serving.fleet_step`` (arg ``pools``) around a sharded router's tick.

Keep arguments to the outer spans: each one is a keyword the caller
builds even when no profiler listens.
"""

from __future__ import annotations

import jax

__all__ = ["SPAN_PREFIX", "span"]

SPAN_PREFIX = "serving."


def span(name: str, **args):
    """A profiler span ``serving.<name>`` (a context manager)."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **args)
