"""Observability for the serving stack (``repro.obs``).

The pieces, all optional except the registry and the spans:

* :class:`MetricsRegistry` — labelled counters/gauges/histograms; the
  serving classes' ``stats()`` dicts are views over one shared registry,
  and snapshots round-trip it (always on: it *is* the counter storage).
* :class:`FlightRecorder` — bounded ring of structured per-tick events,
  dumpable on alarm/crash and included in ``StreamServer.snapshot()``.
* :class:`LaunchAuditor` — opt-in runtime interceptor enforcing the
  one-fused-launch-per-IMC-layer-per-tick contract, with ``flag`` and
  ``raise`` modes.
* :func:`span` — ``serving.*`` host spans on the JAX profiler's clock,
  always on (``jax.profiler.trace`` records them beside the device's
  ops; without a profiler session they record nothing).
* :mod:`compiles` — a process-wide tally of jit traces, backend compiles
  and persistent-cache loads, booked per step as ``serving.compiles``.

``ObsConfig`` selects which extras a ``StreamServer`` turns on; the
default (all off) is bit-identical to — and within noise as fast as —
the pre-telemetry server.  ``ObsConfig.from_env()`` reads
``REPRO_OBS_AUDIT`` / ``REPRO_OBS_RECORDER`` so CI can flip the auditor
on without touching call sites.
"""

from __future__ import annotations

import os
from dataclasses import dataclass

from . import compiles
from .audit import AUDIT_MODES, LaunchAuditError, LaunchAuditor
from .metrics import MetricsRegistry, counter_property
from .recorder import FlightRecorder
from .trace import span

__all__ = [
    "AUDIT_MODES",
    "FlightRecorder",
    "LaunchAuditError",
    "LaunchAuditor",
    "MetricsRegistry",
    "ObsConfig",
    "compiles",
    "counter_property",
    "span",
]


@dataclass(frozen=True)
class ObsConfig:
    """What telemetry a ``StreamServer`` runs beyond the registry.

    recorder   flight-recorder ring capacity in events; 0 disables it.
    audit      launch-auditor mode: "off", "flag" or "raise".
    """

    recorder: int = 0
    audit: str = "off"

    def __post_init__(self):
        if self.audit not in AUDIT_MODES:
            raise ValueError(
                f"audit must be one of {AUDIT_MODES}, got {self.audit!r}")
        if self.recorder < 0:
            raise ValueError("recorder capacity must be >= 0")

    @classmethod
    def from_env(cls):
        """Build from ``REPRO_OBS_*`` env vars (read at call time)."""
        return cls(
            recorder=int(os.environ.get("REPRO_OBS_RECORDER", "0")),
            audit=os.environ.get("REPRO_OBS_AUDIT", "off"),
        )
