"""Cluster-wide observability: tracing, typed metrics, trace export.

The paper's hardware engine is a black box once an operation is
initiated — real GASNet grew ``GASNET_TRACE`` operation tracing for
exactly that reason, and ACCL+ instruments its collective engine with
hardware performance counters.  This package is our software analogue:

- :mod:`repro.obs.metrics` — a typed Counter/Gauge/Histogram registry
  that the serving ``stats()`` dicts are built on (explicit kinds, a
  ``reset()`` that only clears counters).
- :mod:`repro.obs.trace` — a per-rank span/event tracer clocked on the
  SPMD tick counter.  Spans are host-side (around initiation and sync,
  never inside compiled code) and ring-buffered; scoped spans also
  land in the JAX profiler's trace, beside the device's operations.
  Disabled, a scoped span costs about a microsecond and every other
  site is guarded by one attribute check on a no-op recorder.
- :mod:`repro.obs.export` — merges per-rank streams on the tick clock
  into Chrome-trace JSON (load in ``chrome://tracing`` / Perfetto) and
  dumps a flight-recorder ring of the last N ticks on rank death.
- :mod:`repro.obs.attrib` — per-request critical-path attribution over
  the recorded lifecycle instants; ``why_slow(rid)`` names the dominant
  segment and the convoying co-residents.
- :mod:`repro.obs.health` — live SLO monitor on the tick clock:
  deadline-risk gauges, ``slo_at_risk``/``slo_violated`` instants, and
  the admission backpressure floor.

Nothing here imports the rest of ``repro`` — core and serving layers
import ``obs``, never the other way around.
"""
from repro.obs import attrib, export, health, metrics, trace
from repro.obs.attrib import Breakdown, attribute, why_slow
from repro.obs.health import HealthMonitor
from repro.obs.metrics import Counter, Gauge, Histogram, Registry
from repro.obs.trace import (
    NullTracer,
    Span,
    Tracer,
    active,
    disable,
    enable,
    span,
)

__all__ = [
    "Breakdown",
    "Counter",
    "Gauge",
    "HealthMonitor",
    "Histogram",
    "NullTracer",
    "Registry",
    "Span",
    "Tracer",
    "active",
    "attribute",
    "attrib",
    "disable",
    "enable",
    "export",
    "health",
    "metrics",
    "span",
    "trace",
    "why_slow",
]
