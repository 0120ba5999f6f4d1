"""repro.obs — the observability plane (flight recorder subsystem).

SELCC's core claim is coherence with NO remote compute, so the compute
side is the only place the system can be observed — and before this
package that observation was fragmented: rich carry-accumulated
counters on sharded verbs, ``{}`` on flat ones, and four incompatible
ad-hoc stat dicts across the serving/txn/index layers.  ``repro.obs``
unifies it:

* :class:`PlaneTelemetry` — the typed per-dispatch counter record every
  fused driver (flat AND sharded) now returns, diff-able bit-for-bit
  between planes on the same op trace;
* :class:`FlightRecorder` — a bounded span ring attached to
  ``DevicePlane`` / ``ServeLoop``: one :class:`Span` per verb dispatch,
  plus EWMA line/home heat for online placement, and
  ``recorder.snapshot()`` (bench ``meta.telemetry``);
* :class:`MetricsRegistry` — counters / gauges /
  :class:`StreamingHistogram` (log-bucketed p50/p99 without samples)
  with Prometheus text exposition (``render_prom()``), for scraping a
  serving loop.

Every verb's one bracket (``DevicePlane._span``) opens a
``plane.<verb>`` ``jax.profiler.TraceAnnotation`` with fixed child
spans (``plane.pad``/``dispatch``/``sync``/``telemetry``/``results``),
so a profiler trace puts each dispatch on the device's clock; the same
bracket feeds an attached recorder.  Neither enters a trace, so
``engine.TRACE_COUNTS`` proves they add zero compiled code.
"""

from .metrics import (Counter, EwmaHeat, Gauge, MetricsRegistry,
                      StreamingHistogram)
from .recorder import FlightRecorder, Span
from .telemetry import TELEMETRY_READS, PlaneTelemetry

__all__ = [
    "Counter", "EwmaHeat", "FlightRecorder", "Gauge",
    "MetricsRegistry", "PlaneTelemetry", "Span", "StreamingHistogram",
    "TELEMETRY_READS",
]
