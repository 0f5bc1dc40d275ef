"""repro.obs — zero-dependency observability layer (DESIGN.md §13).

Three pieces, one discipline: the numbers production discloses are the
numbers the benches disclose.

* :mod:`repro.obs.trace` — flight-recorder spans with Chrome/Perfetto
  ``trace.json`` export, threaded through façade → backend → kernel and
  the serving/durability paths; stage spans inside a served launch feed
  always-on counters and, while a JAX profiler session collects, the
  profiler's own trace.
* :mod:`repro.obs.counters` — the per-launch kernel byte/tile ledger
  (:class:`~repro.obs.counters.LaunchReport`) and the §12 bench's
  accounting functions, now shared by bench and production.
* :mod:`repro.obs.metrics` — a Prometheus-text / JSON metrics registry
  snapshotting ``AccessStats`` + serve telemetry with per-tenant labels.

This package imports nothing from the rest of ``repro`` (only numpy and
the stdlib), so every layer may depend on it without cycles.
"""

from repro.obs import counters, metrics, trace
from repro.obs.counters import (
    LaunchReport,
    collect_launch_reports,
    merge_reports,
)
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import (
    Tracer,
    counter,
    disable,
    drain_counters,
    enable,
    get_tracer,
    instant,
    set_tracer,
    span,
    stage,
)

__all__ = [
    "LaunchReport",
    "MetricsRegistry",
    "Tracer",
    "collect_launch_reports",
    "counter",
    "counters",
    "disable",
    "drain_counters",
    "enable",
    "get_tracer",
    "instant",
    "merge_reports",
    "metrics",
    "set_tracer",
    "span",
    "stage",
    "trace",
]
