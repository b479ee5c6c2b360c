"""znicz_tpu.observe — the unified telemetry plane (ISSUE 5 + 6).

One process-global metrics registry (``registry.REGISTRY``: Counter /
Gauge / Histogram with labels, dict snapshots, shared quantile
estimation, Prometheus text exposition), one bounded-ring span tracer
(``trace.TRACER``: ``span()`` / ``instant()`` / Chrome-trace export),
the fixed instrumentation hooks the runtime calls (``probe``: per-step
timing, recompile detection, cold-compile timing, staged-bytes
accounting, resilience events), the watchtower (``watchtower.
WATCHTOWER``: retained time-series ring + declarative SLO rules
evaluated by the sampler), the flight recorder (``flight``:
atomic crash post-mortem artifacts), and the fleet federation plane
(``federation``: rank-labeled cross-process metric aggregation,
``/fleet/*`` endpoints, merged distributed traces — ISSUE 11).

Scrape surfaces: ``WebStatus`` serves ``GET /metrics`` (Prometheus
text), ``GET /trace.json`` (ring dump) and ``GET /timeseries.json``
(watchtower delta ring); ``python -m znicz_tpu trace out.json
workflow.py`` exports a run's timeline; ``python -m znicz_tpu flight
artifact.json`` pretty-prints a flight.  Metric name catalogue:
docs/OBSERVABILITY.md (statically checked by
tools/check_metric_catalogue.py).
"""

from znicz_tpu.observe.registry import (REGISTRY, Registry, counter,
                                        gauge, histogram,
                                        quantile_from_buckets)
from znicz_tpu.observe.trace import (TRACER, Tracer, export_trace,
                                     instant, span)
from znicz_tpu.observe.probe import (check_recompiles,
                                     compile_cache_event,
                                     compile_cache_stats,
                                     compile_observed,
                                     enabled, resilience_event,
                                     set_enabled, staged_bytes,
                                     time_compiles, watch_compiles)
from znicz_tpu.observe.anatomy import StepCadence, observe_phase
from znicz_tpu.observe.watchtower import (WATCHTOWER, Rule,
                                          TimeSeriesRing, Watchtower)
from znicz_tpu.observe import flight
from znicz_tpu.observe import federation
from znicz_tpu.observe.federation import (FleetAggregator,
                                          MetricsExporter, merge_traces,
                                          next_request_id,
                                          start_metrics_export)

__all__ = ["REGISTRY", "Registry", "counter", "gauge", "histogram",
           "quantile_from_buckets",
           "TRACER", "Tracer", "span", "instant", "export_trace",
           "set_enabled", "enabled", "watch_compiles",
           "check_recompiles", "staged_bytes", "resilience_event",
           "compile_observed", "time_compiles",
           "compile_cache_event", "compile_cache_stats",
           "StepCadence", "observe_phase",
           "WATCHTOWER", "Watchtower", "Rule", "TimeSeriesRing",
           "flight", "federation", "FleetAggregator", "MetricsExporter",
           "merge_traces", "next_request_id", "start_metrics_export"]
