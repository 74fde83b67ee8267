"""Observability subsystem: tracing, metrics, workloads, SLOs, post-mortems.

`trace` times named spans along the request path (gateway submit ->
worker pump -> engine step -> jit dispatch -> device wait -> retire): as
`jax.profiler` annotations while a profile collects, as `jax.monitoring`
duration events for any listener, and, while armed, into a ring buffer
exported as Chrome trace events loadable in Perfetto; a shared no-op when
nothing observes it. `registry` unifies the per-silo metric
counters (gateway, kvcache, speculation, scheduler) behind one
`MetricsRegistry` whose `snapshot()` is the single serving-telemetry
dict — see `Gateway.snapshot()` and `core.reporting.unified_dashboard`.

On top of those instruments sit the production-shaped pieces: `workload`
generates/replays seeded multi-tenant traces (heavy-tailed lengths,
diurnal bursts, priority tiers), `slo` judges every request against its
tier's latency targets live, and `flight` is the anomaly flight recorder
that dumps the evidence rings to a Perfetto file when an SLO breach,
illegal lifecycle transition, replica failure, or shed spike fires.

The continuous-telemetry layer turns those point-in-time instruments
into series and exposition: `timeseries` runs the sampler thread pulling
`snapshot()` into ring-buffered series with windowed aggregates,
`export` renders OpenMetrics text (with a strict in-repo parser) and
serves it from a stdlib-HTTP endpoint, and `ledger` attributes each
engine dispatch's measured device time across tenants by token share.
"""
from repro.obs import trace
from repro.obs import workload
from repro.obs.registry import (Counter, Gauge, Histogram, MetricsRegistry,
                                DEFAULT_BUCKETS)
from repro.obs.slo import (DEFAULT_TIER_SLOS, SLOSpec, SLOTracker, load_slos,
                           save_slos)
from repro.obs.flight import FlightRecorder
from repro.obs.timeseries import TimeSeriesSampler, flatten_numeric
from repro.obs.export import (MetricsServer, OpenMetricsParseError,
                              openmetrics_text, parse_openmetrics)
from repro.obs.ledger import UtilizationLedger

__all__ = ["Counter", "DEFAULT_BUCKETS", "DEFAULT_TIER_SLOS",
           "FlightRecorder", "Gauge", "Histogram", "MetricsRegistry",
           "MetricsServer", "OpenMetricsParseError", "SLOSpec", "SLOTracker",
           "TimeSeriesSampler", "UtilizationLedger", "flatten_numeric",
           "load_slos", "openmetrics_text", "parse_openmetrics", "save_slos",
           "trace", "workload"]
