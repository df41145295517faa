"""Scheduling observability: metrics registry, device counters, traces.

Three tiers, cheapest first:

1. **On-device counters** (``obs.device``): a small int64 metrics
   vector accumulated inside the kernels that are already running
   (``engine.fastpath`` epoch scans, ``engine.kernels.engine_run``) and
   drained with the existing decision fetch -- zero extra device round
   trips, and gated so the decision stream is bit-identical with
   metrics on or off (pinned by ``tests/test_obs.py``).
2. **Host metrics registry** (``obs.registry``): counters / gauges /
   histograms / timer wrappers with Prometheus text exposition and a
   JSON snapshot.  The sim harness, the host scheduler queues, and the
   distributed tracker register their hot-path stats into it.
3. **Decision trace + QoS conformance** (``obs.trace``,
   ``sim.harness.SimReport.conformance``): a bounded JSONL trace of
   scheduling decisions and an end-of-run per-client conformance table
   (delivered rate vs reservation/weight/limit).

Plus the device telemetry plane (``obs.histograms``, ``obs.flight``):
log2-bucketed latency/tardiness/stall/commit-size histograms and a
per-client conformance ledger accumulated inside the epoch scans, and
an HBM flight recorder of the last R commit records drained only at
epoch/checkpoint boundaries -- distributions in the data path, not the
control path.

And the time-domain tracing plane (``obs.spans``,
``obs.trace_export``, ``obs.watchdog``): a thread-safe ns-resolution
host span tracer (nested spans, fixed category set, bounded
ring), Chrome trace-event / Perfetto export so any run produces a
``chrome://tracing``-loadable timeline, and a steady-state watchdog
that warns on launch-cadence stalls and dispatch-share breaches.
Spans are host-side only, never in-graph -- decisions are
bit-identical with tracing on or off.

And the capacity plane (``obs.compile_plane``, ``obs.capacity``): an
instrumented jit-cache wrapper adopted by every module-level jit cache
(per-entry lower+compile wall, retraces with the arg-signature diff
that caused them, ``cost_analysis`` flops/bytes, ``memory_analysis``
HBM breakdown -- exported as ``dmclock_compile_*`` families and as
``compile``-category spans), a static HBM footprint ledger over the
live state pytrees with a ``plan_capacity()`` inverse (max clients
per chip for a budget and knob setting), and a roofline attributor
classifying workloads compute-/memory-/dispatch-bound.

See ``docs/OBSERVABILITY.md`` for metric names and schemas.
"""

from .registry import (Counter, Gauge, Histogram, MetricsHTTPServer,
                       MetricsRegistry, TimerMetric, default_registry,
                       publish_span_gauges, start_http_server)
from .trace import DecisionTrace, validate_trace_file
from .spans import SpanTracer
from .trace_export import export_chrome_trace, validate_chrome_trace
from .watchdog import Watchdog
from .slo import SloPlane
from .alerts import SloEvaluator, mount_slo_api
from .compile_plane import (CompilePlane, instrumented_jit,
                            publish_compile_metrics)
from .compile_plane import plane as compile_plane_singleton
from . import alerts, capacity, compile_plane, device, flight, \
    histograms, provenance, slo, spans, trace_export

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "TimerMetric",
    "default_registry", "MetricsHTTPServer", "start_http_server",
    "publish_span_gauges",
    "DecisionTrace", "validate_trace_file",
    "SpanTracer", "export_chrome_trace", "validate_chrome_trace",
    "Watchdog", "SloPlane", "SloEvaluator", "mount_slo_api",
    "CompilePlane", "instrumented_jit", "publish_compile_metrics",
    "compile_plane_singleton",
    "alerts", "capacity", "compile_plane", "device", "flight",
    "histograms", "provenance", "slo", "spans", "trace_export",
]
