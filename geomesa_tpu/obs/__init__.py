"""Unified observability: tracing + quantile metrics + recompile watch.

The cross-cutting layer ISSUE 5 adds so a slow query on a 1B-row lean
store decomposes into plan / range-decomposition / device dispatch /
host-spill scan / cache-miss time instead of one opaque number:

* :mod:`.trace` — Dapper-style spans with contextvar propagation,
  always/ratio/slow samplers, ring + JSONL exporters, a slow-query log,
  and the :func:`device_span` helper that attributes each dispatch's
  enqueue and block-until-ready wait to the owning query;
* :mod:`.recompile` — the XLA recompile tracker (jax.monitoring
  listener + wrapped-jit fallback) that turns silent retraces into
  ``jax.compile.*`` metrics and span attributes;
* :mod:`.prom` — Prometheus text exposition over metric snapshots
  (p50/p95/p99 from the log-bucketed histograms in metrics.py);
* :mod:`.resource` — storage/HBM accounting (ISSUE 9): the
  ``storage.*`` gauges, the ``/debug/storage`` report, and the
  accounted-vs-actual-nbytes reconciliation audit;
* :mod:`.explain_analyze` — EXPLAIN ANALYZE: the plan narration
  merged with measured actuals (estimate vs rows scanned/matched,
  per-phase ms), served at ``/explain``;
* :mod:`.heat` — access-temperature tracking (ISSUE 12): per-(schema,
  index, generation) touch counters decayed into a temperature score,
  the ranked hot→cold ``/debug/heat`` report joined with storage
  placement, and the ``heat.*`` gauges — the workload data plane the
  tier autopilot consumes;
* :mod:`.jobs` — the background-job registry (ISSUE 12):
  ingest/compaction runs with phase spans, progress, and terminal
  outcomes, served at ``/debug/jobs``.

Everything configures through the ``geomesa.obs.*`` system properties
(config.ObsProperties); docs/observability.md is the operator contract.
"""

from __future__ import annotations

from ..config import ObsProperties
from .explain_analyze import (
    ExplainAnalyzeResult, explain_analyze, explain_analyze_sql,
)
from .heat import (
    HeatTracker, heat_enabled, heat_report, heat_tracker,
    merge_index_generations, publish_heat_gauges, record_index_scan,
)
from .jobs import JobRecord, JobRegistry, jobs_registry
from .prom import prometheus_text
from .recompile import compile_count, counting_jit, install as \
    install_recompile_tracker
from .attribution import STAGES as SLO_STAGES, attribute
from .resource import publish_storage_gauges, storage_report
from .slo import ExemplarHistogram, Objective, SloPlane, slo_plane
from .trace import (
    AlwaysSampler, JsonlExporter, NeverSampler, RatioSampler,
    RingExporter, Sampler, SlowOnlySampler, Span, Trace, Tracer,
    current_span, current_trace_id, device_inflight, device_span, obs_count,
    scan_work, span, tracer,
)

__all__ = ["Span", "Trace", "Tracer", "Sampler", "AlwaysSampler",
           "NeverSampler", "RatioSampler", "SlowOnlySampler",
           "RingExporter", "JsonlExporter", "tracer", "span",
           "device_span", "device_inflight", "scan_work", "current_span",
           "current_trace_id", "obs_count", "prometheus_text", "compile_count", "counting_jit",
           "install_recompile_tracker",
           "storage_report", "publish_storage_gauges",
           "ExplainAnalyzeResult", "explain_analyze",
           "explain_analyze_sql",
           "HeatTracker", "heat_tracker", "heat_enabled",
           "record_index_scan", "merge_index_generations",
           "heat_report", "publish_heat_gauges",
           "JobRecord", "JobRegistry", "jobs_registry",
           "SloPlane", "slo_plane", "ExemplarHistogram", "Objective",
           "SLO_STAGES", "attribute"]

# the recompile listener is process-global and effectively free — hook
# it as soon as observability loads (gated by the option so fully
# instrumentation-silent runs stay possible)
if ObsProperties.RECOMPILE_TRACK.to_bool():
    install_recompile_tracker()

# the SLO plane feeds off finished root traces; the hook itself
# fast-exits when geomesa.slo.enabled is off, so wiring it
# unconditionally costs one list iteration per finished trace
tracer.add_finish_hook(slo_plane.on_trace_finish)
