"""Observability: call-lifecycle tracing + metric series for the framework.

The pieces (all stdlib-only — core/ imports this layer and must stay
jax-free):

- :mod:`.catalog` — the ONE place every ``mtpu_*`` metric name is declared
  (enforced by ``tests/test_static.py``);
- :mod:`.trace`   — span model, per-call JSONL trace files, cross-process
  context propagation (``tpurun trace <call_id>`` reads these);
- :mod:`.reqtrace` — request-scoped DISTRIBUTED tracing over the serving
  fleet: one trace id per request (== the request id) stitched across
  gateway, scheduler queues, router, prefill/decode replicas, and the
  disagg page-migration wire (``tpurun explain <request_id>``);
- :mod:`.metrics` — recorder functions the executor/engine call to emit
  catalog series into the prometheus registry;
- :mod:`.export`  — file-backed push gateway for ephemeral processes
  (``tpurun metrics`` merges the pushed expositions) + the Perfetto /
  chrome://tracing converter (``tpurun trace <id> --perfetto``);
- :mod:`.journal` — the autoscaler decision journal (``tpurun scaler``,
  gateway ``/autoscaler``);
- :mod:`.slo`     — declared latency/error targets evaluated against the
  live histograms (gateway ``/healthz``, ``tpurun top``).

User code inside a remote function can nest its own spans::

    from modal_examples_tpu.observability import span

    @app.function()
    def work(x):
        with span("load-model"):
            ...
"""

from __future__ import annotations

from . import catalog
from .export import (
    export_chrome_trace,
    live_and_pushed_metrics,
    push_metrics_file,
    pushed_jobs,
    read_pushed_metrics,
    spans_to_chrome_trace,
)
from . import alerts
from . import incident
from . import timeseries
from .alerts import DEFAULT_RULES, AlertEvaluator, AlertRule
from .incident import capture as capture_incident, list_incidents
from .journal import (
    JOURNALS,
    DecisionJournal,
    default_journal,
    named_journal,
)
from .timeseries import TsdbSampler, ensure_sampler, read_window
from .metrics import (
    record_container_kill,
    record_engine_batch,
    record_engine_queue_wait,
    record_phase,
    record_prefix_evictions,
    record_queue_wait,
    record_retry,
    record_scaler_decision,
    record_scheduler_error,
    record_snapshot_store_get,
    record_token_totals,
    record_tpot,
    record_ttft,
    sample_host_rss,
    set_engine_gauges,
    set_inflight,
    set_kv_occupancy,
    set_prefix_cache_pages,
    set_snapshot_store_size,
)
from . import profiler
from .profiler import HotPathProfiler
from . import reqtrace
from .reqtrace import explain_lines, finish_request, start_request_trace
from .slo import DEFAULT_SLOS, SLO, evaluate as evaluate_slos, healthz
from .trace import (
    Span,
    TraceContext,
    TraceStore,
    current_context,
    current_trace_id,
    default_store,
    set_context,
    span,
    tracing_enabled,
)

__all__ = [
    "DEFAULT_RULES",
    "DEFAULT_SLOS",
    "AlertEvaluator",
    "AlertRule",
    "DecisionJournal",
    "HotPathProfiler",
    "JOURNALS",
    "TsdbSampler",
    "alerts",
    "capture_incident",
    "ensure_sampler",
    "incident",
    "list_incidents",
    "named_journal",
    "read_window",
    "timeseries",
    "SLO",
    "Span",
    "TraceContext",
    "TraceStore",
    "catalog",
    "current_context",
    "current_trace_id",
    "default_journal",
    "default_store",
    "evaluate_slos",
    "explain_lines",
    "export_chrome_trace",
    "finish_request",
    "healthz",
    "live_and_pushed_metrics",
    "push_metrics_file",
    "pushed_jobs",
    "read_pushed_metrics",
    "record_container_kill",
    "record_engine_batch",
    "record_engine_queue_wait",
    "record_phase",
    "record_prefix_evictions",
    "record_queue_wait",
    "record_retry",
    "record_scaler_decision",
    "record_scheduler_error",
    "record_snapshot_store_get",
    "record_token_totals",
    "record_tpot",
    "record_ttft",
    "profiler",
    "reqtrace",
    "sample_host_rss",
    "set_context",
    "start_request_trace",
    "set_engine_gauges",
    "set_inflight",
    "set_kv_occupancy",
    "set_prefix_cache_pages",
    "set_snapshot_store_size",
    "span",
    "spans_to_chrome_trace",
    "tracing_enabled",
]
