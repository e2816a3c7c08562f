"""cylon_tpu.telemetry — unified metrics: registry, exporters, fleet view.

One process-local, thread-safe registry of typed instruments
(:class:`Counter` / :class:`Gauge` / :class:`Histogram` /
:class:`Timer`) with label support replaces the three disjoint
registries the rebuild had grown (``tracing`` span stats, the
watchdog's section-timing deque, ad-hoc bench dicts). Hot layers
instrument through module helpers::

    from cylon_tpu import telemetry

    telemetry.counter("exchange.bytes_true", op="dist_join").inc(nb)
    with telemetry.timer("barrier.wait_seconds").time():
        ...
    snap = telemetry.snapshot()          # in-process, for tests
    fleet = telemetry.gather_metrics(env)  # merged across ranks

Design contract (mirrors the watchdog's fast path): with no exporter
configured — ``CYLON_TPU_METRICS_DIR`` unset — instrumentation is dict
updates only; no thread starts, no file opens. Exporters
(:mod:`cylon_tpu.telemetry.export`): JSONL snapshot lines + a
Prometheus text dump per process, armed lazily off the env knob.

The ops plane on top (ISSUE 9): :mod:`cylon_tpu.telemetry.memory`
(HBM live-bytes gauges, per-op peak watermarks, OOM forensics) and
:mod:`cylon_tpu.telemetry.profile` (per-query EXPLAIN plans and the
per-request ANALYZE profiles ``QueryTicket.profile()`` serves), both
read live by :mod:`cylon_tpu.serve.introspect`'s HTTP endpoint.

The event-level half is :mod:`cylon_tpu.telemetry.trace` — the
``CYLON_TPU_TRACE`` flight recorder: per-rank span/instant/counter
timelines, Chrome Trace export (:func:`to_chrome_trace` /
:func:`write_chrome_trace`), clock-aligned cross-rank merge
(:func:`gather_traces` + ``trace.merge_timelines``) and critical-path
straggler attribution (``trace.critical_path``). Same
no-overhead-when-off contract. See ``docs/observability.md``.
"""

from cylon_tpu.telemetry import events, memory, profile, timeseries, trace
from cylon_tpu.telemetry.aggregate import (gather_metrics,
                                           gather_traces,
                                           merge_snapshots)
from cylon_tpu.telemetry.export import (PEAKS, REQUIRED_BENCH_KEYS,
                                        bench_metrics,
                                        chrome_trace_json,
                                        fraction_of_peak,
                                        json_safe,
                                        metrics_dir, snapshot_to_json,
                                        to_chrome_trace, to_prometheus,
                                        write_chrome_trace,
                                        write_snapshot)
from cylon_tpu.telemetry.registry import (BUCKET_BOUNDS, Counter, Gauge,
                                          Histogram, MetricRegistry,
                                          Timer, add_record, counter,
                                          current_tenant, delta, gauge,
                                          get_records, histogram,
                                          instruments, merge_histograms,
                                          metric, registry, reset,
                                          snapshot, tenant_labels,
                                          tenant_scope, timer, total)

__all__ = [
    "BUCKET_BOUNDS", "Counter", "Gauge", "Histogram", "Timer",
    "MetricRegistry", "registry", "counter", "gauge", "histogram",
    "timer", "metric", "instruments", "snapshot", "delta", "reset",
    "total", "add_record", "get_records", "merge_snapshots",
    "gather_metrics", "gather_traces", "json_safe", "snapshot_to_json",
    "to_prometheus", "metrics_dir", "write_snapshot", "bench_metrics",
    "REQUIRED_BENCH_KEYS", "PEAKS", "fraction_of_peak", "trace",
    "to_chrome_trace", "chrome_trace_json", "write_chrome_trace",
    "tenant_scope", "current_tenant", "tenant_labels",
    "merge_histograms", "memory", "profile", "events", "timeseries",
]
