"""Structured telemetry: metrics, manifests, events, spans.

The pieces (SURVEY section 5 "observability"):

- :mod:`sagecal_tpu.obs.registry` — host-side counters/gauges/histograms
  with Prometheus text export; a shared no-op registry when telemetry is
  off so instrumented call sites never branch.
- :mod:`sagecal_tpu.obs.events` — ``RunManifest`` + append-only JSONL
  event log (``SAGECAL_TELEMETRY=1`` / ``SAGECAL_EVENT_LOG=...``).
- :mod:`sagecal_tpu.obs.perf` — performance observability:
  ``instrumented_jit`` compile/recompile tracking, device-memory
  watermarks, the transfer-guard audit, and the bench regression gate.
- :mod:`sagecal_tpu.obs.contracts` — opt-in ``SAGECAL_CHECKIFY=1``
  runtime contracts: checkify NaN/div/index checks on every
  ``instrumented_jit`` entry, surfaced as ``contract_violation``
  events (CLI exit 4).
- :mod:`sagecal_tpu.obs.trace` — hierarchical execution spans
  (``SAGECAL_TRACE=1``): span-tree JSONL + Chrome-trace export, ADMM
  per-band straggler attribution.
- :mod:`sagecal_tpu.obs.flight` — in-process flight recorder
  (``SAGECAL_FLIGHT=1``): bounded activity ring, heartbeat file, hang
  watchdog, and crash handlers dumping all-thread stacks.
- :mod:`sagecal_tpu.obs.devprof` — device-profiler capture
  (``SAGECAL_DEVICE_PROFILE=dir`` / ``--device-profile``), the
  zero-dependency trace parser, and per-kernel-family attribution.
- :mod:`sagecal_tpu.obs.roofline` — per-``device_kind`` peak table,
  arithmetic-intensity classification, per-kernel MFU/BW-util.
- :mod:`sagecal_tpu.obs.evidence` — evidence classes (tpu-wallclock /
  cpu-wallclock / aot-bytes / aot-hlo) stamped on every banked metric;
  the gate/trend cross-evidence refusal logic.
- :mod:`sagecal_tpu.obs.diag` — the ``sagecal-tpu diag`` CLI.

This package root imports neither jax nor numpy (obs.perf defers its
jax imports to call time), so ``from sagecal_tpu.obs import
telemetry_enabled`` is safe anywhere, including before backend
selection.
"""

from sagecal_tpu.obs.registry import (  # noqa: F401
    MetricsRegistry,
    NullRegistry,
    get_registry,
    set_telemetry,
    telemetry,
    telemetry_enabled,
)
from sagecal_tpu.obs.events import (  # noqa: F401
    EventLog,
    RunManifest,
    default_event_log,
    read_events,
    read_events_merged,
    validate_manifest,
)
from sagecal_tpu.obs.trace import (  # noqa: F401
    NullTracer,
    Tracer,
    band_attribution,
    close_tracer,
    configure_tracer,
    get_tracer,
    read_spans,
    set_trace,
    straggler_stats,
    trace_enabled,
    write_chrome_trace,
)
from sagecal_tpu.obs.flight import (  # noqa: F401
    FlightRecorder,
    close_flight_recorder,
    flight_enabled,
    get_flight_recorder,
    install_crash_handlers,
    note_activity,
    set_flight,
)
from sagecal_tpu.obs.contracts import (  # noqa: F401
    ContractViolation,
    checkify_enabled,
    drain_contract_events,
    emit_contract_events,
)
from sagecal_tpu.obs.perf import (  # noqa: F401
    TransferAudit,
    append_bench_history,
    bench_trend,
    device_memory_snapshot,
    dump_memory_profile,
    emit_perf_events,
    instrumented_jit,
    read_bench_history,
    record_memory_watermark,
)
from sagecal_tpu.obs.aggregate import (  # noqa: F401
    check_lifecycle,
    dedupe_snapshots,
    fleet_view,
    lifecycle_report,
    merge_states,
    metrics_snapshot_path,
    quantile_bounds_from_state,
    read_metrics_snapshots,
    write_metrics_snapshot,
)
from sagecal_tpu.obs.devprof import (  # noqa: F401
    attribute_trace,
    classify_kernel,
    device_profile,
    last_trace_path,
    read_trace_events,
    start_device_profile,
    stop_device_profile,
)
from sagecal_tpu.obs.evidence import (  # noqa: F401
    EVIDENCE_CLASSES,
    metric_evidence,
    record_evidence,
    wallclock_evidence,
)
from sagecal_tpu.obs.roofline import (  # noqa: F401
    PEAK_TABLE,
    bw_util,
    lookup_peaks,
    mfu,
)
from sagecal_tpu.obs.slo import (  # noqa: F401
    SLOMonitor,
    SLOSpec,
    evaluate_results,
    format_slo_report,
    load_slo_specs,
)

# obs.quality names resolve lazily (PEP 562): the module needs numpy,
# and this package root must stay importable without it
_QUALITY_NAMES = (
    "DivergenceAbort",
    "abort_if_diverged",
    "analyze_events",
    "assess_consensus",
    "assess_quality",
    "check_and_emit",
    "quality_summary",
    "quality_to_host",
    "write_baseline_heatmap",
    "write_station_heatmap",
)


def __getattr__(name):
    if name in _QUALITY_NAMES:
        from sagecal_tpu.obs import quality as _quality

        return getattr(_quality, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


__all__ = [
    *_QUALITY_NAMES,
    "MetricsRegistry",
    "NullRegistry",
    "get_registry",
    "set_telemetry",
    "telemetry",
    "telemetry_enabled",
    "EventLog",
    "RunManifest",
    "default_event_log",
    "read_events",
    "read_events_merged",
    "validate_manifest",
    "NullTracer",
    "Tracer",
    "band_attribution",
    "close_flight_recorder",
    "close_tracer",
    "configure_tracer",
    "get_tracer",
    "read_spans",
    "set_trace",
    "straggler_stats",
    "trace_enabled",
    "write_chrome_trace",
    "FlightRecorder",
    "flight_enabled",
    "get_flight_recorder",
    "install_crash_handlers",
    "note_activity",
    "set_flight",
    "ContractViolation",
    "checkify_enabled",
    "drain_contract_events",
    "emit_contract_events",
    "TransferAudit",
    "append_bench_history",
    "bench_trend",
    "device_memory_snapshot",
    "dump_memory_profile",
    "emit_perf_events",
    "instrumented_jit",
    "read_bench_history",
    "record_memory_watermark",
    "check_lifecycle",
    "dedupe_snapshots",
    "fleet_view",
    "lifecycle_report",
    "merge_states",
    "metrics_snapshot_path",
    "quantile_bounds_from_state",
    "read_metrics_snapshots",
    "write_metrics_snapshot",
    "SLOMonitor",
    "SLOSpec",
    "evaluate_results",
    "format_slo_report",
    "load_slo_specs",
    "attribute_trace",
    "classify_kernel",
    "device_profile",
    "last_trace_path",
    "read_trace_events",
    "start_device_profile",
    "stop_device_profile",
    "EVIDENCE_CLASSES",
    "metric_evidence",
    "record_evidence",
    "wallclock_evidence",
    "PEAK_TABLE",
    "bw_util",
    "lookup_peaks",
    "mfu",
]
