"""Fleet-wide observability: metrics registries, the telemetry aggregator,
exporters (Prometheus / JSON / tensorboard), span tracing, the live
performance plane (MFU/FLOPs/recompiles/device memory + profiler capture),
the SLO engine, the learning-dynamics plane (in-jit algorithm
diagnostics with staleness-conditioned attribution — ``tpu_rl.obs.learn``),
and the run-history plane (embedded time-series store + ``/query`` +
anomaly detection — ``tpu_rl.obs.history``/``anomaly``, with the offline
``tpu_rl.obs.report`` / ``tpu_rl.obs.compare`` CLIs reading it back).

See ``docs/ARCHITECTURE.md`` ("Observability") for the data flow.
"""

from tpu_rl.obs.aggregator import (
    DEFAULT_STALE_AFTER_S,
    LEARNER_VERSION_GAUGE,
    STALENESS_HIST,
    TelemetryAggregator,
    maybe_aggregator,
)
from tpu_rl.obs.anomaly import (
    ANOMALY_LEVEL_SHIFTS_METRIC,
    ANOMALY_SPIKES_METRIC,
    AnomalyDetector,
)
from tpu_rl.obs.audit import append_jsonl, append_resume
from tpu_rl.obs.clocksync import ClockEstimate, ClockSync
from tpu_rl.obs.exporters import (
    JsonExporter,
    TelemetryHTTPServer,
    TensorboardExporter,
    render_healthz,
    render_prometheus,
)
from tpu_rl.obs.flightrec import FlightRecorder
from tpu_rl.obs.goodput import (
    BUCKETS,
    STRAGGLER_GAUGE,
    GoodputLedger,
    maybe_ledger,
    robust_z,
    straggler_report,
)
from tpu_rl.obs.history import (
    HistoryReader,
    TimeSeriesStore,
    channel_name,
    downsample,
    flatten_snapshots,
    history_path,
    maybe_history,
)
from tpu_rl.obs.learn import (
    BUCKET_GAUGE_PREFIX,
    GAUGE_PREFIX,
    N_STALE_BUCKETS,
    STALE_BUCKET_LABELS,
    DiagAccumulator,
    derive,
    ess_normalized,
    explained_variance,
    host_stale_rows,
    learn_record,
    publish,
    stale_bucket_index,
)
from tpu_rl.obs.merge import merge_result_dir, merge_traces
from tpu_rl.obs.perf import (
    PEAK_FLOPS,
    PerfTracker,
    ProfilerCapture,
    device_memory_books,
    device_memory_bytes,
    device_peak_flops,
    maybe_perf_tracker,
    process_self_stats,
)
from tpu_rl.obs.registry import (
    HIST_BUCKETS,
    MetricsRegistry,
    PeriodicSnapshot,
    diff_snapshots,
    hist_quantile,
    merge_snapshots,
)
from tpu_rl.obs.slo import SloEngine, SloRule, maybe_slo_engine, parse_slo_spec
from tpu_rl.obs.trace import TraceRecorder

__all__ = [
    "ANOMALY_LEVEL_SHIFTS_METRIC",
    "ANOMALY_SPIKES_METRIC",
    "AnomalyDetector",
    "BUCKETS",
    "BUCKET_GAUGE_PREFIX",
    "ClockEstimate",
    "ClockSync",
    "DEFAULT_STALE_AFTER_S",
    "DiagAccumulator",
    "FlightRecorder",
    "GAUGE_PREFIX",
    "GoodputLedger",
    "HIST_BUCKETS",
    "HistoryReader",
    "JsonExporter",
    "LEARNER_VERSION_GAUGE",
    "MetricsRegistry",
    "N_STALE_BUCKETS",
    "PEAK_FLOPS",
    "PerfTracker",
    "PeriodicSnapshot",
    "ProfilerCapture",
    "STALENESS_HIST",
    "STALE_BUCKET_LABELS",
    "STRAGGLER_GAUGE",
    "SloEngine",
    "SloRule",
    "TelemetryAggregator",
    "TelemetryHTTPServer",
    "TensorboardExporter",
    "TimeSeriesStore",
    "TraceRecorder",
    "append_jsonl",
    "append_resume",
    "channel_name",
    "derive",
    "device_memory_books",
    "device_memory_bytes",
    "device_peak_flops",
    "diff_snapshots",
    "downsample",
    "ess_normalized",
    "explained_variance",
    "flatten_snapshots",
    "hist_quantile",
    "history_path",
    "host_stale_rows",
    "learn_record",
    "maybe_aggregator",
    "maybe_history",
    "maybe_ledger",
    "maybe_perf_tracker",
    "maybe_slo_engine",
    "merge_result_dir",
    "merge_snapshots",
    "merge_traces",
    "parse_slo_spec",
    "process_self_stats",
    "publish",
    "render_healthz",
    "render_prometheus",
    "robust_z",
    "stale_bucket_index",
    "straggler_report",
]
