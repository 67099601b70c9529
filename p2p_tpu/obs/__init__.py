"""Unified telemetry subsystem (SURVEY §5.1: the reference had a tqdm bar).

One import surface for everything a production trainer reports through:

- **metrics registry** (:mod:`.registry`): counters / gauges / histograms /
  EWMA rates with tags, pluggable record sinks, cross-host aggregation;
- **sinks** (:mod:`.sinks`): crash-safe JSONL (the ``metrics_<name>.jsonl``
  stream), stdout heartbeat, TensorBoard event files, Prometheus textfile;
- **span tracing** (:mod:`.spans`): host wall-clock spans paired with
  ``jax.profiler.TraceAnnotation``, exported as Perfetto-loadable JSON, plus
  the ``trace()`` XPlane capture;
- **in-jit taps** (:mod:`.taps`): NaN/Inf sentinels and grad-norm scalars
  via ``jax.debug.callback`` — no device fence on the happy path;
- **watchdogs** (:mod:`.watchdogs`): unexpected-recompile detection off the
  ``jax.monitoring`` compile events; per-device HBM sampling;
- **timing** (:mod:`.timing`): the fenced ``StepTimer`` with the chained
  single-fence mode the serving engine uses — one img/sec/chip definition;
- **manifest** (:mod:`.manifest`): the per-run provenance JSON (config hash,
  git SHA, mesh shape, dtype policy).
"""

from p2p_tpu.obs.manifest import build_manifest, config_hash, write_manifest
from p2p_tpu.obs.registry import (
    Counter,
    EWMARate,
    Gauge,
    Histogram,
    MetricsRegistry,
    combine_host_snapshots,
    get_registry,
    set_registry,
)
from p2p_tpu.obs.sinks import (
    JSONLSink,
    MetricsLogger,
    PrometheusTextfileSink,
    Sink,
    StdoutSink,
    TensorBoardSink,
    prometheus_exposition,
)
from p2p_tpu.obs.spans import (
    GcPauseMeter,
    SpanRecorder,
    StepClock,
    get_recorder,
    span,
    timed_annotation,
    trace,
)
from p2p_tpu.obs.taps import (
    add_sentinel_handler,
    grad_norm_taps,
    nan_sentinel,
    remove_sentinel_handler,
)
from p2p_tpu.obs.timing import StepTimer, measure_rtt
from p2p_tpu.obs.watchdogs import (
    MemoryWatchdog,
    RetraceWatchdog,
    budget_drift,
    crosscheck_hbm_budget,
)

__all__ = [
    "Counter",
    "EWMARate",
    "Gauge",
    "GcPauseMeter",
    "Histogram",
    "JSONLSink",
    "MemoryWatchdog",
    "MetricsLogger",
    "MetricsRegistry",
    "PrometheusTextfileSink",
    "RetraceWatchdog",
    "budget_drift",
    "crosscheck_hbm_budget",
    "Sink",
    "SpanRecorder",
    "StdoutSink",
    "StepClock",
    "StepTimer",
    "TensorBoardSink",
    "add_sentinel_handler",
    "build_manifest",
    "combine_host_snapshots",
    "config_hash",
    "get_recorder",
    "get_registry",
    "grad_norm_taps",
    "measure_rtt",
    "nan_sentinel",
    "prometheus_exposition",
    "remove_sentinel_handler",
    "set_registry",
    "span",
    "timed_annotation",
    "trace",
    "write_manifest",
]
