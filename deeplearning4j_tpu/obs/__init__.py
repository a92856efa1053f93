from deeplearning4j_tpu.obs.listeners import (
    TrainingListener,
    ListenerBus,
    ScoreIterationListener,
    PerformanceListener,
    CollectScoresListener,
    TimeIterationListener,
    EvaluativeListener,
)
from deeplearning4j_tpu.obs.metrics import MetricsWriter
from deeplearning4j_tpu.obs.profiler import check_finite, timeline
from deeplearning4j_tpu.obs.registry import (
    Counter, Gauge, Histogram, LabeledCounter, LabeledGauge,
    LabeledHistogram, MetricsRegistry,
    get_registry, set_registry, install_standard_metrics,
    record_device_memory)
from deeplearning4j_tpu.obs import costmodel, flight_recorder, health, remote
from deeplearning4j_tpu.obs.flight_recorder import FlightRecorder, Watchdog
from deeplearning4j_tpu.obs.health import (HealthConfig, HealthHalt,
                                           HealthMonitor)
from deeplearning4j_tpu.obs.remote import ClusterStore, RemoteStatsRouter
from deeplearning4j_tpu.obs.stats import (
    StatsListener, InMemoryStatsStorage, FileStatsStorage,
    render_html_report, render_html)
from deeplearning4j_tpu.obs.tracing import (
    Span, SpanContext, Tracer,
    span, current_span, current_context, device_sync,
    get_tracer, set_tracer, use_tracer, inject, extract)
from deeplearning4j_tpu.obs.ui_server import UIServer

__all__ = [
    "TrainingListener",
    "ListenerBus",
    "ScoreIterationListener",
    "PerformanceListener",
    "CollectScoresListener",
    "TimeIterationListener",
    "EvaluativeListener",
    "MetricsWriter",
    "check_finite",
    "timeline",
    "Counter",
    "Gauge",
    "Histogram",
    "LabeledCounter",
    "LabeledGauge",
    "LabeledHistogram",
    "costmodel",
    "flight_recorder",
    "health",
    "remote",
    "FlightRecorder",
    "Watchdog",
    "HealthConfig",
    "HealthHalt",
    "HealthMonitor",
    "ClusterStore",
    "RemoteStatsRouter",
    "MetricsRegistry",
    "get_registry",
    "set_registry",
    "install_standard_metrics",
    "record_device_memory",
    "StatsListener",
    "InMemoryStatsStorage",
    "FileStatsStorage",
    "render_html_report",
    "render_html",
    "Span",
    "SpanContext",
    "Tracer",
    "span",
    "current_span",
    "current_context",
    "device_sync",
    "get_tracer",
    "set_tracer",
    "use_tracer",
    "inject",
    "extract",
    "UIServer",
]
