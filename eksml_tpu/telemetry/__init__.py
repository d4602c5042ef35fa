"""Unified telemetry: registry → cross-host aggregation → exporter /
flight recorder.

The observability layer (ISSUE 4).  Data flow::

    subsystems ──publish──▶ MetricRegistry ──▶ /metrics (OpenMetrics,
    (train/data/resilience)      │               every pod)
                                 └──▶ fit loop ──▶ cross-host
                                      aggregation ──▶ rank-0
                                      metrics.jsonl / TB rows
    resilience transitions ──event()──▶ FlightRecorder ──▶
        events-host<i>.jsonl + watchdog report tail +
        tools/run_report.py post-mortems

Span tracing (ISSUE 5) rides the same flow: subsystems time hot-path
intervals through the module-level ``span()`` (no-op without an
installed :class:`~eksml_tpu.telemetry.tracing.Tracer`), the ring
flushes Chrome-trace JSON to ``<logdir>/trace-host<i>.json``, and the
exporter's ``/debugz/profile`` endpoint (or the anomaly detector)
asks the fit loop for a bounded ``jax.profiler`` capture through a
:class:`~eksml_tpu.telemetry.tracing.ProfileTrigger`.

The goodput ledger (ISSUE 13) consumes BOTH streams through module
sinks (``install_span_sink`` / ``add_event_sink``) and classifies
every second of run wall-clock into named buckets — ``train_step``
(goodput) vs compile/data/checkpoint/eval/hang/downtime (badput) —
published as ``eksml_goodput_ratio`` +
``eksml_badput_seconds_total{bucket=}``, banked to
``goodput-host<i>.jsonl``, and merged across restarts by
``tools/goodput_report.py`` (see telemetry/goodput.py).

Config knobs live under ``config.TELEMETRY`` (tracing under
``config.TELEMETRY.TRACING``, goodput under
``config.TELEMETRY.GOODPUT``); chart plumbing (prometheus.io/scrape
annotations, container port, liveness probe) in
charts/maskrcnn*/templates.
"""

from eksml_tpu.telemetry.aggregate import (HOST_AGG_KEYS,  # noqa: F401
                                           aggregate_host_scalars,
                                           publish_aggregates,
                                           stats_from_matrix)
from eksml_tpu.telemetry.exporter import (TelemetryExporter,  # noqa: F401
                                          render_openmetrics)
from eksml_tpu.telemetry.goodput import \
    BUCKETS as GOODPUT_BUCKETS  # noqa: F401
from eksml_tpu.telemetry.goodput import (GoodputMeter,  # noqa: F401
                                         build_ledger,
                                         goodput_path_for,
                                         recover_downtime)
from eksml_tpu.telemetry.recorder import (FlightRecorder,  # noqa: F401
                                          add_event_sink, event,
                                          events_path_for, get,
                                          install, remove_event_sink)
from eksml_tpu.telemetry.registry import (MetricRegistry,  # noqa: F401
                                          default_registry)
from eksml_tpu.telemetry.tracing import (NULL_SPAN,  # noqa: F401
                                         AnomalyDetector, GcMeter,
                                         ProfileTrigger, StepStamper,
                                         Tracer, complete_span,
                                         get_tracer, install_span_sink,
                                         install_tracer, span,
                                         trace_path_for)
