"""repro.obs — structured tracing + metrics for the exchange stack.

Three small modules, one discipline (observe the same decomposition the
model prices):

* :mod:`repro.obs.trace` — hierarchical spans (``program_iteration`` →
  ``exchange`` → ``plan``/``pack``/``wire``/``unpack`` → ``stencil``)
  with decision signatures and predicted-seconds attributes,
  tracer-guarded like the telemetry probe, on the profiler's clock; and
  :func:`scope`, the ``tempi.<phase>`` named scopes that split a
  compiled step's device time by phase in a profiler trace;
* :mod:`repro.obs.metrics` — process-local counters/gauges
  (:meth:`Communicator.stats` publishes; ``save()`` persists);
* :mod:`repro.obs.export` — Chrome-trace JSON (Perfetto /
  ``chrome://tracing``), text flamechart summaries joining observed
  phase times against model predictions, and the CI trace validator.

``python -m repro.obs {summary,validate} TRACE.json`` is the CLI.
"""

from repro.obs.export import (
    aggregate_events,
    aggregate_spans,
    load_chrome_trace,
    save_chrome_trace,
    summary,
    to_chrome_trace,
    validate,
)
from repro.obs.metrics import (
    METRICS_FILENAME,
    METRICS_FORMAT,
    MetricsRegistry,
    default_metrics,
    publish_comm_stats,
)
from repro.obs.trace import (
    DEFAULT_MAX_SPANS,
    PHASES,
    SCOPE_PREFIX,
    TRACE_FORMAT,
    Span,
    Tracer,
    scope,
)

__all__ = [
    "TRACE_FORMAT",
    "PHASES",
    "DEFAULT_MAX_SPANS",
    "Span",
    "SCOPE_PREFIX",
    "Tracer",
    "scope",
    "METRICS_FORMAT",
    "METRICS_FILENAME",
    "MetricsRegistry",
    "default_metrics",
    "publish_comm_stats",
    "to_chrome_trace",
    "save_chrome_trace",
    "load_chrome_trace",
    "aggregate_spans",
    "aggregate_events",
    "summary",
    "validate",
]
