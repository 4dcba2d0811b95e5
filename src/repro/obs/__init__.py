"""End-to-end observability for the LEGO reproduction pipeline.

Zero-dependency (stdlib-only) subsystem with four pillars, each its own
module:

``trace``
    context-manager/decorator spans emitting Chrome trace-event JSON
    (Perfetto / chrome://tracing), process-safe so DSE worker pools merge
    per-worker traces on join.
``metrics``
    process-global counters/gauges/histograms wired through the hot paths
    (mapping cache, candidate enumeration, LP delay matching, design
    scoring); dumped as the ``metrics`` section of every ``BENCH_*.json``.
``provenance``
    schema-versioned run metadata (git sha, host, timestamp, argv) stamped
    into every bench artifact.
``log``
    the ``repro`` module-logger hierarchy behind the CLIs' ``-v`` flags.
``vcd``
    deterministic VCD waveform writer for rtlsim netlist introspection.

See ``docs/OBSERVABILITY.md`` for the user guide and metric-name table.
"""

from .log import add_verbosity_flag, configure, get_logger
from .metrics import (METRICS, Counter, Gauge, Histogram, Registry,
                      metrics_enabled, set_metrics_enabled)
from .provenance import PROVENANCE_SCHEMA, git_sha, provenance_record
from .trace import (Span, Tracer, disable_tracing, drain_events,
                    enable_tracing, instant, merge_events, save_trace,
                    set_annotation_factory, span, span_counts,
                    tracing_enabled)
from .vcd import VCDWriter

__all__ = [
    "span", "instant", "Span", "Tracer", "enable_tracing", "disable_tracing",
    "tracing_enabled", "drain_events", "merge_events", "save_trace",
    "span_counts", "set_annotation_factory",
    "METRICS", "Registry", "Counter", "Gauge", "Histogram",
    "set_metrics_enabled", "metrics_enabled",
    "PROVENANCE_SCHEMA", "provenance_record", "git_sha",
    "get_logger", "configure", "add_verbosity_flag",
    "VCDWriter",
]
