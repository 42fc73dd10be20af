"""repro.obs — pipeline observability (DESIGN.md §16).

Three pieces, importable from this package root:

* :func:`span` / :class:`Span` / :class:`Tracer` — always-on program spans
  that land on the profiler's clock as ``repro/<name>`` annotations, are
  recorded into a tracer only when one is active, and never change what
  runs; plus the program's own compile counter, :func:`compile_counts`,
  the stage-2 chase-path counter, :func:`chase_stage_counts`, the
  tape-replay path counter, :func:`replay_stage_counts`, and the
  reflector-tape byte counter, :func:`tape_bytes` (``trace.py``);
  :func:`scope` names a stage's jitted body ``repro.<stage>`` for the
  device trace.  Ambient-tracer helpers: :func:`current`,
  :func:`activated`, :func:`install`.
* :class:`StreamingHistogram` — mergeable fixed-log-bucket latency
  histograms with bounded memory (``hist.py``).
* :class:`MetricsServer` / :func:`render_serve_metrics` /
  :func:`render_compile_metrics` — Prometheus text exposition over stdlib
  http.server (``prom.py``); JSONL span export/round-trip in
  ``export.py``.
"""

from .export import JsonlExporter, SpanRecord, load_jsonl
from .hist import StreamingHistogram
from .prom import (MetricsServer, render_compile_metrics,
                   render_fleet_metrics, render_serve_metrics)
from .trace import (
    Span,
    Tracer,
    activated,
    chase_stage_counts,
    compile_counts,
    count_chase_stage,
    count_replay_stage,
    count_tape_bytes,
    current,
    install,
    replay_stage_counts,
    scope,
    span,
    tape_bytes,
)

__all__ = [
    "JsonlExporter",
    "SpanRecord",
    "load_jsonl",
    "StreamingHistogram",
    "MetricsServer",
    "render_compile_metrics",
    "render_fleet_metrics",
    "render_serve_metrics",
    "Span",
    "Tracer",
    "activated",
    "chase_stage_counts",
    "compile_counts",
    "count_chase_stage",
    "count_replay_stage",
    "count_tape_bytes",
    "current",
    "install",
    "replay_stage_counts",
    "scope",
    "span",
    "tape_bytes",
]
