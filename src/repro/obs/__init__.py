"""Unified observability: tracing and metrics.

  trace      nestable spans -> Chrome-trace/Perfetto JSON
  metrics    process-global counters / gauges / log-scale histograms

Both are dependency-free (stdlib; jax touched lazily), so ``repro.obs`` is
importable from anywhere in the stack.
"""
from . import metrics, trace
from .metrics import counter, gauge, histogram
from .trace import span

__all__ = [
    "trace",
    "metrics",
    "span",
    "counter",
    "gauge",
    "histogram",
]
