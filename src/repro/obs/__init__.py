"""Observability subsystem (DESIGN.md §11): host spans on the profiler's
clock with Perfetto export and the naming contract of the compiled step's
scopes (`obs.trace`), and the counters/gauges/histograms metrics bus
(`obs.metrics`).  Nothing is inserted into any compiled graph; a span
with no profiler running and no tracer installed is one sub-microsecond
annotation."""
from repro.obs.metrics import JsonlSink, MetricsBus
from repro.obs.trace import Tracer, get_tracer, set_tracer, span

__all__ = ["JsonlSink", "MetricsBus", "Tracer", "get_tracer", "set_tracer",
           "span"]
