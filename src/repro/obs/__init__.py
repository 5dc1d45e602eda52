"""Unified observability layer (§15): metrics registry + span tracing.

`Obs` bundles the two surfaces every instrumented component takes as an
optional ``obs=`` parameter:

  * ``obs.metrics`` — a `MetricsRegistry` (always present; creating one is
    cheap and components need it for their `metrics()` readouts);
  * ``obs.tracer`` — an optional `Tracer`; when absent, `obs.instant(...)`
    is a no-op and `obs.span(...)` records nothing in Perfetto JSON, so
    tracing costs nothing unless a driver passed ``--trace-out``.

`span(name, obs)` is the one entry point for host spans.  It always enters
a `jax.profiler.TraceAnnotation`, which lands in a JAX profiler trace on
the device trace's clock and costs a few hundred nanoseconds when no
profiler runs; when `obs` has a tracer it also records the Perfetto span.
`Obs.span` is built on it.

Components default to a private `Obs()` when none is supplied, so their
counters always work standalone; drivers pass ONE shared `Obs` down the
stack so engine, transport, WAL, serving, and fault events land in a
single registry and a single per-process trace file.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               DEFAULT_BUCKETS, now)
from repro.obs.trace import (Tracer, load_trace, merge_traces,
                             trace_categories, validate_trace)

__all__ = ["Obs", "span", "Counter", "Gauge", "Histogram", "MetricsRegistry",
           "Tracer", "DEFAULT_BUCKETS", "now", "load_trace",
           "merge_traces", "trace_categories", "validate_trace"]


class _Both:
    """Two context managers entered and left as one (profiler annotation
    outside, Perfetto span inside); `as` binds the Perfetto span."""

    __slots__ = ("outer", "inner")

    def __init__(self, outer, inner):
        self.outer, self.inner = outer, inner

    def __enter__(self):
        self.outer.__enter__()
        return self.inner.__enter__()

    def __exit__(self, exc_type, exc, tb):
        try:
            return self.inner.__exit__(exc_type, exc, tb)
        finally:
            self.outer.__exit__(exc_type, exc, tb)


def span(name: str, obs: "Obs | None" = None, cat: str = "", **args):
    """Host span context manager: a profiler annotation named `name`, plus
    the Perfetto span (with `cat` and `args`) when `obs` has a tracer."""
    ann = TraceAnnotation(name)
    if obs is None or obs.tracer is None:
        return ann
    return _Both(ann, obs.tracer.span(name, cat=cat, args=args or None))


class Obs:
    """Bundle of a metrics registry and an optional tracer."""

    __slots__ = ("metrics", "tracer", "trace_path")

    def __init__(self, registry: MetricsRegistry | None = None,
                 tracer: Tracer | None = None,
                 trace_path: str | None = None):
        self.metrics = MetricsRegistry() if registry is None else registry
        self.tracer = tracer
        self.trace_path = trace_path

    def span(self, name: str, cat: str = "", **args):
        """`span(name, self, ...)`: the Perfetto part is a no-op without a
        tracer."""
        return span(name, self, cat, **args)

    def instant(self, name: str, cat: str = "", **args) -> None:
        if self.tracer is not None:
            self.tracer.instant(name, cat=cat, args=args or None)

    def flush(self) -> None:
        """Persist the trace now (called before a fault-injected kill so
        the victim's timeline survives `os._exit`)."""
        if self.tracer is not None and self.trace_path is not None:
            self.tracer.save(self.trace_path)
