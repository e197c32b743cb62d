"""``repro.obs`` — the observability subsystem (DESIGN.md §10).

Three pillars, pure stdlib (never imports jax, so the host-side
scheduler/allocator layers can depend on it freely):

* **Tracing** (:mod:`repro.obs.trace`): span context managers, instants,
  counters and per-request async tracks into a bounded ring buffer; an
  optional annotation sink that enters each span in a profiler's trace
  too (the serve engine supplies ``jax.profiler``'s); a process-global
  no-op tracer when disabled (one method call, zero recording on the hot
  path); Chrome trace-event JSON export viewable at
  https://ui.perfetto.dev.
* **Metrics** (:mod:`repro.obs.metrics`): ``Counter`` / ``Gauge`` /
  ``Histogram`` (log-spaced fixed buckets, exact sum/min/max) behind a
  labeled :class:`MetricsRegistry` with ``snapshot() -> dict``.
* **Instrumentation** wired through the stack: serve engine request
  lifecycle (TTFT / ITL / queue-wait histograms, one span per tick phase,
  per-request async tracks, compile counters per phase), scheduler +
  block-pool gauges and counters,
  ``ops.dispatch`` per-(op, impl) call counters, and accuracy-guard trip
  events.

    from repro import obs

    tracer = obs.enable_tracing()
    ...  # serve traffic
    tracer.export_chrome("trace.json")      # load in Perfetto
    print(obs.default_registry().snapshot())
"""

from repro.obs.metrics import (  # noqa: F401
    DEFAULT_TIME_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    default_registry,
    log_buckets,
    set_default_registry,
)
from repro.obs.trace import (  # noqa: F401
    NULL_TRACER,
    NullTracer,
    TraceEvent,
    Tracer,
    disable_tracing,
    enable_tracing,
    get_tracer,
    set_tracer,
)


def reset() -> None:
    """Restore the no-op tracer and empty the global registry (tests)."""
    disable_tracing()
    default_registry().clear()
