"""Unified telemetry: process-wide metrics registry + span tracer.

One import surface for every instrumentation site::

    from holo_tpu import telemetry

    _DISPATCHES = telemetry.counter(
        "holo_spf_dispatch_total", "SPF device dispatches", ("engine",))
    _DISPATCHES.labels(engine="tpu").inc()

    with telemetry.span("spf.dispatch", instance="ospfv2"):
        ...

Exports ride three surfaces (all daemon-wired in
:mod:`holo_tpu.daemon.daemon` behind the ``[telemetry]`` config
section):

- Prometheus text endpoint (:mod:`holo_tpu.telemetry.prometheus`);
- the gNMI/gRPC state tree via
  :class:`holo_tpu.telemetry.provider.TelemetryStateProvider`;
- Chrome trace-event JSON span dumps (:mod:`holo_tpu.telemetry.trace`)
  via ``holo-tpu-tools trace`` or ``HOLO_TPU_TRACE_DUMP=<path>``.

Everything here is stdlib-only and import-light: instrumented hot paths
(SPF dispatch, RIB churn, packet rx/tx) pay a dict hit and a locked
float add per event, and :func:`set_enabled` (False) turns every update
into an early return: a dispatch then writes no metric and opens no span
(``tests/test_telemetry.py::
test_disabled_registry_dispatch_writes_no_metric_and_opens_no_span``).
"""

from __future__ import annotations

import os

from holo_tpu.telemetry import registry as _registry_mod
from holo_tpu.telemetry.registry import (  # noqa: F401 — public API
    DEFAULT_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    deferred_mean,
    enabled,
    volatile_children,
    write_stamp,
)
from holo_tpu.telemetry.trace import SpanTracer

_registry = MetricsRegistry()
_tracer = SpanTracer()


def set_enabled(on: bool) -> None:
    """Global kill switch for BOTH the metrics registry and the default
    span tracer — a disabled process sheds every instrumentation
    cost, spans included."""
    _registry_mod.set_enabled(on)
    _tracer.enabled = bool(on)


def registry() -> MetricsRegistry:
    """The process-wide default registry."""
    return _registry


def tracer() -> SpanTracer:
    """The process-wide default span tracer."""
    return _tracer


def counter(name: str, help: str = "", labelnames=(), stamped: bool = True):
    return _registry.counter(name, help, tuple(labelnames), stamped=stamped)


def gauge(name: str, help: str = "", labelnames=(), stamped: bool = True):
    return _registry.gauge(name, help, tuple(labelnames), stamped=stamped)


def histogram(
    name: str, help: str = "", labelnames=(), buckets=None,
    stamped: bool = True,
):
    return _registry.histogram(
        name, help, tuple(labelnames), buckets, stamped=stamped
    )


def span(name: str, **attrs):
    """Context manager recording one span on the default tracer."""
    return _tracer.span(name, **attrs)


def current_span_id():
    return _tracer.current_span_id()


def current_instance():
    return _tracer.current_instance()


def snapshot(prefix: str | None = None) -> dict:
    """Flat metrics view for the benchmark, tests and debugging."""
    return _registry.snapshot(prefix)


# Optional env-triggered span dump on process exit: any run (test,
# daemon, benchmark cell) gets a perfetto-loadable trace with no code
# change.  Registered once, at first package import.
_dump_path = os.environ.get("HOLO_TPU_TRACE_DUMP")
if _dump_path:  # pragma: no cover — exercised via subprocess in tests
    import atexit

    atexit.register(lambda: _tracer.dump(_dump_path))
