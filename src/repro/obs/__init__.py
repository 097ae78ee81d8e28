"""repro.obs — unified tracing, metrics, and profiling for the HPClust stack.

One module-level recorder gates everything. Until ``configure()`` (or
``set_recorder()``) installs one, every entry point below is a near-free
no-op: ``span()`` returns the shared ``NULL_SPAN`` singleton and the metric
helpers return immediately — the hot paths in core/, kernels/, data/,
serving/ and runtime/ stay unperturbed (asserted in tests/test_obs.py).

Typical use (what the launch CLIs' ``--trace`` flag does)::

    from repro import obs

    obs.configure(jsonl="trace.jsonl")
    with obs.span("stream.window", window=0, rows=65536):
        ...
    obs.inc("stream.windows")
    obs.observe("serve.request_latency_s", 0.012)
    obs.event("resilience.preempted", step=7)
    obs.shutdown()               # metrics snapshot + close sinks

Read the trace back with ``python -m repro.obs summarize trace.jsonl``.
Device-side naming (``jax.named_scope``, and the ``TraceAnnotation`` that
mirrors a configured recorder's spans into a ``jax.profiler`` trace) lives
in ``repro.obs.jaxhooks``.
"""
from __future__ import annotations

import time
from typing import Optional

from repro.obs.core import (  # noqa: F401
    NULL_SPAN,
    Clock,
    Counter,
    Gauge,
    Histogram,
    MetricRegistry,
    NullSpan,
    Recorder,
    Span,
    quantile,
)
from repro.obs.sinks import JsonlSink, ListSink, prometheus_text  # noqa: F401

_recorder: Optional[Recorder] = None


def get_recorder() -> Optional[Recorder]:
    return _recorder


def set_recorder(rec: Optional[Recorder]) -> Optional[Recorder]:
    """Install ``rec`` as the active recorder; returns the previous one so
    tests can restore it."""
    global _recorder
    prev = _recorder
    _recorder = rec
    return prev


def enabled() -> bool:
    """Gate for instrumentation whose *attributes* are expensive to compute —
    plain ``span()``/``inc()`` calls do not need it."""
    return _recorder is not None


def configure(
    *,
    jsonl: str | None = None,
    sinks: tuple = (),
    clock: Clock = time.monotonic,
) -> Recorder:
    """Build a ``Recorder`` (JSONL sink when ``jsonl`` is given, plus any
    extra ``sinks``), install it, and return it.

    Its spans are mirrored as ``jax.profiler.TraceAnnotation``s of the same
    name: inside an active profiler session they land in the trace's host
    plane, on the device ops' clock; outside one they cost next to
    nothing."""
    from repro.obs import jaxhooks  # jax only once a recorder is wanted

    all_sinks = list(sinks)
    if jsonl is not None:
        all_sinks.append(JsonlSink(jsonl))
    rec = Recorder(tuple(all_sinks), clock=clock,
                   annotate=jaxhooks.trace_annotation)
    set_recorder(rec)
    return rec


def span(name: str, **attrs):
    rec = _recorder
    if rec is None:
        return NULL_SPAN
    return rec.span(name, **attrs)


def event(name: str, **attrs) -> None:
    rec = _recorder
    if rec is not None:
        rec.event(name, **attrs)


def inc(name: str, n: float = 1.0) -> None:
    rec = _recorder
    if rec is not None:
        rec.inc(name, n)


def gauge(name: str, v: float) -> None:
    rec = _recorder
    if rec is not None:
        rec.gauge(name, v)


def observe(name: str, v: float) -> None:
    rec = _recorder
    if rec is not None:
        rec.observe(name, v)


def flush() -> None:
    rec = _recorder
    if rec is not None:
        rec.flush()


def shutdown() -> None:
    """Close the active recorder (final metrics snapshot + sink close) and
    uninstall it. Safe to call when nothing is configured."""
    global _recorder
    rec = _recorder
    _recorder = None
    if rec is not None:
        rec.close()


__all__ = [
    "NULL_SPAN",
    "Clock",
    "Counter",
    "Gauge",
    "Histogram",
    "JsonlSink",
    "ListSink",
    "MetricRegistry",
    "NullSpan",
    "Recorder",
    "Span",
    "configure",
    "enabled",
    "event",
    "flush",
    "gauge",
    "get_recorder",
    "inc",
    "observe",
    "prometheus_text",
    "quantile",
    "set_recorder",
    "shutdown",
    "span",
]
