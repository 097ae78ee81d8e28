"""Dependency-free tracing + metrics core: spans, counters, gauges, histograms.

Contract (docs/observability.md):

  * **Zero overhead by default.** The module-level recorder in ``repro.obs``
    is ``None`` until ``configure()``/``set_recorder()`` is called; every
    instrumentation entry point early-returns the shared ``NULL_SPAN``
    singleton, so a disabled hot path costs one global read and allocates
    nothing (asserted by identity in tests/test_obs.py).
  * **Monotonic, injectable clock.** Durations come from ``time.monotonic``
    (never wall clock, which can step backwards under NTP); tests inject a
    deterministic fake so span durations are exact.
  * **Thread-safe.** Span stacks are thread-local (a prefetch worker's spans
    nest under its own roots, not the consumer's); metric updates are
    lock-protected; sink writes serialize on the sink's own lock.

No jax or numpy imports here: the core must be importable — and near-free —
from every module in the stack, including pure-host ones (data.pipeline,
serving.engine) and the analysis suite's no-execution constraint.
"""
from __future__ import annotations

import itertools
import os
import threading
import time
from typing import Any, Callable, Optional

Clock = Callable[[], float]

# Histograms keep raw observations up to this cap so the summarizer can
# compute exact quantiles; past the cap only count/sum/min/max keep updating
# (quantiles then describe the first _VALUES_CAP observations).
_VALUES_CAP = 8192

_RUN_IDS = itertools.count()


class NullSpan:
    """Shared do-nothing span, returned whenever tracing is disabled."""

    __slots__ = ()

    def __enter__(self) -> "NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs) -> "NullSpan":
        return self


NULL_SPAN = NullSpan()


class Span:
    """One timed region. Use as a context manager; nesting is tracked through
    the recorder's thread-local stack, so ``parent_id`` is assigned on entry
    without any caller bookkeeping."""

    __slots__ = (
        "name", "attrs", "span_id", "parent_id", "t0", "dur", "thread", "_rec",
        "_mirror",
    )

    def __init__(self, rec: "Recorder", name: str, attrs: dict):
        self.name = name
        self.attrs = attrs
        self.span_id = next(rec._ids)
        self.parent_id: Optional[int] = None
        self.t0 = 0.0
        self.dur = 0.0
        self.thread = threading.current_thread().name
        self._rec = rec
        self._mirror = None

    def set(self, **attrs) -> "Span":
        self.attrs.update(attrs)
        return self

    def __enter__(self) -> "Span":
        stack = self._rec._stack()
        self.parent_id = stack[-1].span_id if stack else None
        stack.append(self)
        if self._rec.annotate is not None:
            self._mirror = self._rec.annotate(self.name)
            self._mirror.__enter__()
        self.t0 = self._rec.clock()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.dur = self._rec.clock() - self.t0
        if self._mirror is not None:
            self._mirror.__exit__(exc_type, exc, tb)
            self._mirror = None
        stack = self._rec._stack()
        if stack and stack[-1] is self:
            stack.pop()
        else:  # exited out of order (generator finalized late): best effort
            try:
                stack.remove(self)
            except ValueError:
                pass
        if exc_type is not None:
            self.attrs.setdefault("error", exc_type.__name__)
        self._rec._emit_span(self)
        return False

    def to_record(self) -> dict:
        return {
            "type": "span",
            "name": self.name,
            "ts": self.t0,
            "dur": self.dur,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "run": self._rec.run,
            "thread": self.thread,
            "attrs": self.attrs,
        }


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------


class Counter:
    __slots__ = ("name", "value", "_lock")

    def __init__(self, name: str):
        self.name = name
        self.value = 0.0
        self._lock = threading.Lock()

    def add(self, n: float = 1.0) -> None:
        with self._lock:
            self.value += n

    def snapshot(self) -> float:
        with self._lock:
            return self.value


class Gauge:
    __slots__ = ("name", "value", "_lock")

    def __init__(self, name: str):
        self.name = name
        self.value = 0.0
        self._lock = threading.Lock()

    def set(self, v: float) -> None:
        with self._lock:
            self.value = float(v)

    def snapshot(self) -> float:
        with self._lock:
            return self.value


class Histogram:
    __slots__ = ("name", "count", "total", "vmin", "vmax", "values", "_lock")

    def __init__(self, name: str):
        self.name = name
        self.count = 0
        self.total = 0.0
        self.vmin = float("inf")
        self.vmax = float("-inf")
        self.values: list[float] = []
        self._lock = threading.Lock()

    def observe(self, v: float) -> None:
        v = float(v)
        with self._lock:
            self.count += 1
            self.total += v
            if v < self.vmin:
                self.vmin = v
            if v > self.vmax:
                self.vmax = v
            if len(self.values) < _VALUES_CAP:
                self.values.append(v)

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "count": self.count,
                "sum": self.total,
                "min": self.vmin if self.count else None,
                "max": self.vmax if self.count else None,
                "values": list(self.values),
            }


def quantile(sorted_values: list, q: float) -> float:
    """Nearest-rank quantile over an already-sorted list (no numpy)."""
    if not sorted_values:
        raise ValueError("quantile of empty list")
    idx = min(len(sorted_values) - 1, max(0, round(q * (len(sorted_values) - 1))))
    return sorted_values[int(idx)]


class MetricRegistry:
    """Name -> metric map with lock-protected lazy creation."""

    def __init__(self):
        self._metrics: dict[str, Any] = {}
        self._lock = threading.Lock()

    def _get(self, name: str, cls):
        m = self._metrics.get(name)  # fast path: dict reads are GIL-atomic
        if m is None:
            with self._lock:
                m = self._metrics.get(name)
                if m is None:
                    m = cls(name)
                    self._metrics[name] = m
        if not isinstance(m, cls):
            raise TypeError(
                f"metric {name!r} already registered as "
                f"{type(m).__name__}, requested {cls.__name__}"
            )
        return m

    def counter(self, name: str) -> Counter:
        return self._get(name, Counter)

    def gauge(self, name: str) -> Gauge:
        return self._get(name, Gauge)

    def histogram(self, name: str) -> Histogram:
        return self._get(name, Histogram)

    def snapshot(self) -> dict:
        with self._lock:
            metrics = list(self._metrics.values())
        out: dict = {"counters": {}, "gauges": {}, "histograms": {}}
        for m in metrics:
            if isinstance(m, Counter):
                out["counters"][m.name] = m.snapshot()
            elif isinstance(m, Gauge):
                out["gauges"][m.name] = m.snapshot()
            else:
                out["histograms"][m.name] = m.snapshot()
        return out


# ---------------------------------------------------------------------------
# recorder
# ---------------------------------------------------------------------------


class Recorder:
    """Active telemetry collector: spans + events stream to the sinks as they
    close; metrics accumulate in the registry and are emitted as one
    ``{"type": "metrics"}`` snapshot record on ``flush()``/``close()``.

    ``annotate`` mirrors every span into another timeline: called with the
    span's name, it returns a context manager entered and exited with the
    span on the same thread. ``repro.obs.configure()`` passes
    ``jaxhooks.trace_annotation``, so spans land in an active
    ``jax.profiler`` trace on the device ops' clock; a bare ``Recorder``
    mirrors nothing.
    """

    def __init__(
        self,
        sinks: tuple = (),
        *,
        clock: Clock = time.monotonic,
        annotate: Optional[Callable[[str], Any]] = None,
    ):
        self.clock = clock
        self.sinks = list(sinks)
        self.metrics = MetricRegistry()
        self.annotate = annotate
        # Span ids are only unique within one recorder; the run token keys
        # them globally so appended traces from several CLI invocations (or
        # several recorders in one test process) never cross-link.
        self.run = f"{os.getpid():x}.{next(_RUN_IDS)}"
        self._ids = itertools.count(1)
        self._tls = threading.local()

    # -- spans ---------------------------------------------------------------

    def _stack(self) -> list:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = []
            self._tls.stack = stack
        return stack

    def span(self, name: str, **attrs) -> Span:
        return Span(self, name, attrs)

    def current_span(self) -> Optional[Span]:
        stack = self._stack()
        return stack[-1] if stack else None

    def _emit_span(self, span: Span) -> None:
        self._write(span.to_record())

    # -- events --------------------------------------------------------------

    def event(self, name: str, **attrs) -> None:
        self._write(
            {"type": "event", "name": name, "ts": self.clock(),
             "run": self.run, "attrs": attrs}
        )

    # -- metrics -------------------------------------------------------------

    def inc(self, name: str, n: float = 1.0) -> None:
        self.metrics.counter(name).add(n)

    def gauge(self, name: str, v: float) -> None:
        self.metrics.gauge(name).set(v)

    def observe(self, name: str, v: float) -> None:
        self.metrics.histogram(name).observe(v)

    # -- lifecycle -----------------------------------------------------------

    def _write(self, record: dict) -> None:
        for sink in self.sinks:
            sink.write(record)

    def flush(self) -> None:
        self._write(
            {"type": "metrics", "ts": self.clock(), **self.metrics.snapshot()}
        )
        for sink in self.sinks:
            sink.flush()

    def close(self) -> None:
        self.flush()
        for sink in self.sinks:
            sink.close()
