"""Double-buffered host-to-device window prefetch for the streaming tier.

``fit_stream`` / ``run_elastic_sharded`` consume an unbounded window stream;
without prefetch every window serializes host ingest -> sanitize -> H2D
transfer -> compute. This module overlaps the first three stages with the
fourth: while window *w* computes on the device, a background thread
sanitizes window *w+1* and lands it via ``jax.device_put`` (which is
asynchronous — the transfer itself overlaps compute; on the SPMD tier the
caller's ``place`` hook supplies the mesh's ``NamedSharding``). With a queue
depth of N the device always has up to N ready windows to chew through.

Bit-identity contract (tested in tests/test_throughput.py): the prefetched
stream yields EXACTLY what the synchronous path computes — same sanitize
call, same f32 conversion, same skip semantics for resumed (``start_at``)
and all-bad windows — so prefetch on/off cannot change results, only their
arrival time. Producer exceptions are re-raised in the consumer as the
ORIGINAL exception object (the chaos suites assert on exception types).

Observability, when a ``repro.obs`` recorder is active: ``sanitize.window``
and ``h2d.put`` spans around the two host stages of each window (on the
producer thread; ``sanitize.window`` carries the screen's ``threads``, 1
when it ran inline, and ``blocks``), and ``stream.wait`` around the
consumer's wait for its next window (on the synchronous path, around the
whole preparation).
``h2d.put`` covers what ``place`` holds the host for; an asynchronous
``device_put`` returns before its copy ends. With the default placement the
producer starts a window's copy only once the previous window's has landed
(``_settle``, outside both spans), so one copy is in flight at a time; a
``place`` given by the caller (the sharded tier's) is left unsettled.
"""
from __future__ import annotations

import queue
import threading
from typing import Any, Callable, Iterable, Iterator, NamedTuple, Optional

import jax
import numpy as np

from repro import obs
from repro.resilience.sanitize import sanitize_window, screen_plan

_POLL_S = 0.2


class PrefetchedWindow(NamedTuple):
    """One stream window, sanitized and (unless skipped) device-resident."""

    index: int                    # position in the raw stream
    host: Optional[np.ndarray]    # sanitized f32 host copy; None => skip
    device: Any                   # placed device value (None when skipped)
    n_bad: int                    # non-finite rows repaired by sanitize
    flagged: bool = False         # flag_fn() sampled when this was pulled


class _Done:
    """Queue sentinel: the raw stream finished cleanly."""


class _Failure:
    """Queue sentinel carrying the producer thread's exception."""

    def __init__(self, exc: BaseException):
        self.exc = exc


def default_place(w: np.ndarray) -> jax.Array:
    """Single-device placement — identical to ``jnp.asarray(w, f32)`` for an
    f32 host array (the synchronous path's conversion)."""
    return jax.device_put(w)


def _prepare(
    wi: int,
    window: Any,
    sanitize: bool,
    place: Callable[[np.ndarray], Any],
    flagged: bool,
    prior: Any = None,
) -> PrefetchedWindow:
    """sanitize -> f32 -> device_put for one window (either thread).

    ``prior`` is the value placed for the window before, on the prefetch
    thread with the default placement: its copy lands before this window's
    starts (``_settle``)."""
    w = np.asarray(window)
    n_bad = 0
    if sanitize:
        with obs.span("sanitize.window") as span:
            shape = w.shape
            w, n_bad = sanitize_window(w)
            plan = screen_plan(shape)
            span.set(threads=plan.threads, blocks=plan.blocks)
        if w is None:  # every row non-finite: the caller skips + counts it
            return PrefetchedWindow(wi, None, None, n_bad, flagged)
    w = np.asarray(w, np.float32)
    _settle(prior)
    with obs.span("h2d.put"):
        placed = place(w)
    return PrefetchedWindow(wi, w, placed, n_bad, flagged)


def _settle(placed: Any) -> None:
    """Wait until ``placed``'s host-to-device copy has landed.

    Copies to one chip in flight together run many times slower than the
    same copies back to back (two 3.2 GB windows on TPU v5e: 7-11 s
    together, 0.33 s each alone), so the producer starts one only once the
    last has landed; the sanitize of the next window still overlaps it. A
    failed copy is left for the consumer, which meets it where it uses that
    window."""
    if placed is None:
        return
    try:
        jax.block_until_ready(placed)
    except jax.errors.JaxRuntimeError:
        pass


def device_stream(
    windows: Iterable[Any],
    *,
    depth: int,
    sanitize: bool = True,
    start_at: int = 0,
    place: Callable[[np.ndarray], Any] | None = None,
    flag_fn: Callable[[], bool] | None = None,
) -> Iterator[PrefetchedWindow]:
    """Yield ``PrefetchedWindow``s for ``windows[start_at:]``.

    ``depth <= 0`` is the synchronous fallback (no thread, no queue) — the
    opt-out path and the reference for the bit-identity contract. Windows
    below ``start_at`` (a checkpoint fast-forward) are consumed from the raw
    iterator without sanitizing, exactly like the pre-prefetch resume loop.

    ``place`` maps a sanitized f32 host array to its device form; the SPMD
    tier passes a broadcast + ``NamedSharding`` placement, everyone else
    gets ``default_place``, whose copies the producer runs one at a time
    (``_settle``). The host copy rides along in the yielded item so
    recovery paths can re-place the window after a mesh change.

    ``flag_fn`` is the preemption hook: it is sampled in PULL ORDER (right
    after each raw window is taken from ``windows``) and delivered as
    ``item.flagged``, so a consumer that stops on the first flagged item
    behaves identically whether the producer ran ahead or not. A True
    sample also ends production — a preempted stream must not keep pulling.
    """
    settle = place is None
    place = place or default_place
    if depth <= 0:
        for wi, window in enumerate(windows):
            if wi < start_at:
                continue
            flagged = bool(flag_fn()) if flag_fn is not None else False
            with obs.span("stream.wait", window=wi):
                item = _prepare(wi, window, sanitize, place, flagged)
            yield item
            if flagged:
                return
        return

    q: queue.Queue = queue.Queue(maxsize=depth)
    stop = threading.Event()

    def _put(item: Any) -> None:
        # Bounded put that gives up when the consumer has left (generator
        # closed): a daemon thread must never wedge on a full queue.
        while not stop.is_set():
            try:
                q.put(item, timeout=_POLL_S)
                return
            except queue.Full:
                continue

    def run() -> None:
        prior = None
        try:
            for wi, window in enumerate(windows):
                if stop.is_set():
                    return
                if wi < start_at:
                    continue
                flagged = bool(flag_fn()) if flag_fn is not None else False
                item = _prepare(wi, window, sanitize, place, flagged, prior)
                if settle:
                    prior = item.device
                _put(item)
                if flagged:
                    break
            _put(_Done())
        except BaseException as e:  # noqa: BLE001 — forwarded, never silent
            _put(_Failure(e))

    t = threading.Thread(
        target=run, name="repro-device-prefetch", daemon=True)
    t.start()
    try:
        while True:
            # The last wait is for the end of the stream: it carries no
            # ``window``.
            with obs.span("stream.wait") as wait:
                while True:
                    try:
                        got = q.get(timeout=_POLL_S)
                        break
                    except queue.Empty:
                        if not t.is_alive() and q.empty():
                            raise RuntimeError(
                                "device prefetch thread died without "
                                "reporting an error"
                            ) from None
                if isinstance(got, PrefetchedWindow):
                    wait.set(window=got.index)
            if isinstance(got, _Done):
                return
            if isinstance(got, _Failure):
                raise got.exc  # the original exception, type preserved
            yield got
    finally:
        stop.set()
