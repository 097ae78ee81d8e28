"""The program's own spans in a profiler trace.

A ``repro.obs`` recorder installed by ``repro.obs.configure()`` writes each
span as a ``jax.profiler.TraceAnnotation`` of the same name, so in a
``--trace 1`` run the spans sit in the trace's host plane, on the clock of
the device ops and over the same slice. A program that writes no such
annotation leaves nothing here to read.
"""
from __future__ import annotations


def mean_ms(tr, name: str) -> float | None:
    """Mean milliseconds of the host events called ``name`` that lie wholly
    inside the traced slice. None where there are none, or where the trace
    holds no device (a CPU rehearsal): these spans are read beside the
    device's time in the same slice."""
    if tr is None or not tr.devices:
        return None
    durs = [h.dur for h in tr.host
            if h.name == name and h.start >= tr.start
            and h.start + h.dur <= tr.end]
    if not durs:
        return None
    return sum(durs) / len(durs) / 1e6
