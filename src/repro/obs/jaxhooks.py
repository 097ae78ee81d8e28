"""JAX-aware observability hooks.

Everything here degrades to a no-op when jax (or the specific profiler API)
is unavailable, so importing this module never adds a hard dependency beyond
what the instrumented code already has. Host-side spans live in
``repro.obs.core``; these hooks connect them to JAX's profiler:

  * ``named_scope``      — names a traced region so it survives into HLO
    metadata and XLA profiles (usable inside jit/vmap/scan bodies);
  * ``trace_annotation`` — host-thread annotation visible in a
    ``jax.profiler`` timeline (NOT usable inside traced code);
    ``repro.obs.configure()`` mirrors every span through it.
"""
from __future__ import annotations

import contextlib
from typing import ContextManager

try:  # pragma: no cover - exercised implicitly by every traced test
    import jax
except Exception:  # noqa: BLE001 — analysis-only hosts may lack jax entirely
    jax = None  # type: ignore[assignment]

_TRACE_ANNOTATION = getattr(getattr(jax, "profiler", None),
                            "TraceAnnotation", None)


def named_scope(name: str) -> ContextManager:
    """``jax.named_scope`` when available, else a null context."""
    if jax is not None and hasattr(jax, "named_scope"):
        return jax.named_scope(name)
    return contextlib.nullcontext()


def trace_annotation(name: str) -> ContextManager:
    """``jax.profiler.TraceAnnotation`` when available, else a null context.
    Outside a profiler session the annotation records nothing."""
    if _TRACE_ANNOTATION is not None:
        return _TRACE_ANNOTATION(name)
    return contextlib.nullcontext()
