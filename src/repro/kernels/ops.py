"""Dispatching wrappers around the Pallas kernels.

``impl`` resolution:
  - "pallas":    compiled TPU kernel (requires a TPU backend).
  - "interpret": Pallas interpret mode — used by the CPU test suite.
  - "ref":       the jnp oracle (what XLA lowers on CPU / in dry-runs).
  - None/"auto": "pallas" on TPU, "ref" elsewhere.

The wrappers own all padding so the kernels can assume hardware-aligned
tiles: S is padded with junk rows (sliced off), D with zero columns (no-op in
dot products), K with +inf-norm centroids (can never win an argmin) /
out-of-range assignments (fall outside every one-hot tile).

Tile sizes come from ``repro.kernels.autotune`` when ``REPRO_AUTOTUNE`` is
enabled (persisted per backend/shape-bucket/dtype) and fall back to the
static heuristics in ``_heuristic_blocks`` otherwise. ``compute_dtype``
(argument or ``REPRO_COMPUTE_DTYPE=bf16``) switches the assign/lloyd kernels
to bf16 inputs with f32 accumulation; it is a *static* jit argument so each
dtype gets its own compile-cache entry.

Observability: the jitted bodies carry ``jax.named_scope`` labels
(``kernel.assign``, ``kernel.update``, ...), so each kernel's ops are named
in HLO metadata and XLA profiles, inside the round program too.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import flags
from repro.kernels import autotune, ref
from repro.kernels.assign import assign_pallas
from repro.kernels.update import cluster_sums_pallas
from repro.obs import jaxhooks

Array = jax.Array

_LANE = 128
_SUBLANE = 8  # f32; bf16 tiles need 16 sublanes


def resolve_impl(impl: str | None) -> str:
    if impl in (None, "auto"):
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    if impl not in ("pallas", "interpret", "ref"):
        raise ValueError(f"unknown impl {impl!r}")
    return impl


def _round_up(v: int, m: int) -> int:
    return v + (-v) % m


def _sublane(compute_dtype: str) -> int:
    return 16 if compute_dtype == "bf16" else _SUBLANE


def _heuristic_blocks(kernel: str, s: int, k: int, d: int,
                      compute_dtype: str) -> tuple[int, int, int]:
    """The static tile defaults (used when autotune is off or misses).

    ``block_k`` is always one lane tile: K is lane-padded to >= 128, so a
    bigger k-block only helps once K itself exceeds 128 — exactly what the
    autotuner probes. ``block_s``/``block_d`` shrink to the (aligned) data so
    tiny problems don't pad to a full default tile.
    """
    sub = _sublane(compute_dtype)
    if kernel == "update":
        bs = min(512, _round_up(s, sub))
    else:
        bs = min(256, _round_up(s, sub))
    bd = min(512, _round_up(d, _LANE))
    return bs, _LANE, bd


def _blocks(kernel: str, s: int, k: int, d: int,
            compute_dtype: str) -> tuple[int, int, int]:
    tuned = autotune.lookup(kernel, s, k, d, dtype=compute_dtype)
    if tuned is None:
        return _heuristic_blocks(kernel, s, k, d, compute_dtype)
    bs, bk, bd = tuned
    sub = _sublane(compute_dtype)
    # Sanitize a cache entry written by another backend/version: alignment is
    # a hard kernel requirement, tune quality is not.
    return _round_up(bs, sub), _round_up(bk, _LANE), _round_up(bd, _LANE)


@functools.partial(jax.jit, static_argnames=("impl", "compute_dtype"))
def _assign_clusters_jit(
    x: Array, c: Array, *, impl: str | None = None, compute_dtype: str = "f32",
) -> tuple[Array, Array]:
    with jaxhooks.named_scope("kernel.assign"):
        impl = resolve_impl(impl)
        if impl == "ref":
            return ref.assign_ref(x, c)
        s, d = x.shape
        k = c.shape[0]
        bs, bk, bd = _blocks("assign", s, k, d, compute_dtype)
        sp, kp, dp = _round_up(s, bs), _round_up(k, bk), _round_up(d, bd)
        xp = jnp.pad(x, ((0, sp - s), (0, dp - d)))
        cp = jnp.pad(c, ((0, kp - k), (0, dp - d)))
        idx, dist = assign_pallas(
            xp, cp, k_valid=k, block_s=bs, block_k=bk, block_d=bd,
            compute_dtype=compute_dtype, interpret=(impl == "interpret"),
        )
        return idx[:s], dist[:s]


def assign_clusters(
    x: Array, c: Array, *, impl: str | None = None,
    compute_dtype: str | None = None,
) -> tuple[Array, Array]:
    """Nearest-centroid assignment: x (s,d), c (k,d) -> (idx (s,), dist (s,))."""
    cdt = flags.compute_dtype(compute_dtype)
    return _assign_clusters_jit(x, c, impl=impl, compute_dtype=cdt)


@functools.partial(jax.jit, static_argnames=("k", "impl"))
def _cluster_sums_jit(x: Array, idx: Array, k: int, *, impl: str | None = None) -> tuple[Array, Array]:
    with jaxhooks.named_scope("kernel.update"):
        impl = resolve_impl(impl)
        if impl == "ref":
            return ref.cluster_sums_ref(x, idx, k)
        s, d = x.shape
        bs, bk, bd = _blocks("update", s, k, d, "f32")
        sp, dp = _round_up(s, bs), _round_up(d, bd)
        kp = _round_up(k, bk)
        # Padding rows get assignment kp (out of range of every tile).
        idxp = jnp.pad(idx.astype(jnp.int32), (0, sp - s), constant_values=kp)
        xp = jnp.pad(x, ((0, sp - s), (0, dp - d)))
        sums, counts = cluster_sums_pallas(
            xp, idxp, k, block_s=bs, block_k=bk, block_d=bd,
            interpret=(impl == "interpret"),
        )
        return sums[:, :d], counts


def cluster_sums(x: Array, idx: Array, k: int, *, impl: str | None = None) -> tuple[Array, Array]:
    """Per-cluster sums (k,d) and counts (k,) from assignments idx (s,)."""
    return _cluster_sums_jit(x, idx, k, impl=impl)


@functools.partial(jax.jit, static_argnames=("impl", "compute_dtype"))
def _mssc_objective_jit(
    x: Array, c: Array, *, impl: str | None = None, compute_dtype: str = "f32",
) -> Array:
    with jaxhooks.named_scope("kernel.objective"):
        _, dist = assign_clusters(x, c, impl=impl, compute_dtype=compute_dtype)
        return jnp.sum(dist)


def mssc_objective(
    x: Array, c: Array, *, impl: str | None = None,
    compute_dtype: str | None = None,
) -> Array:
    """Equation (1): sum of squared distances to nearest centroids."""
    cdt = flags.compute_dtype(compute_dtype)
    return _mssc_objective_jit(x, c, impl=impl, compute_dtype=cdt)


@functools.partial(jax.jit, static_argnames=("impl", "compute_dtype"))
def _lloyd_pass_jit(
    x: Array, c: Array, *, impl: str | None = None, compute_dtype: str = "f32",
):
    with jaxhooks.named_scope("kernel.lloyd_pass"):
        impl = resolve_impl(impl)
        s, d = x.shape
        k = c.shape[0]
        if impl == "ref" or d > 4096:
            idx, dist = assign_clusters(
                x, c, impl=impl, compute_dtype=compute_dtype)
            sums, counts = cluster_sums(x, idx, k, impl=impl)
            return idx, dist, sums, counts
        from repro.kernels.lloyd import lloyd_pass_pallas

        bs, bk, _ = _blocks("lloyd", s, k, d, compute_dtype)
        # The fused kernel keeps full-D row blocks resident (lane-padded
        # once); only S and K tile, so x/c are padded exactly once here.
        sp, kp, dp = _round_up(s, bs), _round_up(k, bk), _round_up(d, _LANE)
        xp = jnp.pad(x, ((0, sp - s), (0, dp - d)))
        cp = jnp.pad(c, ((0, kp - k), (0, dp - d)))
        idx, dist, sums, counts = lloyd_pass_pallas(
            xp, cp, k_valid=k, s_valid=s, block_s=bs, block_k=bk,
            compute_dtype=compute_dtype, interpret=(impl == "interpret"),
        )
        return idx[:s], dist[:s], sums[:k, :d], counts[:k]


def lloyd_pass(
    x: Array, c: Array, *, impl: str | None = None,
    compute_dtype: str | None = None,
):
    """Fused Lloyd pass: (idx, dist, sums, counts) with ONE read of x.

    Falls back to assign+cluster_sums (two passes) on the ref path or when
    D exceeds the VMEM row-block budget.
    """
    cdt = flags.compute_dtype(compute_dtype)
    return _lloyd_pass_jit(x, c, impl=impl, compute_dtype=cdt)


# ---------------------------------------------------------------------------
# autotune probe factories (repro.kernels.autotune times these on a miss in
# REPRO_AUTOTUNE=probe mode; deterministic synthetic data, no host RNG)
# ---------------------------------------------------------------------------


def _require_tpu() -> None:
    """Probes time compiled TPU kernels only: an interpret-mode timing says
    nothing about tiles on the chip and must never enter the cache."""
    backend = jax.default_backend()
    if backend != "tpu":
        raise RuntimeError(
            f"REPRO_AUTOTUNE=probe times compiled TPU kernels; the JAX "
            f"backend is {backend!r}")


def _probe_data(s: int, d: int, k: int):
    x = (jnp.arange(s * d, dtype=jnp.float32) % 97).reshape(s, d) * 0.1
    c = (jnp.arange(k * d, dtype=jnp.float32) % 89).reshape(k, d) * 0.1
    return x, c


def _probe_assign(s, k, d, dtype, blocks):
    _require_tpu()
    bs, bk, bd = blocks
    sp, kp, dp = _round_up(s, bs), _round_up(k, bk), _round_up(d, bd)
    x, c = _probe_data(sp, dp, kp)
    return lambda: assign_pallas(
        x, c, k_valid=k, block_s=bs, block_k=bk, block_d=bd,
        compute_dtype=dtype,
    )


def _probe_update(s, k, d, dtype, blocks):
    _require_tpu()
    bs, bk, bd = blocks
    sp, dp = _round_up(s, bs), _round_up(d, bd)
    x, _ = _probe_data(sp, dp, 1)
    idx = (jnp.arange(sp, dtype=jnp.int32) % max(k, 1))
    return lambda: cluster_sums_pallas(
        x, idx, k, block_s=bs, block_k=bk, block_d=bd,
    )


def _probe_lloyd(s, k, d, dtype, blocks):
    from repro.kernels.lloyd import lloyd_pass_pallas

    _require_tpu()
    bs, bk, _ = blocks
    sp, kp, dp = _round_up(s, bs), _round_up(k, bk), _round_up(d, _LANE)
    x, c = _probe_data(sp, dp, kp)
    return lambda: lloyd_pass_pallas(
        x, c, k_valid=k, s_valid=s, block_s=bs, block_k=bk,
        compute_dtype=dtype,
    )


autotune.register_probe("assign", _probe_assign)
autotune.register_probe("update", _probe_update)
autotune.register_probe("lloyd", _probe_lloyd)
