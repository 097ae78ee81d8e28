"""Pure-jnp oracles for the Pallas kernels.

These are the semantic ground truth: every Pallas kernel in this package is
validated against these functions (interpret mode on CPU, compiled on TPU).
They are also the lowering path used by the CPU-simulated multi-pod dry-runs,
so they must be shardable, numerically robust and free of host callbacks.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array
_F32 = jax.lax.Precision.HIGHEST  # f32 products on a TPU, not one bf16 pass


def pairwise_sq_dists(x: Array, c: Array) -> Array:
    """Squared Euclidean distances between rows of x (s,d) and c (k,d) -> (s,k).

    Uses the expanded form ||x||^2 - 2 x.c + ||c||^2 in f32, clamped at zero
    (the expansion can go slightly negative in floating point). The product
    asks for ``HIGHEST`` precision: a TPU's default is one bf16 pass.
    """
    x = x.astype(jnp.float32)
    c = c.astype(jnp.float32)
    xx = jnp.sum(x * x, axis=-1, keepdims=True)  # (s, 1)
    cc = jnp.sum(c * c, axis=-1)  # (k,)
    d2 = xx - 2.0 * jnp.matmul(x, c.T, precision=_F32) + cc[None, :]
    return jnp.maximum(d2, 0.0)


def assign_ref(x: Array, c: Array) -> tuple[Array, Array]:
    """Nearest-centroid assignment.

    Args:
      x: (s, d) points.
      c: (k, d) centroids.
    Returns:
      idx:  (s,) int32 index of nearest centroid.
      dist: (s,) f32 squared distance to that centroid.
    """
    d2 = pairwise_sq_dists(x, c)
    idx = jnp.argmin(d2, axis=-1).astype(jnp.int32)
    dist = jnp.take_along_axis(d2, idx[:, None].astype(jnp.int32), axis=-1)[:, 0]
    return idx, dist


def assign_ref_batched(x: Array, c: Array, batch: int = 65536) -> tuple[Array, Array]:
    """assign_ref evaluated in row batches via lax.map (bounds peak memory).

    For big s*k this avoids materializing the full (s,k) distance matrix —
    the jnp analogue of the FlashAssign kernel's memory behaviour.
    """
    s = x.shape[0]
    if s <= batch:
        return assign_ref(x, c)
    pad = (-s) % batch
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    xb = xp.reshape(-1, batch, x.shape[1])
    idx, dist = jax.lax.map(lambda xi: assign_ref(xi, c), xb)
    return idx.reshape(-1)[:s], dist.reshape(-1)[:s]


def cluster_sums_ref(x: Array, idx: Array, k: int) -> tuple[Array, Array]:
    """Per-cluster sums and counts.

    Args:
      x:   (s, d) points.
      idx: (s,) int32 cluster assignment in [0, k).
    Returns:
      sums:   (k, d) f32 per-cluster coordinate sums.
      counts: (k,)  f32 per-cluster point counts.
    """
    onehot = jax.nn.one_hot(idx, k, dtype=jnp.float32)  # (s, k)
    sums = jnp.matmul(onehot.T, x.astype(jnp.float32), precision=_F32)
    counts = jnp.sum(onehot, axis=0)
    return sums, counts


def lloyd_update_ref(x: Array, c: Array) -> tuple[Array, Array, Array, Array]:
    """One Lloyd iteration: assign + recompute means.

    Empty (degenerate) clusters keep their previous centroid and are flagged.

    Returns:
      new_c:    (k, d) f32 updated centroids.
      obj:      ()    f32 sum of squared distances under the *old* centroids.
      counts:   (k,)  f32 cluster sizes.
      degenerate: (k,) bool mask of empty clusters.
    """
    k = c.shape[0]
    idx, dist = assign_ref(x, c)
    sums, counts = cluster_sums_ref(x, idx, k)
    degenerate = counts == 0
    denom = jnp.maximum(counts, 1.0)[:, None]
    new_c = jnp.where(degenerate[:, None], c.astype(jnp.float32), sums / denom)
    return new_c, jnp.sum(dist), counts, degenerate


def mssc_objective_ref(x: Array, c: Array) -> Array:
    """f(C, X) = sum_i min_j ||x_i - c_j||^2 (Equation 1 of the paper)."""
    _, dist = assign_ref(x, c)
    return jnp.sum(dist)
