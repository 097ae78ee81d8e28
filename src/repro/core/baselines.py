"""Baseline algorithms the paper compares against (SS6.2).

* Forgy K-means  (Algorithm 1)  — full-data Lloyd from a uniform-random seed.
* K-means++ K-means             — full-data Lloyd from greedy K-means++ seeds;
  the plain reference ``chip_smoke.py`` holds the streaming engine to.
* PBK-BDC        (Algorithm 2)  — partition X into segments of size p,
  K-means each, pool the centroids, K-means the pool, final assign.
* Minibatch K-means (Sculley 2010, paper SS2) — per-batch SGD centroid update
  with per-center counts; an extra lower baseline.

All are batched so the "big data" datasets of the scaling experiment never
materialize an (m, k) distance matrix.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import kmeans as km  # module import (package does not re-export the fn)
from repro.core import kmeanspp
from repro.kernels import ops

Array = jax.Array

# Jitted once at import (analysis JH003): the per-call wrappers these replace
# keyed the compile cache on a fresh lambda identity, re-tracing every call.
_jit_kmeans = jax.jit(km.kmeans, static_argnames=("max_iters", "tol", "impl"))
_jit_objective = jax.jit(ops.mssc_objective, static_argnames=("impl",))
_jit_kmeanspp = jax.jit(kmeanspp.kmeanspp, static_argnames=("k",))


class BaselineResult(NamedTuple):
    centroids: np.ndarray
    objective: float
    iterations: int


def _full_objective(x: np.ndarray, c: Array, impl, batch: int = 1 << 17) -> float:
    c = jnp.asarray(c)
    return sum(
        float(_jit_objective(jnp.asarray(x[i : i + batch]), c, impl=impl))
        for i in range(0, len(x), batch)
    )


def forgy_kmeans(
    x: np.ndarray,
    k: int,
    *,
    seed: int = 0,
    max_iters: int = 300,
    tol: float = 1e-4,
    impl: str | None = None,
) -> BaselineResult:
    """Algorithm 1: uniform-random initial centroids + Lloyd to convergence."""
    rng = np.random.default_rng(seed)
    c0 = jnp.asarray(x[rng.choice(len(x), size=k, replace=False)], jnp.float32)
    res = _jit_kmeans(
        jnp.asarray(x, jnp.float32), c0, max_iters=max_iters, tol=tol, impl=impl
    )
    return BaselineResult(
        np.asarray(res.centroids), float(res.objective), int(res.iterations)
    )


def kmeanspp_kmeans(
    x: np.ndarray | Array,
    k: int,
    *,
    seed: int = 0,
    max_iters: int = 300,
    tol: float = 1e-4,
    impl: str | None = None,
) -> BaselineResult:
    """Greedy K-means++ seeds drawn on all of x, then Lloyd to convergence."""
    xd = jnp.asarray(x, jnp.float32)
    c0 = _jit_kmeanspp(jax.random.PRNGKey(seed), xd, k)
    res = _jit_kmeans(xd, c0, max_iters=max_iters, tol=tol, impl=impl)
    return BaselineResult(
        np.asarray(res.centroids), float(res.objective), int(res.iterations)
    )


def pbk_bdc(
    x: np.ndarray,
    k: int,
    *,
    segment_size: int = 4096,
    seed: int = 0,
    max_iters: int = 300,
    tol: float = 1e-4,
    impl: str | None = None,
) -> BaselineResult:
    """Algorithm 2 (Alguliyev et al. 2021).

    Segments are clustered with K-means (Forgy seeds), their centroids pooled
    into repository P, which is clustered again; final objective is evaluated
    on the full dataset.
    """
    rng = np.random.default_rng(seed)
    m = len(x)
    n_seg = max(1, m // segment_size)
    perm = rng.permutation(m)

    def run(xx, cc):
        return _jit_kmeans(xx, cc, max_iters=max_iters, tol=tol, impl=impl)

    pool = []
    iters = 0
    for si in range(n_seg):
        seg = x[perm[si * segment_size : (si + 1) * segment_size]]
        if len(seg) < k:
            continue
        c0 = jnp.asarray(seg[rng.choice(len(seg), size=k, replace=False)], jnp.float32)
        res = run(jnp.asarray(seg, jnp.float32), c0)
        pool.append(np.asarray(res.centroids))
        iters += int(res.iterations)
    p = np.concatenate(pool, axis=0)
    c0 = jnp.asarray(p[rng.choice(len(p), size=k, replace=False)], jnp.float32)
    res = run(jnp.asarray(p, jnp.float32), c0)
    obj = _full_objective(x, res.centroids, impl)
    return BaselineResult(np.asarray(res.centroids), obj, iters + int(res.iterations))


def minibatch_kmeans(
    x: np.ndarray,
    k: int,
    *,
    batch_size: int = 1024,
    steps: int = 100,
    seed: int = 0,
    impl: str | None = None,
) -> BaselineResult:
    """Sculley's web-scale K-means: per-center learning rates 1/n_c."""
    rng = np.random.default_rng(seed)
    c = jnp.asarray(x[rng.choice(len(x), size=k, replace=False)], jnp.float32)
    counts = jnp.zeros((k,), jnp.float32)

    for _ in range(steps):
        xb = jnp.asarray(x[rng.integers(0, len(x), size=batch_size)], jnp.float32)
        c, counts = _minibatch_step(c, counts, xb, k=k, impl=impl)
    obj = _full_objective(x, c, impl)
    return BaselineResult(np.asarray(c), obj, steps)


@functools.partial(jax.jit, static_argnames=("k", "impl"))
def _minibatch_step(c, counts, xb, *, k: int, impl: str | None):
    idx, _ = ops.assign_clusters(xb, c, impl=impl)
    sums, n = ops.cluster_sums(xb, idx, k, impl=impl)
    new_counts = counts + n
    lr = jnp.where(n > 0, n / jnp.maximum(new_counts, 1.0), 0.0)[:, None]
    target = sums / jnp.maximum(n, 1.0)[:, None]
    return c + lr * (target - c), new_counts
