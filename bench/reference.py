"""The plain reference: nearest-centroid distances, objective and cluster
sums in numpy float64. It imports nothing of the program under test."""
from __future__ import annotations

import numpy as np


def sq_dists(x: np.ndarray, c: np.ndarray) -> np.ndarray:
    """(n, k) squared distances in float64. The norm expansion loses about
    1e-16 of ||x||^2, some nine orders below float32's rounding."""
    x64 = np.asarray(x, np.float64)
    c64 = np.asarray(c, np.float64)
    d2 = (np.einsum("nd,nd->n", x64, x64)[:, None] - 2.0 * x64 @ c64.T
          + np.einsum("kd,kd->k", c64, c64)[None])
    return np.maximum(d2, 0.0)


def objective(x: np.ndarray, c: np.ndarray, *, batch: int = 1 << 14) -> float:
    """f(C, X): the sum over rows of the squared distance to the nearest
    centroid, in float64."""
    total = 0.0
    for i in range(0, len(x), batch):
        total += float(sq_dists(x[i:i + batch], c).min(axis=1).sum())
    return total


def cluster_sums(x: np.ndarray, labels: np.ndarray,
                 k: int) -> tuple[np.ndarray, np.ndarray]:
    """Per-cluster row sums (k, d) and counts (k,) in float64."""
    onehot = np.zeros((len(labels), k), np.float64)
    onehot[np.arange(len(labels)), labels] = 1.0
    return onehot.T @ np.asarray(x, np.float64), onehot.sum(axis=0)


def lloyd_gain(x: np.ndarray, c: np.ndarray, *, batch: int = 1 << 14) -> float:
    """How far ``c`` is from a fixed point of Lloyd's update on ``x``: the
    share of f(C, X) that one more update would remove, in float64. With
    the assignments held, moving each centroid to the mean of its rows
    lowers the objective by exactly sum_j n_j |c_j - mean_j|^2."""
    c64 = np.asarray(c, np.float64)
    k, d = c64.shape
    sums = np.zeros((k, d), np.float64)
    counts = np.zeros(k, np.float64)
    total = 0.0
    for i in range(0, len(x), batch):
        xb = np.asarray(x[i:i + batch], np.float64)
        d2 = sq_dists(xb, c64)
        lab = d2.argmin(axis=1)
        total += float(d2[np.arange(len(xb)), lab].sum())
        s, n = cluster_sums(xb, lab, k)
        sums += s
        counts += n
    live = counts > 0
    means = sums[live] / counts[live, None]
    gain = float((counts[live] * ((c64[live] - means) ** 2).sum(axis=1)).sum())
    return gain / max(total, 1e-300)
