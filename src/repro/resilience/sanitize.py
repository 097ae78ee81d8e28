"""Stream-window sanitization: drop/mask non-finite rows before the device.

The paper's noise experiments (SS7.1) assume noise is *finite*; on real
streams a corrupted shard or overflowed feature produces NaN/Inf rows, and a
single such row drives every distance, objective and centroid to NaN —
poisoning all workers at once. Sanitization happens host-side, before
``jnp.asarray``, so the compiled program never sees a non-finite sample.

Masked rows are replaced (cyclically) by surviving rows rather than dropped:
window shape is part of the jit cache key, so shape-preserving repair keeps
one compiled program per window size instead of one per corruption pattern.

The finiteness screen reads the window in row blocks whose boolean
temporary stays cache-sized, into one preallocated row mask: a window-sized
temporary never exists. Windows of more than one block are split over a
thread pool kept for the life of the process (NumPy's ufunc loops release
the GIL, so the blocks run in parallel); a window of one block (at most
``_BLOCK_ELEMS`` elements, 6 MB of f32) runs on the calling thread. Each
row's verdict is the same ``isfinite(...).all()`` either way, so the result
does not depend on the plan.
"""
from __future__ import annotations

import functools
import os
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import NamedTuple, Optional

import numpy as np

# Elements per row block: 2048 rows at d=768, a 1.5 MB boolean temporary.
_BLOCK_ELEMS = 2048 * 768

_pool: Optional[ThreadPoolExecutor] = None
_pool_lock = threading.Lock()


class ScreenPlan(NamedTuple):
    """How ``sanitize_window`` screens an (m, d) window; a function of the
    shape alone (and of the process's CPU affinity)."""

    rows: int      # rows per block
    blocks: int
    threads: int   # 1: inline on the calling thread


@functools.cache
def _pool_size() -> int:
    """Threads for the screen: the CPUs this process may run on, less two
    for the stream loop and the runtime's copy thread, and at least two
    wherever two CPUs are usable. Read once, as the pool is made once."""
    n = len(os.sched_getaffinity(0))
    return max(min(n, 2), n - 2)


def _get_pool() -> ThreadPoolExecutor:
    global _pool
    with _pool_lock:
        if _pool is None:
            _pool = ThreadPoolExecutor(
                max_workers=_pool_size(), thread_name_prefix="repro-sanitize")
        return _pool


def screen_plan(shape: tuple[int, ...]) -> ScreenPlan:
    """The block size, block count and thread count for a window of
    ``shape`` (m, d)."""
    m, d = shape
    rows = max(1, _BLOCK_ELEMS // max(d, 1))
    blocks = max(1, -(-m // rows))
    threads = min(_pool_size(), blocks)
    return ScreenPlan(rows, blocks, threads)


def _screen(x: np.ndarray, good: np.ndarray, rows: int, blocks: range) -> None:
    """``good[r] = isfinite(x[r]).all()`` over the given row blocks."""
    tmp = np.empty((rows, x.shape[1]), bool)
    for b in blocks:
        lo = b * rows
        hi = min(lo + rows, x.shape[0])
        np.isfinite(x[lo:hi], out=tmp[:hi - lo]).all(axis=1, out=good[lo:hi])


def _finite_rows(x: np.ndarray) -> np.ndarray:
    """The (m,) mask of rows of ``x`` with no NaN or Inf."""
    plan = screen_plan(x.shape)
    good = np.empty(x.shape[0], bool)
    if plan.threads == 1:
        _screen(x, good, plan.rows, range(plan.blocks))
        return good
    pool = _get_pool()
    futures = [pool.submit(_screen, x, good, plan.rows,
                           range(t, plan.blocks, plan.threads))
               for t in range(plan.threads)]
    for f in futures:
        f.result()
    return good


def sanitize_window(x: np.ndarray) -> tuple[Optional[np.ndarray], int]:
    """Replace non-finite rows of a (m, d) window with finite ones.

    Returns ``(clean_window, n_bad_rows)``. The clean window has the same
    shape and dtype as the input; bad rows are overwritten by surviving rows
    chosen cyclically (deterministic, seed-free). If *every* row is
    non-finite the window is unusable and ``(None, m)`` is returned — the
    caller should skip it and count it.
    """
    x = np.asarray(x)
    if x.ndim != 2:
        raise ValueError(f"expected a (m, d) window, got shape {x.shape}")
    bad = ~_finite_rows(x)
    n_bad = int(bad.sum())
    if n_bad == 0:
        return x, 0
    good_idx = np.flatnonzero(~bad)
    if good_idx.size == 0:
        return None, n_bad
    out = np.array(x, copy=True)
    fill = good_idx[np.arange(n_bad) % good_idx.size]
    out[np.flatnonzero(bad)] = x[fill]
    return out, n_bad
