"""The rows each worker of the round program drew, replayed from the seed.

``HPClust.fit_stream`` keys its PRNG with ``PRNGKey(seed)``, splits it once,
and splits the second half into one key per worker. In every round a worker
splits its key in two: from the first it draws ``sample_size`` row indices
of the window, uniformly with replacement; the second it splits again and
keeps the first half for the next round (``core/strategies.py``,
``run_rounds`` and ``_worker_round``). The keys run on across windows.

Replaying that chain with ``jax.random`` alone tells the check which rows a
worker's incumbent was fitted and scored on, without taking anything from
the program's run. A change to how the program draws its sample changes
what these rows must be, and is a change to the benchmark too.
"""
from __future__ import annotations

import functools

import jax
import numpy as np


@functools.partial(jax.jit, static_argnames=("workers", "rounds"))
def _sample_keys(key, *, workers: int, rounds: int):
    _, k0 = jax.random.split(key)
    keys = jax.random.split(k0, workers)

    def step(keys, _):
        halves = jax.vmap(jax.random.split)(keys)  # (workers, 2, 2)
        nxt = jax.vmap(lambda k: jax.random.split(k)[0])(halves[:, 1])
        return nxt, halves[:, 0]

    _, sample_keys = jax.lax.scan(step, keys, None, length=rounds)
    return sample_keys  # (rounds, workers, 2)


def incumbent_rounds(history: np.ndarray) -> np.ndarray:
    """Per worker, the last round whose incumbent objective differs from the
    round before: the round whose sample the incumbent was fitted on, since
    an incumbent changes only when a round's result is accepted. -1 where a
    worker never held a finite incumbent."""
    h = np.asarray(history, np.float64)
    prev = np.vstack([np.full((1, h.shape[1]), np.inf), h[:-1]])
    changed = (h != prev) & np.isfinite(h)
    last = np.where(changed.any(axis=0),
                    h.shape[0] - 1 - np.argmax(changed[::-1], axis=0), -1)
    return last.astype(np.int64)


def fit_stream_indices(seed32: int, *, workers: int, rounds: int,
                       sample_size: int, window_rows: int,
                       picks: dict) -> dict:
    """``{worker: row indices}`` of the sample each worker drew in round
    ``picks[worker]`` of a ``fit_stream`` call keyed with ``seed32``; the
    rounds count from the call's first window."""
    keys = _sample_keys(jax.random.PRNGKey(seed32), workers=workers,
                        rounds=rounds)
    return {w: np.asarray(jax.random.randint(keys[r, w], (sample_size,), 0,
                                             window_rows))
            for w, r in picks.items()}
