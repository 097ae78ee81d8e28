"""What decides ``correct``: the timed path's answers against the plain
reference (``bench/reference.py``), each number beside its own limit.

The limits live in the configuration's file under ``limits``; PERF.md gives
the readings each was set from. The numbers:

* ``objective_gap``: the objective each worker reports for its incumbent,
  as the round program computed it on the sample it fitted the incumbent on,
  against the float64 objective of that incumbent on those rows. The rows
  are replayed from the seed (``bench/draws.py``); the round is the last one
  in which the worker's reported objective changed. The largest relative
  gap over the workers. This is the precision of the timed path's distances
  and sums as the window ran them; a worker with no finite incumbent reads
  infinite.
* ``answer_gap``: the answer is the incumbent of the worker that reports the
  least objective (Algorithm 3's last line, done here plainly). The largest
  gap between the returned centroids and that incumbent: exactly 0.
* ``incumbent_gap``: the incumbent objective the program reports, per sample
  row, against the float64 objective of the returned centroids per holdout
  row. Keep-the-best favours lucky samples, so this only catches a wrong
  scale: a step that did nothing, a sample half used, an altered answer.
* ``lloyd_gain``: the answer says it is a set of converged K-means
  centroids; one more float64 Lloyd update on the holdout would lower the
  holdout objective by this share. Sampling noise leaves about 2k/n; a
  centroid altered, duplicated or never fitted leaves much more.
* ``monotone_violations``: rounds in which some worker's incumbent got worse;
  keep-the-best allows none.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

from bench import draws, reference


class Number(NamedTuple):
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value) and self.value <= self.limit)


def _objective_gap(config: dict, traffic: dict, outcome, ring: list,
                   seed: int) -> float:
    h = np.asarray(outcome.history, np.float64)
    picks = draws.incumbent_rounds(h)
    if np.any(picks < 0):
        return float("inf")
    s = int(config["sample_size"])
    rows = draws.fit_stream_indices(
        seed & 0xFFFFFFFF, workers=h.shape[1], rounds=h.shape[0],
        sample_size=s, window_rows=len(ring[0]),
        picks={w: int(r) for w, r in enumerate(picks)})
    rpw = int(traffic["rounds_per_window"])
    reported = np.asarray(outcome.worker_objectives, np.float64)
    wc = np.asarray(outcome.worker_centroids)
    gap = 0.0
    for w, idx in rows.items():
        window = ring[(int(picks[w]) // rpw) % len(ring)]
        f64 = reference.objective(window[idx], wc[w])
        gap = max(gap, abs(reported[w] - f64) / max(f64, 1e-300))
    return gap


def _answer_gap(outcome) -> float:
    obj = np.asarray(outcome.worker_objectives, np.float64)
    obj = np.where(np.isfinite(obj), obj, np.inf)
    c = np.asarray(outcome.centroids, np.float64)
    wc = np.asarray(outcome.worker_centroids, np.float64)
    return min(float(np.max(np.abs(c - wc[w])))
               for w in np.flatnonzero(obj == obj.min()))


def holdout_ratio(outcome, holdout: np.ndarray, centres: np.ndarray,
                  hold: float | None = None) -> float:
    """The end-to-end quality: the float64 holdout objective of the returned
    centroids over that of the generating centres. A search that ends in a
    poorer optimum reads higher. It is reported, and decides nothing."""
    if hold is None:
        hold = reference.objective(holdout, outcome.centroids)
    return hold / reference.objective(holdout, centres)


def compare(config: dict, traffic: dict, outcome, ring: list,
            holdout: np.ndarray, seed: int) -> tuple[list[Number], float]:
    """Every number that decides ``correct``, each with its limit; and the
    float64 holdout objective of the returned centroids."""
    limits = config["limits"]
    if outcome.windows < 1:
        return [Number("windows_answered", 0.0, -1.0)], float("nan")
    hold = reference.objective(holdout, outcome.centroids)
    per_sample = outcome.objective / config["sample_size"]
    per_holdout = hold / len(holdout)
    h = np.asarray(outcome.history, np.float64)
    violations = int(np.sum(~(h[1:] <= h[:-1]))) if len(h) > 1 else 0
    values = {
        "objective_gap": _objective_gap(config, traffic, outcome, ring, seed),
        "answer_gap": _answer_gap(outcome),
        "incumbent_gap": abs(per_sample - per_holdout) / per_holdout,
        "lloyd_gain": reference.lloyd_gain(holdout, outcome.centroids),
        "monotone_violations": float(violations),
    }
    return [Number(n, float(v), float(limits[n]))
            for n, v in values.items()], hold
