"""The system under test, driven through its public streaming entry.

``FitStream`` builds the program's object once from the configuration and
the traffic mix, and ``run`` feeds it a window iterator through the entry
that users call, returning what the program returned. The program runs with
its defaults: prefetch depth 2, donation on, autotune off; no ``REPRO_*``
variable is set here.
"""
from __future__ import annotations

from typing import Iterable, NamedTuple

import numpy as np


class Outcome(NamedTuple):
    windows: int                 # windows the call finished
    centroids: np.ndarray        # (k, d) the answer, on the host
    objective: float             # the incumbent objective the program reports
    history: np.ndarray          # (rounds, workers) incumbent per round
    worker_centroids: np.ndarray  # (workers, k, d) every worker's incumbent
    worker_objectives: np.ndarray  # (workers,) the objective each reports


def _seed32(seed: int) -> int:
    # The program keys its PRNG with PRNGKey(seed), which keeps 32 bits.
    return seed & 0xFFFFFFFF


class FitStream:
    """``HPClust.fit_stream`` on one chip."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        from repro.core import HPClust, HPClustConfig

        self.rounds = int(traffic["rounds_per_window"])
        self.cfg = HPClustConfig(
            k=config["k"], sample_size=config["sample_size"],
            workers=config["workers"], rounds=self.rounds,
            strategy=config["strategy"], kmeans_iters=config["kmeans_iters"],
            kmeans_tol=config["kmeans_tol"],
            n_candidates=config["n_candidates"], impl=config.get("impl"))
        self.hp = HPClust(self.cfg, seed=_seed32(seed))

    def run(self, windows: Iterable[np.ndarray]) -> Outcome:
        res = self.hp.fit_stream(windows, rounds_per_window=self.rounds)
        return Outcome(res.stats.windows, res.centroids, res.objective,
                       res.history, np.asarray(res.state.centroids),
                       np.asarray(res.state.best_obj))

