"""HPClust parallel strategies (paper SS4, Algorithms 3-5) as one XLA program.

The paper runs OS threads that mutate shared incumbents under locks. Here the
entire multi-round, multi-worker search compiles to a single ``lax.scan``:

  * workers are a leading axis handled by ``vmap`` (this module — the
    reference/host implementation) or by the ``data`` mesh axis via
    ``shard_map`` (``repro.core.sharded`` — the pod implementation);
  * "keep the best" is a pure ``jnp.where`` — race-free by construction;
  * cooperative sharing is an argmin-select over the worker axis (a masked
    ``psum`` in the sharded twin);
  * the hybrid T1/T2 wall-clock split becomes a round-count split
    (``t1_rounds``), flipping a per-round coordination flag.

Strategies:
  inner        — ONE worker (all parallelism inside the distance evals;
                 on the mesh this is the `model` axis — here it degrades to
                 vmapped/W=1 execution).
  competitive  — W workers, never communicate, argmin at the end (Alg. 3).
  cooperative  — every round each worker restarts from the global best (Alg. 4).
  hybrid       — competitive for t1_rounds, cooperative after (Alg. 5).
  hybrid2      — beyond-paper: hierarchical hybrid for multi-pod meshes;
                 on the vmap path it behaves like hybrid with group-local
                 cooperation (groups = pods) + rare cross-group sync.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import kmeans as km
from repro.core import kmeanspp
from repro.obs import jaxhooks

Array = jax.Array

STRATEGIES = ("inner", "sequential", "competitive", "cooperative", "hybrid", "hybrid2")


@dataclasses.dataclass(frozen=True)
class HPClustConfig:
    """Static configuration of one HPClust run (paper SS6.5 defaults)."""

    k: int                      # number of clusters
    sample_size: int            # s
    workers: int = 8            # W (paper: 8 CPUs)
    rounds: int = 16            # stop condition: max processed samples / worker
    strategy: str = "hybrid"
    t1_rounds: int | None = None  # hybrid switch point; default rounds // 2
    kmeans_iters: int = 300     # paper SS6.5
    kmeans_tol: float = 1e-4    # paper SS6.5
    n_candidates: int = 3       # K-means++ greedy candidates (paper SS6.5)
    groups: int = 1             # hybrid2: number of pods / worker groups
    sync_every: int = 4         # hybrid2: cross-group cooperation period
    impl: str | None = None     # kernel impl: auto/pallas/interpret/ref
    fixed_schedule: bool = False  # use kmeans_fixed (static SPMD trip count)

    def __post_init__(self):
        if self.strategy not in STRATEGIES:
            raise ValueError(f"strategy {self.strategy!r} not in {STRATEGIES}")
        if self.workers < 1 or self.k < 1 or self.sample_size < 1:
            raise ValueError("workers, k and sample_size must be positive")
        if self.strategy == "hybrid2" and self.workers % self.groups:
            raise ValueError("hybrid2 needs workers divisible by groups")

    @property
    def effective_t1(self) -> int:
        return self.rounds // 2 if self.t1_rounds is None else self.t1_rounds


class WorkerState(NamedTuple):
    centroids: Array   # (W, k, d) f32 incumbent C_w
    best_obj: Array    # (W,) f32 incumbent sample objective \hat f_w
    degenerate: Array  # (W, k) bool — empty clusters of the incumbent
    key: Array         # (W, 2) uint32 per-worker PRNG


class RoundMetrics(NamedTuple):
    best_obj: Array      # (W,) incumbent objective after the round
    accepted: Array      # (W,) bool — did the round improve the incumbent
    kmeans_iters: Array  # (W,) int32
    quarantined: Array   # (W,) bool — poisoned incumbent re-seeded this round
    reseed_rows: Array   # (W,) int32 — degenerate warm-start rows redrawn


def _mask_nonfinite(obj: Array) -> Array:
    """Objectives safe for argmin/select: NaN (poisoned arithmetic) and -inf
    (corrupt window) map to +inf so they can never win a selection; +inf is
    the legitimate "no incumbent yet" sentinel and maps to itself."""
    return jnp.where(jnp.isfinite(obj), obj, jnp.inf)


def quarantine_nonfinite(state: WorkerState) -> tuple[WorkerState, Array]:
    """Re-seed poisoned workers from the healthiest survivor.

    A worker is poisoned when its incumbent objective is NaN/-inf or any
    incumbent centroid is non-finite. It is quarantined by copying the
    healthiest (finite-argmin) survivor's centroids and degenerate mask and
    resetting its objective to +inf — so its next round warm-starts from the
    survivor (degenerate rows re-drawn by ``kmeanspp.reseed_degenerate`` in
    ``_reseed``) and any finite result is accepted. If *every* worker
    is poisoned, all reset to the virgin all-degenerate state and the search
    re-seeds from scratch, exactly like round 0.
    """
    finite_c = jnp.all(jnp.isfinite(state.centroids), axis=(1, 2))
    bad = jnp.isnan(state.best_obj) | (state.best_obj == -jnp.inf) | ~finite_c
    donor = jnp.argmin(jnp.where(bad, jnp.inf, state.best_obj))
    donor_bad = bad[donor]  # true only when every worker is poisoned
    donor_c = jnp.where(donor_bad, 0.0, state.centroids[donor])
    donor_d = jnp.where(donor_bad, True, state.degenerate[donor])
    new_c = jnp.where(bad[:, None, None], donor_c[None], state.centroids)
    new_o = jnp.where(bad, jnp.inf, state.best_obj)
    new_d = jnp.where(bad[:, None], donor_d[None], state.degenerate)
    return WorkerState(new_c, new_o, new_d, state.key), bad


def init_state(key: Array, cfg: HPClustConfig, d: int) -> WorkerState:
    """All centroids degenerate, objectives +inf (Algorithms 3-5, lines 1-4)."""
    w = cfg.workers
    return WorkerState(
        centroids=jnp.zeros((w, cfg.k, d), jnp.float32),
        best_obj=jnp.full((w,), jnp.inf, jnp.float32),
        degenerate=jnp.ones((w, cfg.k), jnp.bool_),
        key=jax.random.split(key, w),
    )


def _reseed(keys: Array, samples: Array, base_c: Array, base_deg: Array,
            cfg: HPClustConfig) -> Array:
    """K-means++ redraw of every worker's degenerate warm-start rows
    (Algorithm 3 line 8), run only in a round where some worker has one.

    The ``cond`` sits outside the ``vmap`` over workers: under it the
    predicate is batched and the ``cond`` becomes a ``select`` that computes
    the redraw's distance pass for every row of every worker in every round.
    With no degenerate row the redraw returns its input, so passing
    ``base_c`` through gives the same bits.
    """
    def redraw():
        return jax.vmap(functools.partial(
            kmeanspp.reseed_degenerate, n_candidates=cfg.n_candidates
        ))(keys, samples, base_c, base_deg)

    return jax.lax.cond(jnp.any(base_deg), redraw, lambda: base_c)


def _worker_round(
    state_c: Array,
    state_obj: Array,
    state_deg: Array,
    seeded: Array,
    sample: Array,
    cfg: HPClustConfig,
):
    """One HPClust round for one worker from its seeded warm start
    (Algorithm 3 lines 9-18)."""
    with jaxhooks.named_scope("round.lloyd"):
        if cfg.fixed_schedule:
            res = km.kmeans_fixed(
                sample, seeded, iters=min(cfg.kmeans_iters, 64),
                tol=cfg.kmeans_tol, impl=cfg.impl,
            )
        else:
            res = km.kmeans(
                sample, seeded, max_iters=cfg.kmeans_iters,
                tol=cfg.kmeans_tol, impl=cfg.impl,
            )
    # A non-finite candidate objective (corrupt sample, degenerate math) can
    # never displace the incumbent — -inf would otherwise "win" the compare.
    accept = (res.objective < state_obj) & jnp.isfinite(res.objective)
    new_c = jnp.where(accept, res.centroids, state_c)
    new_obj = jnp.where(accept, res.objective, state_obj)
    new_deg = jnp.where(accept, res.counts == 0, state_deg)
    return new_c, new_obj, new_deg, accept, res.iterations


def _select_base(state: WorkerState, coop: Array, cfg: HPClustConfig):
    """Per-round warm-start selection: own incumbent vs (group) best."""
    w = cfg.workers
    if cfg.strategy in ("inner", "sequential", "competitive"):
        return state.centroids, state.degenerate
    if cfg.strategy == "hybrid2":
        g = cfg.groups
        per = w // g
        obj_g = _mask_nonfinite(state.best_obj).reshape(g, per)
        best_in_group = jnp.argmin(obj_g, axis=1)  # (g,)
        flat_best = best_in_group + jnp.arange(g) * per  # index into W
        base_c_g = state.centroids[flat_best]  # (g, k, d)
        base_d_g = state.degenerate[flat_best]
        base_c = jnp.repeat(base_c_g, per, axis=0)
        base_d = jnp.repeat(base_d_g, per, axis=0)
    else:
        best = jnp.argmin(_mask_nonfinite(state.best_obj))
        base_c = jnp.broadcast_to(state.centroids[best], state.centroids.shape)
        base_d = jnp.broadcast_to(state.degenerate[best], state.degenerate.shape)
    coop_b = jnp.broadcast_to(coop, (w,))
    base_c = jnp.where(coop_b[:, None, None], base_c, state.centroids)
    base_d = jnp.where(coop_b[:, None], base_d, state.degenerate)
    return base_c, base_d


def _coop_flag(r: Array, cfg: HPClustConfig) -> Array:
    if cfg.strategy in ("inner", "sequential", "competitive"):
        return jnp.bool_(False)
    if cfg.strategy == "cooperative":
        return jnp.bool_(True)
    return r >= cfg.effective_t1  # hybrid / hybrid2


def _cross_group_sync(state: WorkerState, r: Array, cfg: HPClustConfig) -> WorkerState:
    """hybrid2: every sync_every rounds, the global best replaces each
    group's *worst* incumbent (keeps diversity; beyond-paper)."""
    if cfg.strategy != "hybrid2" or cfg.groups <= 1:
        return state
    g, per = cfg.groups, cfg.workers // cfg.groups
    do = (r + 1) % cfg.sync_every == 0
    safe_obj = _mask_nonfinite(state.best_obj)
    gbest = jnp.argmin(safe_obj)
    obj_g = safe_obj.reshape(g, per)
    worst_in_group = jnp.argmax(obj_g, axis=1) + jnp.arange(g) * per  # (g,)
    replace = jnp.zeros((cfg.workers,), jnp.bool_).at[worst_in_group].set(True)
    # Don't overwrite the global best itself.
    replace = replace & (jnp.arange(cfg.workers) != gbest) & do
    new_c = jnp.where(replace[:, None, None], state.centroids[gbest], state.centroids)
    new_o = jnp.where(replace, state.best_obj[gbest], state.best_obj)
    new_d = jnp.where(replace[:, None], state.degenerate[gbest], state.degenerate)
    return WorkerState(new_c, new_o, new_d, state.key)


def run_rounds(
    state: WorkerState,
    data: Array,
    cfg: HPClustConfig,
) -> tuple[WorkerState, RoundMetrics]:
    """Run ``cfg.rounds`` HPClust rounds over a device-resident window,
    continuing from ``state`` (incumbents persist across stream windows —
    the MSSC-ITD semantics).

    ``data`` is the current reservoir window of the (conceptually infinite)
    stream: (m, d). Each worker draws an independent uniform sample of size
    ``cfg.sample_size`` per round (with replacement — m >> s in the ITD
    regime, see DESIGN.md).
    """
    m, _ = data.shape

    def round_fn(state: WorkerState, r: Array):
        # named_scope labels survive into HLO metadata, so XLA profiles of
        # the scanned round body stay attributable to algorithm phases.
        with jaxhooks.named_scope("round.quarantine"):
            state, quarantined = quarantine_nonfinite(state)
        with jaxhooks.named_scope("round.select_base"):
            coop = _coop_flag(r, cfg)
            base_c, base_deg = _select_base(state, coop, cfg)
        with jaxhooks.named_scope("round.sample"):
            keys = jax.vmap(lambda kk: jax.random.split(kk))(state.key)
            sample_keys, next_keys = keys[:, 0], keys[:, 1]
            idx = jax.vmap(
                lambda kk: jax.random.randint(kk, (cfg.sample_size,), 0, m)
            )(sample_keys)
            samples = data[idx]  # (W, s, d)
            # The first half rides to the next round, the second seeds.
            keys = jax.vmap(lambda kk: jax.random.split(kk))(next_keys)
            carry_keys, seed_keys = keys[:, 0], keys[:, 1]
        # The sharded engine's scope names: a profile reads both engines alike.
        with jaxhooks.named_scope("round.reseed"):
            reseed_rows = jnp.sum(base_deg, axis=1, dtype=jnp.int32)
            seeded = _reseed(seed_keys, samples, base_c, base_deg, cfg)
        with jaxhooks.named_scope("round.worker_round"):
            new_c, new_obj, new_deg, accepted, iters = jax.vmap(
                lambda c, o, dg, sc, sm: _worker_round(c, o, dg, sc, sm, cfg)
            )(
                state.centroids,
                state.best_obj,
                state.degenerate,
                seeded,
                samples,
            )
        new_state = WorkerState(new_c, new_obj, new_deg, carry_keys)
        with jaxhooks.named_scope("round.cross_group_sync"):
            new_state = _cross_group_sync(new_state, r, cfg)
        return new_state, RoundMetrics(
            new_state.best_obj, accepted, iters, quarantined, reseed_rows
        )

    return jax.lax.scan(round_fn, state, jnp.arange(cfg.rounds))


def run_hpclust(
    key: Array,
    data: Array,
    cfg: HPClustConfig,
) -> tuple[WorkerState, RoundMetrics]:
    """Fresh run: init all-degenerate worker states, then run_rounds."""
    key, k_init = jax.random.split(key)
    state = init_state(k_init, cfg, data.shape[1])
    return run_rounds(state, data, cfg)


def best_of(state: WorkerState) -> tuple[Array, Array]:
    """Algorithm 3 line 21: centroids of the worker with minimum \\hat f_w.

    Non-finite incumbents (poisoned workers) are masked out of the argmin."""
    w = jnp.argmin(_mask_nonfinite(state.best_obj))
    return state.centroids[w], state.best_obj[w]
