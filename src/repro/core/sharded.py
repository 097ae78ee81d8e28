"""HPClust on the production mesh: shard_map SPMD implementation.

Mesh mapping (DESIGN.md SS4):
  * workers              <-> the ``data`` axis (and ``pod`` x ``data`` on the
                             multi-pod mesh) — competitive/cooperative tier;
  * inner parallelism    <-> the ``model`` axis — each worker's sample (and
                             its reservoir shard) is split 16 ways; distance
                             evaluation is local, centroid updates and
                             objectives are ``psum`` over ``model``.

Everything that Algorithms 3-5 do with locks becomes a collective:

  keep-the-best            pure jnp.where per worker group
  cooperative best select  pmin(objective) + owner-masked psum of centroids
  K-means++ / reseed       *global* D^2 categorical draws via the Gumbel-max
                           trick: a psum/pmax over the ``model`` axis turns
                           per-shard maxima into an exact global categorical
                           sample — no gather, no host round-trip
  hybrid T1/T2             static round-count split of a lax.scan
  hybrid2 (beyond paper)   cooperative psum over ('data',) every round, and
                           over ('pod','data') every ``sync_every`` rounds

The Lloyd loop uses the fixed-trip-count variant (kmeans logic inlined with
done-masking): a static schedule keeps the SPMD collective program uniform
across worker groups. See DESIGN.md SS2 for why this replaces the paper's
convergence-triggered exit.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.strategies import HPClustConfig
from repro.kernels import ref
from repro.obs import jaxhooks

Array = jax.Array
_INT_MAX = jnp.iinfo(jnp.int32).max


class ShardedState(NamedTuple):
    """Worker incumbents, leading axis = workers (sharded over worker axes).

    Beyond the incumbents themselves the state carries everything a restart
    needs (the elastic/resumable contract, mirroring the single-host
    ``WorkerState``):

      * ``key`` — per-worker-group PRNG keys. Round keys derive as
        ``fold_in(key_w, rounds_done + r)``, so a run restored from a
        checkpoint replays the exact sample draws the uninterrupted run
        would have made (bit-for-bit on the same mesh).
      * ``alive`` — host-controlled liveness mask. A dead worker group is
        frozen: it never accepts a round result, contributes ``+inf`` to
        every cooperative/hybrid2 selection, and never receives the global
        best. The launcher flips this for quarantined groups on a degraded
        mesh (see ``repro.launch.elastic``).
      * ``rounds_done`` — global round counter (scalar), the PRNG offset.
    """

    centroids: Array    # (W, k, d) f32
    best_obj: Array     # (W,) f32
    degenerate: Array   # (W, k) bool
    key: Array          # (W, 2) uint32 per-worker-group PRNG
    alive: Array        # (W,) bool liveness mask
    rounds_done: Array  # () int32 global round counter


# ---------------------------------------------------------------------------
# collective helpers (all run *inside* shard_map)
# ---------------------------------------------------------------------------

def _owner_mask(value: Array, axes, sizes: dict, *, select_min: bool) -> Array:
    """Boolean: is this device('s group) the unique arg-extremum over axes?

    Ties broken towards the lowest flat axis index, so exactly one group
    wins. ``sizes`` carries the static mesh axis sizes.
    """
    best = jax.lax.pmin(value, axes) if select_min else jax.lax.pmax(value, axes)
    cand = value <= best if select_min else value >= best
    idx = jnp.int32(0)
    axes_t = (axes,) if isinstance(axes, str) else tuple(axes)
    for ax in axes_t:
        idx = idx * sizes[ax] + jax.lax.axis_index(ax)
    owner_idx = jax.lax.pmin(jnp.where(cand, idx, _INT_MAX), axes)
    return cand & (idx == owner_idx)


def _broadcast_from_owner(tree, owner: Array, axes):
    """psum of owner-masked values == broadcast of the owner's values."""
    return jax.tree.map(
        lambda v: jax.lax.psum(
            jnp.where(
                owner.astype(jnp.bool_).reshape((1,) * v.ndim),
                v.astype(jnp.float32),
                0.0,
            ),
            axes,
        ),
        tree,
    )


def _global_categorical_row(
    key: Array, weights: Array, x: Array, axis: str, sizes: dict
):
    """One global categorical draw (prob ∝ weights) over rows sharded on
    ``axis``; returns the winning row of x. Gumbel-max: global argmax of
    log w + Gumbel noise is an exact categorical sample."""
    g = jax.random.gumbel(key, weights.shape, dtype=jnp.float32)
    val = jnp.log(jnp.maximum(weights, 1e-30)) + g
    lmax = jnp.max(val)
    larg = jnp.argmax(val)
    owner = _owner_mask(lmax, axis, sizes, select_min=False)
    row = jnp.where(owner, x[larg], jnp.zeros_like(x[larg]))
    return jax.lax.psum(row, axis)


# ---------------------------------------------------------------------------
# sharded K-means++ reseed + Lloyd
# ---------------------------------------------------------------------------

def _sq_dists_to_point(x: Array, p: Array) -> Array:
    diff = x - p[None, :]
    return jnp.sum(diff * diff, axis=-1)


def _reseed_degenerate_sharded(
    key: Array, x: Array, c: Array, mask: Array, cfg: HPClustConfig,
    inner_axis: str, sizes: dict,
) -> Array:
    """reseed_degenerate with x sharded over inner_axis (global D^2 draws)."""
    xf = x.astype(jnp.float32)
    cf = c.astype(jnp.float32)
    d2 = (
        jnp.sum(xf * xf, axis=1, keepdims=True)
        - 2.0 * xf @ cf.T
        + jnp.sum(cf * cf, axis=1)[None, :]
    )
    d2 = jnp.maximum(d2, 0.0)
    d2 = jnp.where(mask[None, :], jnp.inf, d2)
    mind = jnp.min(d2, axis=1)
    mind = jnp.where(jnp.isinf(mind), 1.0, mind)
    # Decorrelate gumbel noise across inner shards (global draw needs iid
    # noise per *global* row).
    key = jax.random.fold_in(key, jax.lax.axis_index(inner_axis))

    def body(j, state):
        cc, mind, key = state
        key, kj = jax.random.split(key)
        cand_keys = jax.random.split(kj, cfg.n_candidates)
        cands = jnp.stack(
            [
                _global_categorical_row(
                    cand_keys[l], mind, xf, inner_axis, sizes)
                for l in range(cfg.n_candidates)
            ]
        )  # (L, d)
        cand_d2 = jax.vmap(lambda p: _sq_dists_to_point(xf, p))(cands)  # (L, s_loc)
        new_minds = jnp.minimum(mind[None, :], cand_d2)
        potentials = jax.lax.psum(jnp.sum(new_minds, axis=1), inner_axis)  # (L,)
        best = jnp.argmin(potentials)
        # Masked (static-shape) update: no lax.cond so the collective
        # schedule is identical on every worker group.
        new_c_j = jnp.where(mask[j], cands[best], cc[j])
        new_mind_if_live = jnp.minimum(mind, _sq_dists_to_point(xf, cc[j]))
        new_mind = jnp.where(mask[j], new_minds[best], new_mind_if_live)
        return cc.at[j].set(new_c_j), new_mind, key

    cc, _, _ = jax.lax.fori_loop(0, cfg.k, body, (cf, mind, key))
    return cc


def _lloyd_sharded(
    x: Array, c0: Array, cfg: HPClustConfig, inner_axis: str
):
    """Fixed-schedule Lloyd with psum(sums, counts, obj) over the inner axis."""
    k = cfg.k

    def one(c):
        # Local assignment and sums on this shard (f32 products, as in the
        # reference), then summed over the inner-parallel tier.
        idx, dist = ref.assign_ref(x, c)
        sums, counts = ref.cluster_sums_ref(x, idx, k)
        sums = jax.lax.psum(sums, inner_axis)
        counts = jax.lax.psum(counts, inner_axis)
        obj = jax.lax.psum(jnp.sum(dist), inner_axis)
        new_c = jnp.where(
            (counts == 0)[:, None], c, sums / jnp.maximum(counts, 1.0)[:, None]
        )
        return new_c, obj, counts

    def body(_, state):
        c, prev_obj, done, _ = state
        new_c, obj, counts = one(c)
        improved = (prev_obj - obj) > cfg.kmeans_tol * jnp.maximum(obj, 1e-30)
        now_done = done | ~improved
        return (
            jnp.where(done, c, new_c),
            jnp.where(done, prev_obj, obj),
            now_done,
            counts,
        )

    iters = min(cfg.kmeans_iters, 64)
    c0 = c0.astype(jnp.float32)
    zero_counts = jnp.zeros((k,), jnp.float32)
    c, _, _, _ = jax.lax.fori_loop(
        0, iters, body, (c0, jnp.inf, jnp.bool_(False), zero_counts)
    )
    # Final stats under returned centroids.
    _, obj, counts = one(c)
    return c, obj, counts


# ---------------------------------------------------------------------------
# the sharded round loop
# ---------------------------------------------------------------------------

def _rounds_body(
    centroids: Array,   # (1, k, d) local worker shard
    best_obj: Array,    # (1,)
    degenerate: Array,  # (1, k)
    keys: Array,        # (1, 2) this worker group's PRNG key
    alive: Array,       # (1,) liveness mask
    rounds_done: Array, # () global round counter (replicated)
    reservoir: Array,   # (1, m_shard, d) local slice of this worker's reservoir
    *,
    cfg: HPClustConfig,
    worker_axes: tuple[str, ...],
    inner_axis: str,
    pod_axis: str | None,
    sizes: dict,
):
    c = centroids[0]
    obj = best_obj[0]
    deg = degenerate[0]
    key = keys[0]
    live = alive[0]
    res = reservoir[0]
    m_shard = res.shape[0]
    s_loc = max(1, cfg.sample_size // sizes[inner_axis])

    iidx = jax.lax.axis_index(inner_axis)

    intra_axes: tuple[str, ...] = tuple(a for a in worker_axes if a != pod_axis)
    all_axes = worker_axes

    def coop_best(c, obj, deg, axes):
        # Poisoned incumbents (NaN/-inf) must never own the broadcast: mask
        # to +inf before the pmin/owner selection (mirrors strategies.py).
        # Dead worker groups (liveness mask) contribute +inf too, so a
        # quarantined group's stale incumbent can never warm-start anyone.
        obj = jnp.where(live & jnp.isfinite(obj), obj, jnp.inf)
        owner = _owner_mask(obj, axes, sizes, select_min=True)
        best_c, best_deg = _broadcast_from_owner((c, deg.astype(jnp.float32)), owner, axes)
        return best_c, jax.lax.pmin(obj, axes), best_deg > 0.5

    def round_fn(carry, r):
        c, obj, deg = carry
        # Quarantine (device-local, no collectives): a poisoned incumbent
        # resets to the virgin all-degenerate state so the next reseed
        # redraws every centroid row from the live sample.
        with jaxhooks.named_scope("round.quarantine"):
            bad = jnp.isnan(obj) | (obj == -jnp.inf) | ~jnp.all(jnp.isfinite(c))
            c = jnp.where(bad, jnp.zeros_like(c), c)
            obj = jnp.where(bad, jnp.inf, obj)
            deg = jnp.where(bad, jnp.ones_like(deg), deg)
        # Global round numbering: a resumed run folds in the same indices the
        # uninterrupted one would have, so replay is bit-for-bit.
        rkey = jax.random.fold_in(key, rounds_done + r)
        k_samp, k_seed = jax.random.split(rkey)

        # --- coordination: choose the warm start -------------------------
        with jaxhooks.named_scope("round.coop_select"):
            if cfg.strategy in ("inner", "sequential", "competitive"):
                base_c, base_deg = c, deg
            elif cfg.strategy == "cooperative":
                base_c, _, base_deg = coop_best(c, obj, deg, all_axes)
            elif cfg.strategy == "hybrid":
                bc, _, bd = coop_best(c, obj, deg, all_axes)
                coop = r >= cfg.effective_t1
                base_c = jnp.where(coop, bc, c)
                base_deg = jnp.where(coop, bd, deg)
            else:  # hybrid2: intra-pod every round, cross-pod every sync_every
                bc, _, bd = coop_best(c, obj, deg, intra_axes)
                coop = r >= cfg.effective_t1
                base_c = jnp.where(coop, bc, c)
                base_deg = jnp.where(coop, bd, deg)

        # --- sample: stratified over the inner axis ----------------------
        with jaxhooks.named_scope("round.sample"):
            k_samp_loc = jax.random.fold_in(k_samp, iidx)
            idx = jax.random.randint(k_samp_loc, (s_loc,), 0, m_shard)
            sample = res[idx]  # (s_loc, d)

        # --- reseed degenerate + Lloyd ------------------------------------
        with jaxhooks.named_scope("round.reseed"):
            seeded = _reseed_degenerate_sharded(
                k_seed, sample, base_c, base_deg, cfg, inner_axis, sizes
            )
        with jaxhooks.named_scope("round.lloyd"):
            new_c, new_obj, counts = _lloyd_sharded(
                sample, seeded, cfg, inner_axis)

        # --- keep the best -------------------------------------------------
        # Non-finite candidates never displace the incumbent (-inf would
        # otherwise win the compare and poison every later coop round).
        # Dead worker groups are frozen: their results are untrusted, so
        # they never accept — the incumbent they carried stays intact for
        # a later host-side revive/redistribution.
        accept = (new_obj < obj) & jnp.isfinite(new_obj) & live
        c2 = jnp.where(accept, new_c, c)
        o2 = jnp.where(accept, new_obj, obj)
        d2_ = jnp.where(accept, counts == 0, deg)

        # --- hybrid2 cross-pod sync (rare, DCI-budgeted) -------------------
        if cfg.strategy == "hybrid2" and pod_axis is not None:
            do = (r + 1) % cfg.sync_every == 0
            gc, go, gd = coop_best(c2, o2, d2_, all_axes)
            # Replace the per-pod *worst* incumbent with the global best
            # (non-finite incumbents count as worst, so they are replaced;
            # dead groups map to -inf so they never win worst — the global
            # best must not be parked on a quarantined device).
            o2_safe = jnp.where(jnp.isfinite(o2), o2, jnp.inf)
            o2_cand = jnp.where(live, o2_safe, -jnp.inf)
            worst = _owner_mask(o2_cand, intra_axes, sizes, select_min=False)
            better = go < o2_safe
            take = do & worst & better & live
            c2 = jnp.where(take, gc, c2)
            o2 = jnp.where(take, go, o2)
            d2_ = jnp.where(take, gd, d2_)

        return (c2, o2, d2_), o2

    (c, obj, deg), objs = jax.lax.scan(
        round_fn, (c, obj, deg), jnp.arange(cfg.rounds)
    )
    new_rounds_done = (rounds_done + cfg.rounds).astype(jnp.int32)
    return (c[None], obj[None], deg[None], keys, alive,
            new_rounds_done, objs[:, None])


def build_sharded_runner(
    mesh: Mesh,
    cfg: HPClustConfig,
    *,
    inner_axis: str = "model",
    pod_axis: str | None = None,
):
    """Returns (fn, in_shardings, out_shardings) for the mesh.

    fn(state, reservoir) -> (state', per-round objectives (rounds, W)).

    Worker axes are every mesh axis except the inner one; ``cfg.workers``
    must equal their product. Reservoir: (W, m_shard_total, d) sharded
    (workers, inner, -). PRNG keys ride in the state (one per worker
    group), so successive calls — and calls resumed from a checkpoint —
    continue one deterministic stream of rounds.
    """
    worker_axes = tuple(a for a in mesh.axis_names if a != inner_axis)
    n_workers = 1
    for a in worker_axes:
        n_workers *= mesh.shape[a]
    if cfg.workers != n_workers:
        raise ValueError(
            f"cfg.workers={cfg.workers} must equal prod(worker axes)={n_workers}"
        )
    if pod_axis is not None and pod_axis not in worker_axes:
        raise ValueError(f"pod_axis {pod_axis} not in {worker_axes}")

    wspec = P(worker_axes)
    specs = dict(
        centroids=P(worker_axes, None, None),
        best_obj=wspec,
        degenerate=P(worker_axes, None),
        key=P(worker_axes, None),
        alive=wspec,
        rounds_done=P(),
        reservoir=P(worker_axes, inner_axis, None),
    )
    state_specs = ShardedState(
        centroids=specs["centroids"],
        best_obj=specs["best_obj"],
        degenerate=specs["degenerate"],
        key=specs["key"],
        alive=specs["alive"],
        rounds_done=specs["rounds_done"],
    )

    body = functools.partial(
        _rounds_body,
        cfg=cfg,
        worker_axes=worker_axes,
        inner_axis=inner_axis,
        pod_axis=pod_axis,
        sizes=dict(mesh.shape),
    )
    mapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=tuple(state_specs) + (specs["reservoir"],),
        out_specs=tuple(state_specs) + (P(None, worker_axes),),
        check_vma=False,
    )

    def fn(state: ShardedState, reservoir: Array):
        rd = jnp.asarray(state.rounds_done, jnp.int32)
        c, o, d, k, a, r, objs = mapped(
            state.centroids, state.best_obj, state.degenerate,
            state.key, state.alive, rd, reservoir,
        )
        return ShardedState(c, o, d, k, a, r), objs

    state_shardings = ShardedState(
        *(NamedSharding(mesh, s) for s in state_specs)
    )
    in_shardings = (
        state_shardings,
        NamedSharding(mesh, specs["reservoir"]),
    )
    out_shardings = (
        state_shardings,
        NamedSharding(mesh, P(None, worker_axes)),
    )
    return fn, in_shardings, out_shardings


def init_sharded_state(
    cfg: HPClustConfig, d: int, *, seed: int = 0
) -> ShardedState:
    """Virgin state: all centroids degenerate, objectives +inf, all groups
    alive, one independent PRNG stream per worker group."""
    return ShardedState(
        centroids=jnp.zeros((cfg.workers, cfg.k, d), jnp.float32),
        best_obj=jnp.full((cfg.workers,), jnp.inf, jnp.float32),
        degenerate=jnp.ones((cfg.workers, cfg.k), jnp.bool_),
        key=jax.random.split(jax.random.PRNGKey(seed), cfg.workers),
        alive=jnp.ones((cfg.workers,), jnp.bool_),
        rounds_done=jnp.zeros((), jnp.int32),
    )


def state_shapes(cfg: HPClustConfig, d: int) -> ShardedState:
    """ShapeDtypeStructs matching ``init_sharded_state`` (for AOT lowering)."""
    w = cfg.workers
    return ShardedState(
        centroids=jax.ShapeDtypeStruct((w, cfg.k, d), jnp.float32),
        best_obj=jax.ShapeDtypeStruct((w,), jnp.float32),
        degenerate=jax.ShapeDtypeStruct((w, cfg.k), jnp.bool_),
        key=jax.ShapeDtypeStruct((w, 2), jnp.uint32),
        alive=jax.ShapeDtypeStruct((w,), jnp.bool_),
        rounds_done=jax.ShapeDtypeStruct((), jnp.int32),
    )


def mark_dead(state: ShardedState, groups) -> ShardedState:
    """Host-side quarantine: flip the liveness mask off for ``groups``.

    A dead group is frozen by the engine (never accepts, contributes +inf
    to every cooperative selection) until revived or redistributed away.
    """
    alive = np.array(jax.device_get(state.alive), copy=True)
    alive[list(groups)] = False
    return state._replace(alive=jnp.asarray(alive))


def revive(state: ShardedState, groups=None) -> ShardedState:
    """Undo ``mark_dead`` for ``groups`` (default: every group)."""
    alive = np.array(jax.device_get(state.alive), copy=True)
    alive[list(groups) if groups is not None else slice(None)] = True
    return state._replace(alive=jnp.asarray(alive))


def best_of(state: ShardedState) -> tuple[np.ndarray, float]:
    """Centroids/objective of the best *live* worker group (dead and
    non-finite incumbents are masked out of the argmin).

    Gathers to the host first, so it holds for a state on a mesh of any
    axis type (traced indexing of an ``Explicit``-sharded array is refused).
    """
    st = jax.device_get(state)
    best = np.asarray(st.best_obj, np.float32)
    obj = np.where(np.asarray(st.alive, bool) & np.isfinite(best), best,
                   np.inf)
    w = int(np.argmin(obj))
    return np.asarray(st.centroids[w]), float(obj[w])
