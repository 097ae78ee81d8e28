"""Elastic driver for the shard_map engine: checkpoint/resume + degraded mesh.

``run_elastic_sharded`` is the supervised window loop around the jitted SPMD
runner (the sharded twin of ``HPClust.fit_stream``):

  * every ``ckpt_every`` windows the full ``ShardedState`` (per-group PRNG
    keys, liveness mask, round counter) + round history is host-gathered and
    written through ``ShardedStreamCheckpointer``;
  * a device-loss failure around the runner (``DeviceLostError`` from the
    chaos harness, or a real ``XlaRuntimeError`` matched by message) triggers
    degraded-mesh recovery: the lost devices are excluded, the mesh is
    rebuilt over the survivors (``make_host_mesh(exclude=...)``), the runner
    recompiles, and the state restores from the last checkpoint —
    ``redistribute_state`` keeps the objective-ranked best incumbents when
    the surviving mesh carries fewer worker groups;
  * a crash anywhere else best-effort-saves the last good state before
    re-raising, so a same-mesh resume replays bit-for-bit (the state carries
    the PRNG keys and the global round counter).

Keep-the-best makes all of this safe: a checkpointed incumbent is a complete
restart point and any resumed run can only match-or-improve.
"""
from __future__ import annotations

import functools
from typing import Callable, Iterable, NamedTuple, Optional

import numpy as np

from repro import flags, obs
from repro.core.strategies import HPClustConfig
from repro.data import device_prefetch
from repro.launch.mesh import make_host_mesh
from repro.resilience.sharded_ckpt import (
    ShardedStreamCheckpointer,
    redistribute_state,
)


class DeviceLostError(RuntimeError):
    """A device dropped out mid-collective.

    Raised by the chaos injector ``drop_device_midstream``; real XLA
    failures surface as ``XlaRuntimeError`` and are matched by message in
    ``is_device_loss``. ``lost_devices`` names the dead ``Device.id``s so
    the recovery path can exclude exactly them from the rebuilt mesh.
    """

    def __init__(self, msg: str, lost_devices: Iterable[int] = ()):
        super().__init__(msg)
        self.lost_devices = tuple(lost_devices)


# Substrings (lowercased) that mark an XLA runtime failure as device loss
# rather than a programming error. Deliberately conservative: anything else
# propagates — retrying a genuine bug on a smaller mesh helps nobody.
_LOSS_MARKERS = (
    "device lost",
    "device_lost",
    "data_loss",
    "nccl",
    "socket closed",
    "connection reset",
    "peer down",
    "halted",
)


def is_device_loss(exc: BaseException) -> bool:
    """Does ``exc`` look like a device/interconnect loss (vs a real bug)?"""
    if isinstance(exc, DeviceLostError):
        return True
    if type(exc).__name__ in ("XlaRuntimeError", "JaxRuntimeError"):
        msg = str(exc).lower()
        return any(m in msg for m in _LOSS_MARKERS)
    return False


@functools.lru_cache(maxsize=None)
def _jit_sharded_runner(mesh, cfg, inner_axis="model", pod_axis=None,
                        donate=False):
    """One compiled SPMD runner per (mesh, cfg, donate) — shardings close
    over the mesh, so caching here keeps the compile cache shared across
    windows and across recoveries back onto a previously-seen mesh (JH003).
    ``donate`` is part of the cache key: the donating and non-donating
    programs are distinct executables, so a flag flip can never alias a
    stale entry.

    Returns ``(jitted_runner, reservoir_sharding)``; the sharding is what
    the device-prefetch thread uses to land windows directly in SPMD layout.
    """
    import jax

    from repro.core import sharded

    fn, in_sh, out_sh = sharded.build_sharded_runner(
        mesh, cfg, inner_axis=inner_axis, pod_axis=pod_axis
    )
    jitted = jax.jit(
        fn, in_shardings=in_sh, out_shardings=out_sh,
        donate_argnums=(0,) if donate else (),
    )
    return jitted, in_sh[1]


class ElasticResult(NamedTuple):
    centroids: np.ndarray        # (k, d) global best over live groups
    objective: float
    state: object                # final host-gathered ShardedState
    history: np.ndarray          # (rounds_total, W_final) f32
    windows_done: int
    workers: int                 # worker groups on the final mesh
    recoveries: int              # degraded-mesh rebuilds performed
    resumed_at: Optional[int]    # window index restored from, or None


def _worker_count(mesh, inner_axis: str) -> int:
    n = 1
    for a in mesh.axis_names:
        if a != inner_axis:
            n *= mesh.shape[a]
    return n


def run_elastic_sharded(
    stream: Iterable[np.ndarray],
    *,
    k: int,
    sample_size: int = 2048,
    rounds_per_window: int = 8,
    strategy: str = "hybrid",
    seed: int = 0,
    checkpoint_dir=None,
    resume: bool = False,
    ckpt_every: int = 1,
    mesh_shape=None,
    inner_axis: str = "model",
    pod_axis: str | None = None,
    max_recoveries: int = 2,
    kmeans_iters: int = 32,
    runner_wrapper: Optional[Callable] = None,
    prefetch: int | bool | None = None,
) -> ElasticResult:
    """Run the sharded engine over ``stream`` windows, elastically.

    ``runner_wrapper`` (chaos hook) wraps the jitted runner — it is
    re-applied after every recompile, so invocation-counted injectors like
    ``drop_device_midstream`` keep their global count across mesh rebuilds.

    ``prefetch`` (default: the ``REPRO_PREFETCH`` depth) double-buffers
    windows onto the mesh: the background thread broadcasts each window to
    the worker groups and ``jax.device_put``s it with the runner's reservoir
    ``NamedSharding`` while the previous window computes. A mesh rebuild
    bumps the placement epoch; windows placed for a dead mesh are re-placed
    from their host copy before the retry.
    """
    import jax

    from repro.core import sharded

    def make_cfg(workers: int) -> HPClustConfig:
        return HPClustConfig(
            k=k, sample_size=sample_size, workers=workers,
            rounds=rounds_per_window, strategy=strategy,
            groups=2 if strategy == "hybrid2" else 1,
            fixed_schedule=True, kmeans_iters=kmeans_iters,
        )

    def wrap(runner):
        return runner_wrapper(runner) if runner_wrapper is not None else runner

    def to_host(state):
        return jax.device_get(state)

    excluded: set[int] = set()
    donate = flags.donate_enabled()
    mesh = make_host_mesh(mesh_shape, exclude=())
    workers = _worker_count(mesh, inner_axis)
    cfg = make_cfg(workers)
    jitted, res_sharding = _jit_sharded_runner(
        mesh, cfg, inner_axis, pod_axis, donate)
    run_fn = wrap(jitted)

    # (epoch, workers, reservoir sharding) — ONE tuple so the prefetch
    # thread reads a consistent placement even while recover() swaps it.
    placement = (0, workers, res_sharding)

    def place(w: np.ndarray):
        e, wk, sh = placement
        return e, jax.device_put(np.broadcast_to(w, (wk,) + w.shape), sh)

    ckpt = (
        ShardedStreamCheckpointer(checkpoint_dir)
        if checkpoint_dir is not None else None
    )

    state = None
    history = np.zeros((0, workers), np.float32)
    windows_done = 0
    resumed_at: Optional[int] = None
    recoveries = 0

    def adopt(snap, *, event: str):
        """Install a checkpoint onto the *current* mesh, re-ranking only on a
        worker-count change (a same-shape resume must replay bit-for-bit)."""
        nonlocal state, history, windows_done, resumed_at
        st, hist = snap.state, snap.history
        if np.asarray(st.best_obj).shape[0] != workers:
            st, hist = redistribute_state(st, hist, workers)
        state = st
        history = np.asarray(hist, np.float32)
        windows_done = snap.windows_done
        resumed_at = snap.windows_done
        obs.event(event, windows_done=snap.windows_done, workers=workers)

    if ckpt is not None and resume:
        snap = ckpt.restore()
        if snap is not None:
            adopt(snap, event="sharded.resumed")

    def recover(exc: BaseException):
        nonlocal mesh, workers, cfg, run_fn, state, history, recoveries
        nonlocal placement
        lost = set(getattr(exc, "lost_devices", ()) or ())
        excluded.update(lost)
        mesh = make_host_mesh(None, exclude=excluded)
        workers_new = _worker_count(mesh, inner_axis)
        obs.event(
            "resilience.mesh_degraded",
            lost_devices=len(lost),
            excluded_total=len(excluded),
            mesh_shape=str(tuple(mesh.devices.shape)),
            workers=workers_new,
        )
        workers = workers_new
        cfg = make_cfg(workers)
        # A degraded mesh is rebuilt 2-axis; if the pod axis did not survive,
        # hybrid2 degrades gracefully to intra-mesh cooperation.
        pa = pod_axis if pod_axis in mesh.axis_names else None
        jitted, res_sh = _jit_sharded_runner(mesh, cfg, inner_axis, pa,
                                             donate)
        run_fn = wrap(jitted)
        # New epoch: windows the prefetch thread placed for the dead mesh
        # are re-placed from their host copy at retry time.
        placement = (placement[0] + 1, workers, res_sh)
        snap = ckpt.restore() if ckpt is not None else None
        if snap is not None:
            adopt(snap, event="sharded.resumed")
        elif state is not None:
            st, hist = redistribute_state(to_host(state), history, workers)
            state, history = st, np.asarray(hist, np.float32)
        recoveries += 1

    # Sanitize stays off (this tier trusts its feed, as before); the thread
    # still overlaps the f32 copy + broadcast + sharded H2D with compute.
    windows_it = device_prefetch.device_stream(
        stream,
        depth=flags.prefetch_depth(prefetch),
        sanitize=False,
        start_at=windows_done,
        place=place,
    )
    try:
        for item in windows_it:
            wi = item.index
            if state is None:
                state = sharded.init_sharded_state(
                    cfg, item.host.shape[1], seed=seed
                )
            while True:
                epoch, reservoir = item.device
                if epoch != placement[0]:
                    # Placed for a mesh that no longer exists: redo the H2D
                    # from the host copy with the surviving mesh's sharding.
                    _, reservoir = place(item.host)
                try:
                    with obs.span("sharded.window", window=wi,
                                  workers=workers):
                        # Donation deletes the input state's buffers even on
                        # a failed step — the host backup keeps the recovery
                        # and crash-save paths readable.
                        backup = to_host(state) if donate else None
                        try:
                            new_state, objs = run_fn(state, reservoir)
                            jax.block_until_ready(new_state)
                        except BaseException:
                            if backup is not None:
                                state = backup
                            raise
                except Exception as e:  # noqa: BLE001 - triaged below
                    if not is_device_loss(e) or recoveries >= max_recoveries:
                        raise
                    recover(e)
                    continue  # retry this window on the degraded mesh
                state = new_state
                history = np.concatenate(
                    [history, np.asarray(objs, np.float32)], axis=0
                )
                windows_done = wi + 1
                obs.inc("sharded.windows")
                if ckpt is not None and windows_done % ckpt_every == 0:
                    ckpt.save(windows_done, to_host(state), history)
                break
    except BaseException:
        # Crash-save the last good state so a resume loses at most the
        # in-flight window (mirrors fit_stream's crash path).
        if ckpt is not None and state is not None and windows_done > 0:
            try:
                ckpt.save(windows_done, to_host(state), history)
            except Exception:  # pragma: no cover - best effort
                pass
        raise
    finally:
        windows_it.close()  # deterministic prefetch-thread shutdown

    if state is None:
        raise ValueError("empty stream: nothing to cluster")

    st_h = to_host(state)
    centroids, objective = sharded.best_of(st_h)
    return ElasticResult(
        centroids=centroids,
        objective=objective,
        state=st_h,
        history=history,
        windows_done=windows_done,
        workers=workers,
        recoveries=recoveries,
        resumed_at=resumed_at,
    )
