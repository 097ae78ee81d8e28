"""Public HPClust API: fit arrays, fit infinite streams, assign big data.

``HPClust`` is the user-facing estimator; ``fit_stream`` implements the
MSSC-ITD semantics the paper introduces: the algorithm never assumes X fits
anywhere — it consumes a window iterator (the "infinitely tall" stream),
keeps a device-resident reservoir window, and carries worker incumbents
across windows. More rounds / more windows can only improve the incumbent
(keep-the-best), which is the paper's central monotonicity property.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Iterable, Iterator, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import flags, obs
from repro.core import strategies
from repro.core.strategies import HPClustConfig, RoundMetrics, WorkerState
from repro.data import device_prefetch
from repro.kernels import ops
from repro.resilience.preemption import PreemptionGuard
from repro.resilience.stream_ckpt import StreamCheckpointer

Array = jax.Array


def _emit_round_metrics(metrics: RoundMetrics, *, window: int | None = None) -> None:
    """Publish per-round competition telemetry (objective descent, accepted
    rounds, Lloyd iterations, redrawn degenerate rows, quarantines) as
    ``hpclust.round`` trace events and counters. ``metrics`` holds host
    values already fetched from the device: this never waits on it. No-op
    when tracing is disabled."""
    rec = obs.get_recorder()
    if rec is None:
        return
    best = np.asarray(metrics.best_obj)        # (rounds, W)
    accepted = np.asarray(metrics.accepted)
    iters = np.asarray(metrics.kmeans_iters)
    quarantined = np.asarray(metrics.quarantined)
    reseed = np.asarray(metrics.reseed_rows)
    w = best.shape[1] if best.ndim == 2 else 1
    for r in range(best.shape[0]):
        rec.event(
            "hpclust.round",
            round=r,
            window=window,
            best_obj=float(best[r].min()),
            accepted=f"{int(accepted[r].sum())}/{w}",
            lloyd_iters=iters[r].tolist(),
            reseed_rows=reseed[r].tolist(),
            quarantined=int(quarantined[r].sum()),
        )
    rec.inc("hpclust.rounds", int(best.shape[0]))
    rec.inc("hpclust.lloyd_iters", int(iters.sum()))
    rec.inc("hpclust.reseed_rounds", int((reseed.sum(axis=-1) > 0).sum()))
    rec.inc("hpclust.reseed_rows", int(reseed.sum()))
    n_quar = int(quarantined.sum())
    if n_quar:
        rec.inc("resilience.quarantined_workers", n_quar)
        rec.event("resilience.quarantine", window=window, workers=n_quar)


class StreamStats(NamedTuple):
    """Supervision counters for one ``fit_stream`` run."""

    windows: int                # windows consumed (incl. skipped/resumed)
    sanitized_rows: int         # non-finite rows masked/dropped, cumulative
    preempted: bool             # stopped early at a preemption signal
    resumed_at: int | None      # window index restored from checkpoint


class HPClustResult(NamedTuple):
    centroids: np.ndarray       # (k, d)
    objective: float            # best incumbent sample objective
    history: np.ndarray         # (rounds_total, W) incumbent objective per round
    state: WorkerState          # final worker states (for warm restarts)
    stats: StreamStats | None = None  # stream supervision counters (fit_stream)


@dataclasses.dataclass
class HPClust:
    """Estimator wrapper around the compiled strategy engine.

    ``prefetch`` controls the device-prefetch depth for ``fit_stream``:
    ``None``/``True`` -> the ``REPRO_PREFETCH`` default (2), ``False``/``0``
    -> fully synchronous, an int -> that queue depth. Results are
    bit-identical either way (docs/performance.md).
    """

    config: HPClustConfig
    seed: int = 0
    prefetch: int | bool | None = None

    def fit(self, x: np.ndarray | Array) -> HPClustResult:
        """Cluster a (m, d) window (single-shot MSSC)."""
        key = jax.random.PRNGKey(self.seed)
        data = jnp.asarray(x, jnp.float32)
        with obs.span("hpclust.fit", rows=int(data.shape[0]),
                      strategy=self.config.strategy, k=self.config.k,
                      workers=self.config.workers):
            state, metrics = _jit_run_hpclust(key, data, cfg=self.config)
            if obs.enabled():
                _emit_round_metrics(jax.device_get(metrics))
        c, obj = strategies.best_of(state)
        return HPClustResult(
            centroids=np.asarray(c),
            objective=float(obj),
            history=np.asarray(metrics.best_obj),
            state=state,
        )

    def fit_stream(
        self,
        windows: Iterable[np.ndarray],
        *,
        rounds_per_window: int | None = None,
        checkpoint_dir: str | None = None,
        checkpoint_every: int = 1,
        resume: bool = False,
        sanitize: bool = True,
        preemption_guard: PreemptionGuard | None = None,
    ) -> HPClustResult:
        """MSSC-ITD: consume successive stream windows, carrying incumbents.

        Each window is a (m_w, d) array (m_w may vary; it is the reservoir
        the host has streamed in). Worker incumbents, objectives and PRNG
        state persist across windows — the algorithm behaves as if it sampled
        one infinite dataset.

        Supervision (all optional, see docs/resilience.md):
          * ``checkpoint_dir`` — save a ``WorkerState`` checkpoint every
            ``checkpoint_every`` windows (atomic; window index = step). A
            crash mid-stream also checkpoints the last good state before the
            exception propagates.
          * ``resume`` — restore the latest checkpoint and fast-forward the
            stream past the windows it already covers. With a deterministic
            source the resumed run replays the uninterrupted one exactly;
            by keep-the-best monotonicity it can only match-or-improve.
          * ``sanitize`` — mask non-finite rows host-side (counted in
            ``result.stats.sanitized_rows``); an all-bad window is skipped.
          * preemption — SIGTERM (or ``preemption_guard.trigger()``) stops at
            the next window boundary after checkpointing; the result carries
            ``stats.preempted=True``.
        """
        cfg = self.config
        rpw = rounds_per_window or cfg.rounds
        run_cfg = dataclasses.replace(cfg, rounds=rpw)
        key = jax.random.PRNGKey(self.seed)
        state: WorkerState | None = None
        hist: list[np.ndarray] = []
        sanitized_rows = 0
        windows_done = 0
        resumed_at: int | None = None
        preempted = False

        ckpt = None
        if checkpoint_dir is not None:
            ckpt = StreamCheckpointer(checkpoint_dir)
        if resume:
            if ckpt is None:
                raise ValueError("resume=True requires checkpoint_dir")
            restored = ckpt.restore(run_cfg)
            if restored is not None:
                windows_done = restored.windows_done
                state = restored.state
                sanitized_rows = restored.sanitized_rows
                resumed_at = windows_done
                if restored.history.size:
                    hist.append(restored.history)
                obs.event("resilience.resumed", window=windows_done)

        def _history() -> np.ndarray:
            if not hist:
                return np.zeros((0, run_cfg.workers), np.float32)
            return np.concatenate(hist, axis=0)

        own_guard = preemption_guard is None
        guard = PreemptionGuard() if own_guard else preemption_guard
        if own_guard:
            guard.install()
        donate = flags.donate_enabled()
        run_fn = _jit_run_from_state_donated if donate else _jit_run_from_state
        # Sanitize + H2D run on a background thread while the previous window
        # computes (depth 0 = the synchronous path, bit-identical).
        stream = device_prefetch.device_stream(
            windows,
            depth=flags.prefetch_depth(self.prefetch),
            sanitize=sanitize,
            start_at=windows_done,
            # Preemption is sampled in PULL order and delivered per item, so
            # the stop window is the same whether the producer ran ahead
            # (prefetch on) or not (see device_prefetch.device_stream).
            flag_fn=lambda: guard.preempted,
        )
        try:
            for item in stream:
                wi = item.index
                if item.flagged:
                    preempted = True
                    break
                with obs.span("stream.window", window=wi) as w_span:
                    sanitized_rows += item.n_bad
                    if item.n_bad:
                        obs.inc("stream.sanitized_rows", item.n_bad)
                    if item.host is None:  # every row non-finite: skip
                        windows_done = wi + 1
                        obs.event("stream.window_skipped", window=wi)
                        continue
                    data = item.device
                    w_span.set(rows=int(data.shape[0]))
                    if state is None:
                        key, k0 = jax.random.split(key)
                        state = strategies.init_state(
                            k0, run_cfg, data.shape[1])
                    # Donation deletes the input state's buffers even when
                    # the step fails — keep a host snapshot so the crash
                    # checkpoint below can never read a donated buffer.
                    snapshot = None
                    if donate and ckpt is not None:
                        snapshot = jax.device_get(state)
                    # Dispatch only: the program runs on while the host
                    # goes on to the one per-window fetch below.
                    with obs.span("hpclust.rounds", rounds=run_cfg.rounds):
                        try:
                            state, metrics = run_fn(state, data, cfg=run_cfg)
                        except BaseException:
                            if snapshot is not None:
                                state = snapshot
                            raise
                    with obs.span("stream.sync"):
                        if obs.enabled():
                            metrics = jax.device_get(metrics)
                        hist.append(np.asarray(metrics.best_obj))
                    _emit_round_metrics(metrics, window=wi)
                    windows_done = wi + 1
                    obs.inc("stream.windows")
                    obs.inc("stream.rows", int(data.shape[0]))
                    if ckpt is not None \
                            and windows_done % checkpoint_every == 0:
                        with obs.span("ckpt.save", window=windows_done):
                            ckpt.save(windows_done, state, _history(),
                                      sanitized_rows)
        except BaseException:
            # A dying stream (or step) must not lose the incumbents: persist
            # the last good state, then let the original failure propagate.
            if ckpt is not None and state is not None and windows_done > 0:
                try:
                    ckpt.save(windows_done, state, _history(), sanitized_rows)
                except Exception:
                    pass  # never mask the original failure with a save error
            raise
        finally:
            stream.close()  # deterministic prefetch-thread shutdown
            if own_guard:
                guard.restore()

        # A signal that landed during the final window's compute (stream
        # already exhausted) still counts as a preemption.
        preempted = preempted or guard.preempted
        if preempted:
            obs.event("resilience.preempted", window=windows_done)
        if preempted and ckpt is not None and state is not None \
                and windows_done > 0:
            ckpt.save(windows_done, state, _history(), sanitized_rows)
        if state is None:
            raise ValueError("empty stream")
        c, obj = strategies.best_of(state)
        return HPClustResult(
            centroids=np.asarray(c),
            objective=float(obj),
            history=_history(),
            state=state,
            stats=StreamStats(
                windows=windows_done,
                sanitized_rows=sanitized_rows,
                preempted=preempted,
                resumed_at=resumed_at,
            ),
        )

    def assign(
        self, x: np.ndarray | Array, centroids: np.ndarray | Array,
        *, batch: int = 1 << 16,
    ) -> np.ndarray:
        """Final full-dataset assignment (paper SS3 last step), batched."""
        # ops.assign_clusters dispatches through one module-level jit, so
        # every estimator instance shares a single compile cache.
        c = jnp.asarray(centroids, jnp.float32)
        out = []
        x = np.asarray(x, np.float32)
        with obs.span("hpclust.assign", rows=len(x), batch=batch):
            for i in range(0, len(x), batch):
                idx, _ = ops.assign_clusters(
                    jnp.asarray(x[i : i + batch]), c, impl=self.config.impl
                )
                out.append(np.asarray(idx))
        return np.concatenate(out) if out else np.zeros((0,), np.int32)

    def objective(self, x, centroids, *, batch: int = 1 << 16) -> float:
        """f(C, X) over a full dataset, streamed in batches.

        The ragged tail batch is padded back up to the fixed ``batch`` shape
        so ONE compiled program serves the whole pass (a (m % batch, d) tail
        used to retrace). Pad rows are copies of centroid 0 — distance 0 to
        their nearest centroid — and any numerical residue is measured with
        a fixed (1, d) probe and subtracted, so the value is unchanged.
        """
        c = jnp.asarray(centroids, jnp.float32)
        c0 = np.asarray(c)[0]
        x = np.asarray(x, np.float32)
        impl = self.config.impl
        total = 0.0
        with obs.span("hpclust.objective", rows=len(x), batch=batch):
            for i in range(0, len(x), batch):
                sl = x[i : i + batch]
                n_pad = batch - len(sl) if len(x) > batch else 0
                if n_pad:
                    sl = np.concatenate(
                        [sl, np.broadcast_to(c0, (n_pad, c0.shape[0]))])
                total += float(
                    ops.mssc_objective(jnp.asarray(sl), c, impl=impl))
                if n_pad:
                    total -= n_pad * float(
                        ops.mssc_objective(jnp.asarray(c0[None]), c,
                                           impl=impl))
        return total


def _run_from_state(state: WorkerState, data: Array, *, cfg: HPClustConfig):
    """run_rounds, jit-friendly keyword-static wrapper."""
    return strategies.run_rounds(state, data, cfg)


# Jitted once at import: a fresh jax.jit wrapper per fit()/fit_stream() call
# would key the compile cache on the wrapper identity and re-trace for every
# estimator instance (analysis check JH003). The donated variant reuses the
# input WorkerState's buffers for the output carry (REPRO_DONATE, default
# on); it is a SEPARATE jit object so flipping the flag mid-process can
# never alias a stale compile-cache entry.
_jit_run_hpclust = jax.jit(strategies.run_hpclust, static_argnames=("cfg",))
_jit_run_from_state = jax.jit(_run_from_state, static_argnames=("cfg",))
_jit_run_from_state_donated = jax.jit(
    _run_from_state, static_argnames=("cfg",), donate_argnums=(0,))


def stream_from_generator(
    gen: Iterator[np.ndarray], max_windows: int
) -> Iterable[np.ndarray]:
    """Utility: cap an infinite generator at max_windows windows (without
    drawing a window past the cap)."""
    return itertools.islice(gen, max_windows)
