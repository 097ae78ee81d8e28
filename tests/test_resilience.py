"""Chaos harness: fault injection against the resilience layer.

Each test injects a deterministic fault (repro.resilience.chaos) and asserts
the stack degrades the way docs/resilience.md promises: crashes resume
bit-for-bit, poisoned workers are quarantined instead of winning argmins,
corrupt windows are sanitized and counted, dying prefetch producers restart
with backoff, and checkpoint writers never corrupt the previous checkpoint.

Run separately from tier-1 (CI job: chaos):
    PYTHONPATH=src JAX_PLATFORMS=cpu pytest tests/test_resilience.py -q
"""
import itertools
import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager
from repro.core import HPClust, HPClustConfig
from repro.core import strategies
from repro.core.hpclust import stream_from_generator
from repro.data import PipelineError, blob_stream, prefetch_iter
from repro.resilience import (
    Deadline,
    PreemptionGuard,
    RetryError,
    RetryPolicy,
    backoff_delays,
    retry_call,
    sanitize_window,
)
from repro.resilience import chaos
from repro.resilience.chaos import ChaosError
from repro.resilience.sanitize import screen_plan


# ---------------------------------------------------------------------------
# retry / backoff
# ---------------------------------------------------------------------------


def test_backoff_deterministic_and_capped():
    pol = RetryPolicy(base_delay=0.05, max_delay=0.4, multiplier=2.0)
    a = list(itertools.islice(backoff_delays(pol, seed=7), 8))
    b = list(itertools.islice(backoff_delays(pol, seed=7), 8))
    assert a == b
    assert all(0.0 <= d <= 0.4 * (1 + pol.jitter) for d in a)


def test_retry_call_recovers():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise ValueError("boom")
        return "ok"

    out = retry_call(flaky, policy=RetryPolicy(max_attempts=5),
                     sleep=lambda s: None)
    assert out == "ok" and calls["n"] == 3


def test_retry_call_exhausts_with_cause():
    with pytest.raises(RetryError) as ei:
        retry_call(lambda: 1 / 0, policy=RetryPolicy(max_attempts=2),
                   sleep=lambda s: None)
    assert isinstance(ei.value.__cause__, ZeroDivisionError)


def test_deadline_fake_clock():
    t = [0.0]
    dl = Deadline(1.5, clock=lambda: t[0])
    assert not dl.expired and dl.remaining() == pytest.approx(1.5)
    t[0] = 2.0
    assert dl.expired and dl.remaining() == 0.0


# ---------------------------------------------------------------------------
# prefetch supervision
# ---------------------------------------------------------------------------


def test_prefetch_restarts_through_producer_deaths():
    def src():
        yield from range(10)

    factory = chaos.failing_source(src, fail_at=[3, 7])
    got = list(prefetch_iter(factory, size=2, max_restarts=3, poll_s=0.05,
                             sleep=lambda s: None))
    # Restarts re-run the factory from scratch (duplicates allowed); the
    # tail of the range must eventually arrive.
    assert got[-1] == 9
    assert set(got) == set(range(10))


def test_prefetch_raises_after_restart_budget():
    def dead():
        raise ChaosError("dead on arrival")
        yield  # pragma: no cover

    with pytest.raises(PipelineError) as ei:
        list(prefetch_iter(lambda: dead(), size=1, max_restarts=2,
                           poll_s=0.05, sleep=lambda s: None))
    assert isinstance(ei.value.__cause__, ChaosError)


def test_prefetch_finite_stream_completes_cleanly():
    def src():
        yield from range(5)

    assert list(prefetch_iter(src, size=2, poll_s=0.05)) == list(range(5))


# ---------------------------------------------------------------------------
# window sanitization
# ---------------------------------------------------------------------------


def test_sanitize_window_preserves_shape_and_counts():
    x = np.arange(20, dtype=np.float32).reshape(5, 4)
    x[1, 2] = np.nan
    x[3, 0] = np.inf
    out, n_bad = sanitize_window(x)
    assert n_bad == 2
    assert out.shape == x.shape and out.dtype == np.float32
    assert np.isfinite(out).all()
    # good rows untouched
    np.testing.assert_array_equal(out[0], x[0])


def test_sanitize_window_all_bad_and_bad_rank():
    out, n_bad = sanitize_window(np.full((4, 3), np.nan, np.float32))
    assert out is None and n_bad == 4
    with pytest.raises(ValueError):
        sanitize_window(np.zeros((4,), np.float32))


def _sanitize_oracle(x):
    """``sanitize_window`` as first written: one whole-window
    ``isfinite(x).all(axis=1)``, the reference for the blocked screen."""
    x = np.asarray(x)
    bad = ~np.isfinite(x).all(axis=1)
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


def _poison(x, rows, seed=0):
    """Put NaN, +Inf and -Inf, in turn, at a random column of each row."""
    rng = np.random.default_rng(seed)
    for i, r in enumerate(rows):
        x[r, rng.integers(x.shape[1])] = (np.nan, np.inf, -np.inf)[i % 3]
    return x


def _window(m, d, dtype=np.float32, seed=0):
    return np.random.default_rng(seed).normal(size=(m, d)).astype(dtype)


def _pooled(d=768, dtype=np.float32, seed=0):
    """A window of more than one row block, so the pool runs: three full
    row blocks and a short fourth, with bad values on the first and last row
    of each block and of the window, and in random rows."""
    rows = screen_plan((1, d)).rows
    m = 3 * rows + 5
    x = _window(m, d, dtype, seed)
    edges = [0, rows - 1, rows, 2 * rows - 1, 2 * rows, 3 * rows - 1,
             3 * rows, m - 1]
    rand = np.random.default_rng(seed + 1).choice(m, 40, replace=False)
    return _poison(x, sorted(set(edges) | set(rand.tolist())), seed)


_SANITIZE_CASES = {
    "small_nan_inf": lambda: _poison(_window(200, 7), [0, 3, 50, 51, 199]),
    "pooled_block_edges": _pooled,
    "pooled_clean": lambda: _window(3 * screen_plan((1, 768)).rows + 5, 768),
    "small_clean": lambda: _window(64, 5),
    "all_bad_small": lambda: np.full((4, 3), np.nan, np.float32),
    "pooled_all_bad": lambda: _poison(
        _window(4 * screen_plan((1, 512)).rows, 512),
        range(4 * screen_plan((1, 512)).rows)),
    "single_row_clean": lambda: _window(1, 768),
    "single_row_bad": lambda: _poison(_window(1, 768), [0]),
    "pooled_sliced": lambda: _pooled(d=770)[::2, 1:-1],
    "sliced_small": lambda: _poison(_window(40, 9), [1, 2, 39])[1::3, ::2],
    "pooled_float64": lambda: _pooled(dtype=np.float64),
}


@pytest.mark.parametrize("case", sorted(_SANITIZE_CASES))
def test_sanitize_window_bit_identical_to_whole_window_screen(case):
    x = _SANITIZE_CASES[case]()
    if case.startswith("pooled") and len(os.sched_getaffinity(0)) > 1:
        assert screen_plan(x.shape).threads > 1
    want, want_bad = _sanitize_oracle(x)
    out, n_bad = sanitize_window(x)
    assert n_bad == want_bad
    if want is None:
        assert out is None
        return
    if want_bad == 0:
        assert out is x  # no copy on the common path
        return
    assert out.dtype == want.dtype and out.shape == want.shape
    assert out.tobytes() == want.tobytes()


def test_sanitize_window_concurrent_callers_share_the_pool():
    windows = [_pooled(d=64, seed=s) for s in range(8)]
    want = [_sanitize_oracle(x) for x in windows]
    got = [None] * len(windows)

    def work(i):
        got[i] = sanitize_window(windows[i])

    prev = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(len(windows))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(prev)
    for (out, n_bad), (ref, ref_bad) in zip(got, want):
        assert n_bad == ref_bad > 0
        assert out.tobytes() == ref.tobytes()


def test_stream_sanitization_counts_and_keeps_centroids_finite():
    cfg = HPClustConfig(k=4, sample_size=256, workers=2, rounds=2)
    hp = HPClust(cfg, seed=0)
    at = {1: 0.25}
    win = 2048

    def stream():
        return stream_from_generator(blob_stream(win, n=5, k=4, seed=3), 3)

    res = hp.fit_stream(chaos.corrupt_stream(stream(), at=at, mode="nan"))
    assert res.stats.sanitized_rows == chaos.corrupted_rows(at, win)
    assert np.isfinite(res.centroids).all()
    assert np.isfinite(res.objective)
    # sanitization must not change shape-keyed jit cache entries: clean run
    # over the same source also succeeds and is at least as good as random
    clean = HPClust(cfg, seed=0).fit_stream(stream())
    assert np.isfinite(clean.objective)


# ---------------------------------------------------------------------------
# crash / preempt / resume (acceptance: resumed <= uninterrupted + 1e-5)
# ---------------------------------------------------------------------------

_STREAM_CFG = HPClustConfig(k=4, sample_size=256, workers=2, rounds=3)


def _stream(n_windows=4):
    return stream_from_generator(blob_stream(4096, n=5, k=4, seed=7),
                                 n_windows)


def test_crash_midstream_then_resume_matches_uninterrupted(tmp_path):
    res0 = HPClust(_STREAM_CFG, seed=0).fit_stream(_stream())

    with pytest.raises(ChaosError):
        HPClust(_STREAM_CFG, seed=0).fit_stream(
            chaos.crash_stream(_stream(), at_window=2),
            checkpoint_dir=str(tmp_path),
        )
    res1 = HPClust(_STREAM_CFG, seed=0).fit_stream(
        _stream(), checkpoint_dir=str(tmp_path), resume=True
    )
    assert res1.stats.resumed_at == 2
    assert res1.objective <= res0.objective + 1e-5
    # deterministic source + checkpointed PRNG keys => bit-for-bit replay
    np.testing.assert_allclose(res1.history, res0.history)
    np.testing.assert_allclose(res1.centroids, res0.centroids)


def test_preempt_checkpoints_and_resumes(tmp_path):
    guard = PreemptionGuard()
    r1 = HPClust(_STREAM_CFG, seed=0).fit_stream(
        chaos.preempt_stream(_stream(), at_window=2, guard=guard),
        checkpoint_dir=str(tmp_path), preemption_guard=guard,
    )
    assert r1.stats.preempted and r1.stats.windows == 2
    r2 = HPClust(_STREAM_CFG, seed=0).fit_stream(
        _stream(), checkpoint_dir=str(tmp_path), resume=True
    )
    full = HPClust(_STREAM_CFG, seed=0).fit_stream(_stream())
    assert r2.objective <= full.objective + 1e-5


def test_resume_requires_checkpoint_dir():
    with pytest.raises(ValueError):
        HPClust(_STREAM_CFG, seed=0).fit_stream(_stream(), resume=True)


def test_empty_stream_raises():
    with pytest.raises(ValueError):
        HPClust(_STREAM_CFG, seed=0).fit_stream(iter(()))


def test_crashing_checkpoint_manager_preserves_previous(tmp_path):
    m = chaos.CrashingCheckpointManager(tmp_path, crash_at_steps=[2])
    tree = {"a": np.ones(4, np.float32)}
    m.save(1, tree)
    with pytest.raises(ChaosError):
        m.save(2, {"a": np.zeros(4, np.float32)})
    step, restored = m.restore(tree)
    assert step == 1 and np.allclose(restored["a"], 1.0)
    m.save(2, tree)  # one-shot crash: retry succeeds
    assert m.latest_step() == 2


# ---------------------------------------------------------------------------
# poisoned-worker quarantine (acceptance: NaN worker never becomes the base)
# ---------------------------------------------------------------------------

_COOP_CFG = HPClustConfig(k=4, sample_size=256, workers=4, rounds=3,
                          strategy="cooperative")


def _fitted_state(cfg=_COOP_CFG, seed=1):
    data = jnp.asarray(next(blob_stream(4096, n=5, k=4, seed=seed)))
    state = strategies.init_state(jax.random.PRNGKey(0), cfg, 5)
    state, _ = strategies.run_rounds(state, data, cfg)
    return state, data


@pytest.mark.parametrize("mode", ["nan_obj", "neginf_obj"])
def test_poisoned_worker_never_selected_as_base(mode):
    state, _ = _fitted_state()
    healthy_best = int(jnp.argmin(state.best_obj))
    poisoned = (healthy_best + 1) % _COOP_CFG.workers
    ps = chaos.poison_state(state, [poisoned], mode=mode)

    base_c, _ = strategies._select_base(ps, jnp.bool_(True), _COOP_CFG)
    # every worker warm-starts from the healthy best, not the poisoned one
    np.testing.assert_allclose(
        np.asarray(base_c), np.asarray(state.centroids[healthy_best])[None]
        .repeat(_COOP_CFG.workers, axis=0)
    )
    c, obj = strategies.best_of(ps)
    assert np.isfinite(float(obj))
    np.testing.assert_allclose(np.asarray(c),
                               np.asarray(state.centroids[healthy_best]))


@pytest.mark.parametrize("mode", ["nan_obj", "neginf_obj", "nan_centroids"])
def test_quarantine_flags_and_recovers(mode):
    state, data = _fitted_state()
    ps = chaos.poison_state(state, [0], mode=mode)
    st2, m2 = strategies.run_rounds(ps, data, _COOP_CFG)
    q0 = np.asarray(m2.quarantined[0])
    assert q0[0] and not q0[1:].any()
    assert np.isfinite(np.asarray(st2.best_obj)).all()
    assert np.isfinite(np.asarray(m2.best_obj)).all()
    assert np.isfinite(np.asarray(st2.centroids)).all()


def test_quarantine_all_workers_poisoned_recovers():
    state, data = _fitted_state()
    ps = chaos.poison_state(state, range(_COOP_CFG.workers),
                            mode="nan_centroids")
    st2, m2 = strategies.run_rounds(ps, data, _COOP_CFG)
    assert np.asarray(m2.quarantined[0]).all()
    assert np.isfinite(np.asarray(st2.best_obj)).all()


def test_quarantine_is_noop_on_healthy_state():
    state, _ = _fitted_state()
    st2, bad = strategies.quarantine_nonfinite(state)
    assert not np.asarray(bad).any()
    np.testing.assert_array_equal(np.asarray(st2.centroids),
                                  np.asarray(state.centroids))


# ---------------------------------------------------------------------------
# trainer + checkpoint satellites
# ---------------------------------------------------------------------------


def _toy_trainer(tmp_path, **cfg_kw):
    from repro.runtime import Trainer, TrainerConfig

    def step_fn(p, o, b):
        return p + 1, o, {"loss": float(p)}

    def init_state():
        return np.float32(0.0), np.float32(0.0)

    def data():
        while True:
            yield {}

    cfg = TrainerConfig(total_steps=5, ckpt_dir=str(tmp_path), **cfg_kw)
    return Trainer(cfg, step_fn, init_state, data())


def test_trainer_step0_preemption_writes_no_negative_checkpoint(tmp_path):
    tr = _toy_trainer(tmp_path)
    tr.preempt()
    out = tr.run()
    assert out["status"] == "preempted" and out["step"] == 0
    assert not [p.name for p in tmp_path.iterdir() if "-" in p.name]
    assert CheckpointManager(tmp_path).all_steps() == []


def test_trainer_midrun_preemption_still_checkpoints(tmp_path):
    tr = _toy_trainer(tmp_path, ckpt_every=100)
    orig = tr.step_fn

    def step_then_preempt(p, o, b):
        if float(p) >= 2:
            tr.preempt()
        return orig(p, o, b)

    tr.step_fn = step_then_preempt
    out = tr.run()
    assert out["status"] == "preempted" and out["step"] == 3
    assert CheckpointManager(tmp_path).latest_step() == 2


def test_blocking_save_joins_inflight_async_writer(tmp_path):
    m = CheckpointManager(tmp_path, keep=2, async_save=True)
    tree = {"a": np.arange(8, dtype=np.float32)}
    for s in range(5):
        m.save(s, tree, block=False)
    m.save(5, tree)  # must join the in-flight writer, never race it
    m.wait()
    assert m.latest_step() == 5
    step, restored = m.restore(tree)
    assert step == 5 and np.allclose(restored["a"], tree["a"])


# ---------------------------------------------------------------------------
# serving engine satellites
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def engine_parts():
    from repro.configs import get_config
    from repro.models import model as M

    cfg = get_config("qwen3-0.6b", smoke=True)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _mk_engine(engine_parts, **kw):
    from repro.serving.engine import ServeEngine

    cfg, params = engine_parts
    return ServeEngine(cfg, params, slots=2, max_len=64, **kw)


def _req(rid, **kw):
    from repro.serving.engine import Request

    return Request(rid=rid, prompt=np.arange(1, 5, dtype=np.int32),
                   max_tokens=3, **kw)


def test_engine_run_returns_completed_requests(engine_parts):
    eng = _mk_engine(engine_parts)
    reqs = [_req(i) for i in range(3)]
    done = eng.run(reqs)
    assert sorted(r.rid for r in done) == [0, 1, 2]
    assert all(r.done and not r.timed_out for r in done)
    assert all(len(r.out) == 3 for r in done)


def test_engine_bounded_admission(engine_parts):
    from repro.serving.engine import AdmissionError

    eng = _mk_engine(engine_parts, max_queue=1)
    eng.submit(_req(0))
    with pytest.raises(AdmissionError):
        eng.submit(_req(1))


def test_engine_deadline_marks_timed_out(engine_parts):
    t = [0.0]
    eng = _mk_engine(engine_parts, clock=lambda: t[0])
    late = _req(0, deadline_s=0.5)
    eng.submit(late)
    t[0] = 1.0  # deadline passes while queued
    done = eng.run([_req(1)])
    by_rid = {r.rid: r for r in done}
    assert by_rid[0].timed_out and by_rid[0].done
    assert not by_rid[1].timed_out and len(by_rid[1].out) == 3
