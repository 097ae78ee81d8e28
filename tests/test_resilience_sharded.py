"""Elastic sharded engine: checkpoint/resume, degraded-mesh recovery, and
collective-failure chaos (ISSUE 9 acceptance tests).

In-process tests cover the host-side pieces (rank rule, checkpointer,
injectors); everything that needs real collectives runs in a subprocess
with 8 forced CPU devices (XLA_FLAGS must precede the jax import).
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src")}

# ---------------------------------------------------------------------------
# in-process units
# ---------------------------------------------------------------------------


def _host_state(w=6, k=3, d=4, seed=0):
    import jax

    from repro.core.sharded import ShardedState

    rng = np.random.default_rng(seed)
    return ShardedState(
        centroids=rng.normal(size=(w, k, d)).astype(np.float32),
        best_obj=np.arange(w, dtype=np.float32),
        degenerate=np.zeros((w, k), np.bool_),
        key=np.asarray(jax.random.split(jax.random.PRNGKey(seed), w)),
        alive=np.ones((w,), np.bool_),
        rounds_done=np.int32(8),
    )


def test_redistribute_rank_rule_shrink():
    from repro.resilience.sharded_ckpt import redistribute_state

    st = _host_state(w=6)
    # Scrambled objectives; one NaN and one dead group must rank last.
    st = st._replace(
        best_obj=np.array([5.0, 1.0, 3.0, np.nan, 2.0, 4.0], np.float32),
        alive=np.array([1, 1, 1, 1, 0, 1], np.bool_),
    )
    hist = np.tile(st.best_obj, (2, 1)).astype(np.float32)
    st2, hist2 = redistribute_state(st, hist, 3)
    # Ranked best of the finite+alive incumbents: 1.0, 3.0, 4.0.
    assert np.array_equal(st2.best_obj, np.array([1.0, 3.0, 4.0], np.float32))
    # Whole rows (centroids, keys, liveness) follow their incumbent.
    assert np.array_equal(st2.centroids, st.centroids[[1, 2, 5]])
    assert np.array_equal(st2.key, st.key[[1, 2, 5]])
    assert st2.alive.all()
    # History columns follow too.
    assert np.array_equal(hist2, hist[:, [1, 2, 5]])
    assert int(st2.rounds_done) == 8


def test_redistribute_rank_rule_grow_forks_keys():
    from repro.resilience.sharded_ckpt import redistribute_state

    st = _host_state(w=4)
    hist = np.zeros((0, 4), np.float32)
    st2, hist2 = redistribute_state(st, hist, 6)
    # First 4 slots: the ranked originals; clones cycle the ranking.
    assert np.array_equal(st2.best_obj, np.array([0, 1, 2, 3, 0, 1],
                                                 np.float32))
    assert np.array_equal(st2.centroids[4], st.centroids[0])
    # Clones explore distinct PRNG streams: forked, not copied, keys.
    assert not np.array_equal(st2.key[4], st2.key[0])
    assert not np.array_equal(st2.key[5], st2.key[1])
    assert hist2.shape == (0, 6)


def test_redistribute_rejects_bad_worker_count():
    from repro.resilience.sharded_ckpt import redistribute_state

    with pytest.raises(ValueError):
        redistribute_state(_host_state(), np.zeros((0, 6), np.float32), 0)


def test_sharded_checkpointer_roundtrip(tmp_path):
    from repro.resilience.sharded_ckpt import ShardedStreamCheckpointer

    ck = ShardedStreamCheckpointer(tmp_path)
    assert ck.latest() is None
    assert ck.restore() is None
    st = _host_state(w=4)
    hist = np.arange(8, dtype=np.float32).reshape(2, 4)
    ck.save(2, st, hist)
    ck.save(3, st._replace(best_obj=st.best_obj + 1.0), hist)
    assert ck.latest() == 3
    snap = ck.restore(step=2)
    assert snap.windows_done == 2
    for got, want in zip(snap.state, st):
        assert np.array_equal(np.asarray(got), np.asarray(want))
    assert np.array_equal(snap.history, hist)


def test_drop_device_midstream_is_exact_and_one_shot():
    from repro.launch.elastic import DeviceLostError
    from repro.resilience.chaos import drop_device_midstream

    factory = drop_device_midstream(at_call=1, lost_devices=(6, 7))
    runner = factory(lambda x: x + 1)
    assert runner(1) == 2  # call 0 passes
    with pytest.raises(DeviceLostError) as ei:
        runner(1)  # call 1 fires
    assert ei.value.lost_devices == (6, 7)
    # One-shot: the retry (and a re-wrapped recompiled runner, which shares
    # the factory's global call counter) proceeds.
    runner2 = factory(lambda x: x + 10)
    assert runner2(1) == 11


def test_is_device_loss_triage():
    from repro.launch.elastic import DeviceLostError, is_device_loss

    assert is_device_loss(DeviceLostError("boom", (0,)))
    assert not is_device_loss(ValueError("bad shape"))

    XlaRuntimeError = type("XlaRuntimeError", (RuntimeError,), {})
    assert is_device_loss(XlaRuntimeError("NCCL communicator shut down"))
    assert is_device_loss(XlaRuntimeError("DEVICE_LOST: peer down"))
    assert not is_device_loss(XlaRuntimeError("INVALID_ARGUMENT: rank"))


def test_poison_worker_group_modes():
    from repro.resilience.chaos import poison_worker_group

    st = _host_state(w=4)
    p = poison_worker_group(st, [1], mode="neginf_obj")
    assert np.asarray(p.best_obj)[1] == -np.inf
    p = poison_worker_group(st, [0, 2], mode="nan_centroids")
    assert np.isnan(np.asarray(p.centroids)[[0, 2]]).all()
    assert np.isfinite(np.asarray(p.centroids)[1]).all()
    # Keys, liveness, and the round counter ride through untouched.
    assert np.array_equal(np.asarray(p.key), st.key)
    assert int(p.rounds_done) == int(st.rounds_done)
    with pytest.raises(ValueError):
        poison_worker_group(st, [0], mode="meteor")


def test_desync_pod_slices_pod_major():
    from repro.resilience.chaos import desync_pod

    st = _host_state(w=6)
    d = desync_pod(st, 2, pods=3, mode="stale")
    assert np.isinf(np.asarray(d.best_obj)[4:]).all()
    assert np.asarray(d.degenerate)[4:].all()
    assert np.array_equal(np.asarray(d.best_obj)[:4], st.best_obj[:4])
    with pytest.raises(ValueError):
        desync_pod(st, 0, pods=4)  # 6 % 4 != 0


# ---------------------------------------------------------------------------
# 8-device subprocess acceptance tests
# ---------------------------------------------------------------------------

PROLOGUE = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import io, json
import numpy as np
import jax


def windows(n, m=2000, d=6, k=4, seed=0):
    rng = np.random.default_rng(seed)
    centers = rng.uniform(-8, 8, size=(k, d))
    for _ in range(n):
        x = np.concatenate(
            [c + rng.normal(scale=0.5, size=(m // k, d)) for c in centers]
        ).astype(np.float32)
        rng.shuffle(x)
        yield x


KW = dict(k=4, sample_size=64, rounds_per_window=4, strategy="hybrid",
          seed=0, ckpt_every=1, kmeans_iters=8)
"""

DROP_SCRIPT = PROLOGUE + r"""
from repro import obs
from repro.launch.elastic import run_elastic_sharded
from repro.obs.cli import summarize
from repro.resilience.chaos import drop_device_midstream
from repro.resilience.sharded_ckpt import ShardedStreamCheckpointer

ckpt_dir, trace = sys.argv[1], sys.argv[2]
obs.configure(jsonl=trace)
res = run_elastic_sharded(
    windows(4), checkpoint_dir=ckpt_dir, mesh_shape=(4, 2),
    runner_wrapper=drop_device_midstream(at_call=2,
                                         lost_devices=(4, 5, 6, 7)),
    **KW,
)
obs.shutdown()
snap2 = ShardedStreamCheckpointer(ckpt_dir).restore(step=2)
buf = io.StringIO()
summarize(trace, out=buf)
print(json.dumps({
    "objective": res.objective,
    "best_at_2": float(np.min(np.asarray(snap2.state.best_obj))),
    "recoveries": res.recoveries,
    "workers": res.workers,
    "windows": res.windows_done,
    "monotone": bool((np.diff(res.history, axis=0) <= 1e-3).all()),
    "banner": "DEGRADED MESH" in buf.getvalue(),
}))
"""

RESUME_SCRIPT = PROLOGUE + r"""
from repro.launch.elastic import run_elastic_sharded
from repro.resilience.chaos import ChaosError, crash_stream

dir_a, dir_b = sys.argv[1], sys.argv[2]
resA = run_elastic_sharded(windows(4), checkpoint_dir=dir_a,
                           mesh_shape=(4, 2), **KW)
crashed = False
try:
    run_elastic_sharded(crash_stream(windows(4), at_window=2),
                        checkpoint_dir=dir_b, mesh_shape=(4, 2), **KW)
except ChaosError:
    crashed = True
resB = run_elastic_sharded(windows(4), checkpoint_dir=dir_b, resume=True,
                           mesh_shape=(4, 2), **KW)
print(json.dumps({
    "crashed": crashed,
    "resumed_at": resB.resumed_at,
    "state_equal": bool(
        np.array_equal(np.asarray(resA.state.centroids),
                       np.asarray(resB.state.centroids))
        and np.array_equal(np.asarray(resA.state.best_obj),
                           np.asarray(resB.state.best_obj))
        and np.array_equal(np.asarray(resA.state.key),
                           np.asarray(resB.state.key))
        and int(resA.state.rounds_done) == int(resB.state.rounds_done)
    ),
    "history_equal": bool(np.array_equal(resA.history, resB.history)),
}))
"""

SHRINK_SCRIPT = PROLOGUE + r"""
from repro.launch.elastic import run_elastic_sharded
from repro.resilience.sharded_ckpt import (
    ShardedStreamCheckpointer,
    redistribute_state,
)

ckpt_dir = sys.argv[1]
run_elastic_sharded(windows(2), checkpoint_dir=ckpt_dir,
                    mesh_shape=(8, 1), **KW)
snap = ShardedStreamCheckpointer(ckpt_dir).restore()
o8 = np.sort(np.asarray(snap.state.best_obj))
st2, hist2 = redistribute_state(snap.state, snap.history, 2)
res2 = run_elastic_sharded(windows(3), checkpoint_dir=ckpt_dir, resume=True,
                           mesh_shape=(2, 2), **KW)
print(json.dumps({
    "orig_workers": int(o8.shape[0]),
    "ranked": bool(np.array_equal(np.asarray(st2.best_obj), o8[:2])),
    "hist_cols": int(hist2.shape[1]),
    "workers": res2.workers,
    "resumed_at": res2.resumed_at,
    "no_regress": bool(res2.objective <= float(o8[0]) + 1e-4),
    "monotone": bool((np.diff(res2.history, axis=0) <= 1e-3).all()),
}))
"""

LIVENESS_SCRIPT = PROLOGUE + r"""
import jax.numpy as jnp
from repro.core import sharded
from repro.core.strategies import HPClustConfig
from repro.resilience.chaos import poison_worker_group
from repro.launch.mesh import make_host_mesh

mesh = make_host_mesh((4, 2))
cfg = HPClustConfig(k=4, sample_size=64, workers=4, rounds=4,
                    strategy="hybrid", fixed_schedule=True, kmeans_iters=8,
                    groups=2)
x = next(windows(1))
res = jnp.asarray(np.broadcast_to(x, (4,) + x.shape))
fn, in_sh, out_sh = sharded.build_sharded_runner(mesh, cfg)
jfn = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
st, _ = jfn(sharded.init_sharded_state(cfg, x.shape[1], seed=0), res)
st = poison_worker_group(st, [1], mode="neginf_obj")
st = sharded.mark_dead(st, [2])
frozen_c = np.asarray(st.centroids[2])
frozen_o = float(np.asarray(st.best_obj[2]))
st2, objs = jfn(st, res)
best_c, best_o = sharded.best_of(st2)
print(json.dumps({
    "frozen": bool(
        np.array_equal(np.asarray(st2.centroids[2]), frozen_c)
        and float(np.asarray(st2.best_obj[2])) == frozen_o
    ),
    "poison_recovered": bool(np.isfinite(float(np.asarray(st2.best_obj[1])))),
    "objs_finite": bool(np.isfinite(np.asarray(objs)).all()),
    "best_finite": bool(np.isfinite(float(best_o))),
}))
"""

DESYNC_SCRIPT = PROLOGUE + r"""
import jax.numpy as jnp
from repro.core import sharded
from repro.core.strategies import HPClustConfig
from repro.resilience.chaos import desync_pod
from repro.launch.mesh import make_host_mesh

mesh = make_host_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = HPClustConfig(k=4, sample_size=32, workers=4, rounds=6,
                    strategy="hybrid2", fixed_schedule=True, kmeans_iters=8,
                    groups=2, sync_every=2)
x = next(windows(1, m=1000))
res = jnp.asarray(np.broadcast_to(x, (4,) + x.shape))
fn, in_sh, out_sh = sharded.build_sharded_runner(mesh, cfg, pod_axis="pod")
jfn = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
st, _ = jfn(sharded.init_sharded_state(cfg, x.shape[1], seed=0), res)
pre_best = float(np.min(np.asarray(st.best_obj)))
st_d = desync_pod(st, 1, pods=2, mode="stale")
st2, _ = jfn(st_d, res)
post = np.asarray(st2.best_obj)
print(json.dumps({
    "desynced_inf": bool(np.isinf(np.asarray(st_d.best_obj)[2:]).all()),
    "recovered": bool(np.isfinite(post).all()),
    "no_regress": bool(float(np.min(post)) <= pre_best + 1e-4),
}))
"""


def _run(script, *argv):
    out = subprocess.run(
        [sys.executable, "-c", script, *map(str, argv)],
        capture_output=True, text=True, env=ENV, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_drop_device_recovers_on_degraded_mesh(tmp_path):
    """ISSUE 9 acceptance: device loss at window 2 -> rebuild (2,2) mesh
    from the 4 survivors, resume from the last checkpoint, and the final
    global best is <= the incumbent best at the drop point."""
    rec = _run(DROP_SCRIPT, tmp_path / "ckpt", tmp_path / "trace.jsonl")
    assert rec["recoveries"] == 1
    assert rec["workers"] == 2  # 4 surviving devices -> (2, 2) mesh
    assert rec["windows"] == 4  # no window is lost, only retried
    assert rec["objective"] <= rec["best_at_2"] + 1e-4
    assert rec["monotone"]
    assert rec["banner"]  # summarize prints the degraded-mesh banner


def test_same_mesh_crash_resume_is_bit_for_bit(tmp_path):
    rec = _run(RESUME_SCRIPT, tmp_path / "a", tmp_path / "b")
    assert rec["crashed"]
    assert rec["resumed_at"] == 2
    assert rec["state_equal"]
    assert rec["history_equal"]


def test_mesh_shrink_restore_keeps_ranked_best(tmp_path):
    rec = _run(SHRINK_SCRIPT, tmp_path / "ckpt")
    assert rec["orig_workers"] == 8
    assert rec["ranked"]
    assert rec["hist_cols"] == 2
    assert rec["workers"] == 2
    assert rec["resumed_at"] == 2
    assert rec["no_regress"]
    assert rec["monotone"]


def test_liveness_mask_freezes_dead_group(tmp_path):
    rec = _run(LIVENESS_SCRIPT)
    assert rec["frozen"]
    assert rec["poison_recovered"]
    assert rec["objs_finite"]
    assert rec["best_finite"]


def test_desync_pod_repaired_by_cross_pod_sync(tmp_path):
    rec = _run(DESYNC_SCRIPT)
    assert rec["desynced_inf"]
    assert rec["recovered"]
    assert rec["no_regress"]
