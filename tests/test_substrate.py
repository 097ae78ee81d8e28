"""Substrate: optimizers, checkpoint/restart/elastic, trainer fault
tolerance, compression, data pipeline, serving engine."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.data import blob_stream, gaussian_blobs, token_batches
from repro.distributed import compression as comp
from repro.launch import steps as S
from repro.models import model as M
from repro.optim import adafactor, adamw, clip_by_global_norm
from repro.runtime import StepFailure, Trainer, TrainerConfig
from repro.serving import Request, ServeEngine


# ---------------------------------------------------------------------------
# optimizers
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("opt_fn", [adamw, adafactor])
def test_optimizer_descends_quadratic(opt_fn):
    opt = opt_fn(0.1)
    params = {"w": jnp.asarray(np.random.default_rng(0).normal(size=(8, 8)),
                               jnp.float32)}
    state = opt.init(params)
    loss = lambda p: jnp.sum(p["w"] ** 2)
    l0 = float(loss(params))
    for _ in range(50):
        g = jax.grad(loss)(params)
        params, state = opt.update(g, state, params)
    assert float(loss(params)) < 0.5 * l0


def test_clip_by_global_norm():
    g = {"a": jnp.full((10,), 10.0), "b": jnp.full((10,), 10.0)}
    clipped, norm = clip_by_global_norm(g, 1.0)
    total = np.sqrt(sum(float(jnp.sum(v ** 2)) for v in clipped.values()))
    np.testing.assert_allclose(total, 1.0, rtol=1e-5)
    assert float(norm) == pytest.approx(np.sqrt(2000.0), rel=1e-5)


def test_adafactor_state_is_factored():
    opt = adafactor(0.01)
    params = {"w": jnp.zeros((64, 32)), "b": jnp.zeros((32,))}
    st = opt.init(params)
    assert st.slots["w"].row.shape == (64,)
    assert st.slots["w"].col.shape == (32,)
    assert st.slots["b"].full.shape == (32,)


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------


def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2)
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.int32)}}
    mgr.save(3, tree)
    step, out = mgr.restore(tree)
    assert step == 3
    np.testing.assert_array_equal(np.asarray(out["a"]), np.asarray(tree["a"]))
    np.testing.assert_array_equal(np.asarray(out["b"]["c"]),
                                  np.asarray(tree["b"]["c"]))


def test_checkpoint_retention_and_latest(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2)
    tree = {"a": jnp.zeros((2,))}
    for s in (1, 5, 9):
        mgr.save(s, tree)
    assert mgr.all_steps() == [5, 9]
    assert mgr.latest_step() == 9


def test_checkpoint_integrity_check(tmp_path):
    mgr = CheckpointManager(tmp_path)
    tree = {"a": jnp.zeros((128,))}
    mgr.save(0, tree)
    # corrupt the payload
    p = tmp_path / "step_0000000000" / "leaves.npz"
    raw = bytearray(p.read_bytes())
    raw[-1] ^= 0xFF
    p.write_bytes(bytes(raw))
    with pytest.raises(IOError):
        mgr.restore(tree)


def test_checkpoint_elastic_resharding(tmp_path):
    """Restore onto a (1,1) mesh sharding — the elastic-restart path: the
    checkpoint knows nothing about the writer's mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_host_mesh

    mgr = CheckpointManager(tmp_path)
    tree = {"w": jnp.arange(16, dtype=jnp.float32).reshape(4, 4)}
    mgr.save(0, tree)
    mesh = make_host_mesh((1, 1))
    sh = {"w": NamedSharding(mesh, P("data", None))}
    _, out = mgr.restore(tree, shardings=sh)
    np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(tree["w"]))
    assert out["w"].sharding == sh["w"]


def test_checkpoint_async_save(tmp_path):
    mgr = CheckpointManager(tmp_path, async_save=True)
    tree = {"a": jnp.ones((1000,))}
    mgr.save(7, tree, block=False)
    mgr.wait()
    assert mgr.latest_step() == 7


# ---------------------------------------------------------------------------
# trainer fault tolerance
# ---------------------------------------------------------------------------


def _tiny_trainer(tmp_path, failure_at=None, steps=12):
    cfg = get_config("qwen3-0.6b", smoke=True)
    step_fn = jax.jit(S.make_train_step(cfg, grad_accum=1))
    opt = step_fn.__wrapped__.optimizer

    def init_state():
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        return params, opt.init(params)

    data = token_batches(cfg.vocab_size, 2, 16, seed=0)
    return Trainer(
        TrainerConfig(total_steps=steps, ckpt_every=4,
                      ckpt_dir=str(tmp_path / "ckpt")),
        step_fn, init_state, data, failure_at=failure_at,
    )


def test_trainer_completes(tmp_path):
    t = _tiny_trainer(tmp_path, steps=6)
    res = t.run()
    assert res["status"] == "done"
    assert res["step"] == 6


def test_trainer_survives_injected_failures(tmp_path):
    """Crash at steps 6 and 10 -> restart from the step-4/8 checkpoints,
    replay the lost steps, finish."""
    t = _tiny_trainer(tmp_path, failure_at={6, 10}, steps=12)
    res = t.run()
    assert res["status"] == "done"
    assert res["restarts"] == 2
    # steps after the checkpoint but before the crash are re-run: step 5 is
    # logged twice (lost work replayed from the step-4 checkpoint)
    steps_logged = [m["step"] for m in t.metrics_log if "step" in m]
    assert steps_logged.count(5) >= 2
    assert sorted(set(steps_logged)) == list(range(12))


def test_trainer_gives_up_after_max_restarts(tmp_path):
    t = _tiny_trainer(tmp_path, failure_at={1, 2, 3, 4, 5}, steps=8)
    t.cfg.max_restarts = 2
    with pytest.raises(StepFailure):
        t.run()


# ---------------------------------------------------------------------------
# compression
# ---------------------------------------------------------------------------


def test_int8_roundtrip_error_bounded():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1000,)), jnp.float32)
    q, s = comp.quantize_int8(x)
    err = np.abs(np.asarray(comp.dequantize_int8(q, s) - x))
    assert err.max() <= float(s) * 0.5 + 1e-6


def test_error_feedback_unbiased_over_time():
    """EF: the *accumulated* compressed signal tracks the accumulated true
    signal (residual stays bounded)."""
    rng = np.random.default_rng(0)
    state = comp.ef_init((256,))
    total_true = np.zeros((256,))
    total_sent = np.zeros((256,))
    for i in range(60):
        g = jnp.asarray(rng.normal(size=(256,)), jnp.float32)
        sent, state = comp.compress_decompress(g, state)
        total_true += np.asarray(g)
        total_sent += np.asarray(sent)
    resid = np.abs(total_true - total_sent)
    # residual equals the carried error, which is bounded by one quant step
    assert resid.max() < 0.2


def test_compressed_psum_matches_psum():
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh((1, 1))
    x = jnp.asarray(np.random.default_rng(0).normal(size=(64,)), jnp.float32)

    def f(xs):
        out, _ = comp.compressed_psum(xs, "data", comp.ef_init(xs.shape))
        return out

    y = jax.shard_map(f, mesh=mesh, in_specs=P(), out_specs=P(),
                      check_vma=False)(x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x), rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# data + serving
# ---------------------------------------------------------------------------


def test_gaussian_blobs_shapes():
    x, c = gaussian_blobs(1000, n=10, k=10, noise_points=100, seed=0)
    assert x.shape == (1100, 10)
    assert c.shape == (10, 10)


def test_blob_stream_is_stationary():
    g1 = blob_stream(512, seed=3)
    g2 = blob_stream(512, seed=3)
    a, b = next(g1), next(g2)
    np.testing.assert_allclose(a, b)


def test_token_batches_bounds():
    it = token_batches(100, 4, 8, seed=0)
    b = next(it)
    assert b["tokens"].shape == (4, 8)
    assert b["tokens"].min() >= 0 and b["tokens"].max() < 100


def test_serving_engine_completes_requests():
    cfg = get_config("qwen3-0.6b", smoke=True)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    reqs = [
        Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, 8).astype(np.int32),
                max_tokens=4)
        for i in range(5)
    ]
    eng = ServeEngine(cfg, params, slots=2, max_len=64)
    eng.run(reqs, max_steps=100)
    assert all(r.done for r in reqs)
    assert all(len(r.out) == 4 for r in reqs)
