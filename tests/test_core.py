"""Core clustering invariants: Lloyd, K-means++, strategies, streams."""
import dataclasses

try:  # property tests degrade to fixed-seed parametrize without hypothesis
    import hypothesis
    import hypothesis.strategies as st
except ImportError:
    hypothesis = None

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import HPClust, HPClustConfig, best_of
from repro.core import kmeans as km
from repro.core import kmeanspp as kpp
from repro.core import strategies as strat
from repro.core.baselines import (
    forgy_kmeans,
    kmeanspp_kmeans,
    minibatch_kmeans,
    pbk_bdc,
)
from repro.core.hpclust import stream_from_generator
from repro.data import blob_stream
from repro.kernels import ref


# ---------------------------------------------------------------------------
# Lloyd
# ---------------------------------------------------------------------------


def test_lloyd_objective_monotone(blobs):
    x = jnp.asarray(blobs)
    c = x[:7]
    objs = []
    for _ in range(12):
        c, obj, _, _ = km.lloyd_iteration(x, c)
        objs.append(float(obj))
    assert all(a >= b - 1e-3 for a, b in zip(objs, objs[1:])), objs


def test_lloyd_centroid_is_mean(blobs):
    x = jnp.asarray(blobs[:500])
    c0 = x[:4]
    idx, _ = ref.assign_ref(x, c0)
    new_c, _, counts, _ = km.lloyd_iteration(x, c0)
    for j in range(4):
        mask = np.asarray(idx) == j
        if mask.any():
            np.testing.assert_allclose(
                np.asarray(new_c)[j], np.asarray(x)[mask].mean(0),
                rtol=1e-4, atol=1e-4,
            )


def test_kmeans_converges_and_flags_iterations(blobs):
    x = jnp.asarray(blobs)
    res = km.kmeans(x, x[:5], max_iters=300, tol=1e-4)
    assert int(res.iterations) > 1
    assert np.isfinite(float(res.objective))
    res2 = km.kmeans_fixed(x, x[:5], iters=32)
    np.testing.assert_allclose(
        float(res.objective), float(res2.objective), rtol=0.05
    )


def test_empty_cluster_keeps_old_centroid():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(50, 3)).astype(np.float32))
    far = jnp.asarray(np.full((1, 3), 1e3, np.float32))
    c = jnp.concatenate([x[:2], far])
    new_c, _, counts, degenerate = km.lloyd_iteration(x, c)
    assert bool(degenerate[2])
    np.testing.assert_allclose(np.asarray(new_c)[2], np.asarray(far)[0])


# ---------------------------------------------------------------------------
# K-means++
# ---------------------------------------------------------------------------


def test_kmeanspp_centers_are_data_points(blobs):
    x = jnp.asarray(blobs[:512])
    c = kpp.kmeanspp(jax.random.PRNGKey(0), x, 6)
    xs = np.asarray(x)
    for row in np.asarray(c):
        d = ((xs - row[None]) ** 2).sum(1).min()
        assert d < 1e-8


def test_reseed_only_touches_masked_rows(blobs):
    x = jnp.asarray(blobs[:256])
    c0 = jnp.asarray(np.random.default_rng(1).normal(size=(5, 8)).astype(np.float32))
    mask = jnp.asarray([False, True, False, False, True])
    c1 = kpp.reseed_degenerate(jax.random.PRNGKey(1), x, c0, mask)
    keep = ~np.asarray(mask)
    np.testing.assert_allclose(np.asarray(c1)[keep], np.asarray(c0)[keep])
    assert not np.allclose(np.asarray(c1)[~keep], np.asarray(c0)[~keep])


def test_kmeanspp_handles_duplicate_points():
    x = jnp.asarray(np.ones((32, 4), np.float32))
    c = kpp.kmeanspp(jax.random.PRNGKey(0), x, 3)
    assert np.isfinite(np.asarray(c)).all()


def _check_kmeanspp_spread(k, seed):
    """D^2 seeding potential should not be wildly worse than uniform's."""
    r = np.random.default_rng(seed)
    centers = r.uniform(-20, 20, (k, 4))
    x = np.concatenate([c + r.normal(scale=0.1, size=(50, 4)) for c in centers])
    xj = jnp.asarray(x.astype(np.float32))
    cpp = kpp.kmeanspp(jax.random.PRNGKey(seed), xj, k)
    uni = xj[r.integers(0, len(x), k)]
    pot_pp = float(ref.mssc_objective_ref(xj, cpp))
    pot_uni = float(ref.mssc_objective_ref(xj, uni))
    assert pot_pp <= pot_uni * 2.0 + 1e-3


if hypothesis is not None:

    @hypothesis.settings(deadline=None, max_examples=10)
    @hypothesis.given(k=st.integers(2, 8), seed=st.integers(0, 1000))
    def test_kmeanspp_spreads_better_than_uniform(k, seed):
        _check_kmeanspp_spread(k, seed)

else:

    @pytest.mark.parametrize("k,seed", [(2, 0), (4, 77), (8, 1000)])
    def test_kmeanspp_spreads_better_than_uniform(k, seed):
        _check_kmeanspp_spread(k, seed)


# ---------------------------------------------------------------------------
# strategies
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("strategy", ["competitive", "cooperative", "hybrid", "hybrid2"])
def test_incumbent_monotone_per_worker(blobs, strategy):
    """Keep-the-best: per-worker incumbent objective never increases (the
    paper's central monotonicity property)."""
    cfg = HPClustConfig(k=5, sample_size=256, workers=4, rounds=6,
                        strategy=strategy, groups=2)
    _, metrics = jax.jit(strat.run_hpclust, static_argnames=("cfg",))(
        jax.random.PRNGKey(0), jnp.asarray(blobs), cfg=cfg
    )
    hist = np.asarray(metrics.best_obj)  # (rounds, W)
    assert (np.diff(hist, axis=0) <= 1e-3).all()


def test_cooperative_propagates_best(blobs):
    cfg = HPClustConfig(k=5, sample_size=256, workers=4, rounds=8,
                        strategy="cooperative")
    state, metrics = jax.jit(strat.run_hpclust, static_argnames=("cfg",))(
        jax.random.PRNGKey(0), jnp.asarray(blobs), cfg=cfg
    )
    hist = np.asarray(metrics.best_obj)
    # After enough cooperative rounds workers should agree within noise.
    spread = hist[-1].max() / hist[-1].min()
    assert spread < 1.5, hist[-1]


def test_best_of_selects_argmin(blobs):
    cfg = HPClustConfig(k=5, sample_size=256, workers=4, rounds=4,
                        strategy="competitive")
    state, _ = jax.jit(strat.run_hpclust, static_argnames=("cfg",))(
        jax.random.PRNGKey(0), jnp.asarray(blobs), cfg=cfg
    )
    c, obj = best_of(state)
    assert float(obj) == pytest.approx(float(np.asarray(state.best_obj).min()))


def test_hpclust_beats_forgy_on_blobs(blobs):
    cfg = HPClustConfig(k=5, sample_size=512, workers=4, rounds=8,
                        strategy="hybrid")
    hp = HPClust(cfg, seed=0)
    res = hp.fit(blobs)
    full = hp.objective(blobs, res.centroids)
    fb = forgy_kmeans(blobs, 5, seed=0)
    assert full <= fb.objective * 1.05  # paper: HPClust >= Forgy quality


def test_fit_stream_carries_incumbents():
    cfg = HPClustConfig(k=4, sample_size=256, workers=2, rounds=3,
                        strategy="competitive")
    hp = HPClust(cfg, seed=0)
    stream = stream_from_generator(blob_stream(4096, n=6, k=4, seed=0), 3)
    res = hp.fit_stream(stream)
    hist = res.history  # (3*rounds, W)
    assert hist.shape[0] == 9
    assert (np.diff(hist, axis=0) <= 1e-3).all()  # monotone ACROSS windows


def test_assign_and_objective_batched(blobs):
    cfg = HPClustConfig(k=5, sample_size=128, workers=2, rounds=2)
    hp = HPClust(cfg, seed=0)
    res = hp.fit(blobs)
    y = hp.assign(blobs, res.centroids, batch=500)
    assert y.shape == (len(blobs),)
    assert y.max() < 5
    o1 = hp.objective(blobs, res.centroids, batch=500)
    o2 = hp.objective(blobs, res.centroids, batch=len(blobs))
    np.testing.assert_allclose(o1, o2, rtol=1e-5)


# ---------------------------------------------------------------------------
# baselines
# ---------------------------------------------------------------------------


def test_baselines_sane(blobs):
    f = forgy_kmeans(blobs, 5, seed=0)
    p = pbk_bdc(blobs, 5, segment_size=1000, seed=0)
    m = minibatch_kmeans(blobs, 5, steps=30, seed=0)
    for r in (f, p, m):
        assert np.isfinite(r.objective)
        assert r.centroids.shape == (5, 8)


def test_kmeanspp_kmeans_finds_the_blobs(blobs):
    """K-means++ seeds one centroid per tight blob, so Lloyd lands at the
    optimum: about m * d * sigma^2 = 6000 * 8 * 0.25."""
    r = kmeanspp_kmeans(blobs, 5, seed=0, impl="ref")
    assert r.centroids.shape == (5, 8)
    assert r.objective < 1.2 * len(blobs) * 8 * 0.25


def test_stream_from_generator_draws_no_window_past_the_cap():
    drawn = []

    def gen():
        while True:
            drawn.append(len(drawn))
            yield np.zeros((4, 2), np.float32)

    assert len(list(stream_from_generator(gen(), 3))) == 3
    assert drawn == [0, 1, 2]
