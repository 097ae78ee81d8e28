"""The round program runs the K-means++ reseed only in a round where some
worker's warm start has a degenerate row, and gives the same bits as the
round that ran it unconditionally inside the ``vmap`` over workers."""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hpclust
from repro.core import kmeans as km
from repro.core import kmeanspp
from repro.core import strategies as strat

K, S, W, D, M = 8, 512, 4, 16, 4096
CFG = strat.HPClustConfig(k=K, sample_size=S, workers=W, rounds=3,
                          t1_rounds=1, impl="ref")


def _data():
    rng = np.random.default_rng(7)
    centres = rng.uniform(-20, 20, (K, D))
    x = centres[rng.integers(0, K, M)] + rng.normal(size=(M, D))
    return jnp.asarray(x.astype(np.float32))


def _unconditional_rounds(state, data, cfg):
    """The round as it was before the skip: every worker splits its key and
    runs ``reseed_degenerate`` inside the vmapped worker round, whatever its
    mask holds."""
    m = data.shape[0]

    def worker(c, o, dg, key, bc, bd, sample):
        key, k_seed = jax.random.split(key)
        seeded = kmeanspp.reseed_degenerate(
            k_seed, sample, bc, bd, n_candidates=cfg.n_candidates)
        res = km.kmeans(sample, seeded, max_iters=cfg.kmeans_iters,
                        tol=cfg.kmeans_tol, impl=cfg.impl)
        accept = (res.objective < o) & jnp.isfinite(res.objective)
        return (jnp.where(accept, res.centroids, c),
                jnp.where(accept, res.objective, o),
                jnp.where(accept, res.counts == 0, dg),
                key, accept, res.iterations)

    def round_fn(state, r):
        state, quarantined = strat.quarantine_nonfinite(state)
        base_c, base_deg = strat._select_base(
            state, strat._coop_flag(r, cfg), cfg)
        keys = jax.vmap(jax.random.split)(state.key)
        idx = jax.vmap(
            lambda kk: jax.random.randint(kk, (cfg.sample_size,), 0, m)
        )(keys[:, 0])
        c, o, dg, key, accepted, iters = jax.vmap(worker)(
            state.centroids, state.best_obj, state.degenerate, keys[:, 1],
            base_c, base_deg, data[idx])
        new = strat.WorkerState(c, o, dg, key)
        return new, (o, accepted, iters, quarantined)

    return jax.lax.scan(round_fn, state, jnp.arange(cfg.rounds))


def _steady_state(data):
    """A state whose incumbents have no degenerate row, after a few rounds."""
    state = strat.init_state(jax.random.PRNGKey(0), CFG, D)
    state, _ = hpclust._jit_run_from_state(state, data, cfg=CFG)
    assert not np.asarray(state.degenerate).any()
    return state


def _case(name, data):
    if name == "fresh":
        return strat.init_state(jax.random.PRNGKey(1), CFG, D)
    state = _steady_state(data)
    if name == "one_degenerate":
        state = state._replace(degenerate=state.degenerate.at[1, 3].set(True))
    return state


_WANT_ROWS = {"fresh": [K] * W, "steady": [0] * W,
              "one_degenerate": [0, 1, 0, 0]}


@pytest.mark.parametrize("name", sorted(_WANT_ROWS))
def test_round_bit_identical_to_unconditional_reseed(name):
    data = _data()
    state = _case(name, data)
    got_state, got = hpclust._jit_run_from_state(state, data, cfg=CFG)
    want_state, want = jax.jit(_unconditional_rounds, static_argnums=2)(
        state, data, CFG)
    for g, w in zip(jax.tree.leaves(got_state), jax.tree.leaves(want_state)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    for g, w in zip((got.best_obj, got.accepted, got.kmeans_iters,
                     got.quarantined), want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # The first round's warm start is the case's own: both branches ran.
    np.testing.assert_array_equal(np.asarray(got.reseed_rows[0]),
                                  _WANT_ROWS[name])


def test_reseed_rows_count_the_warm_start_masks():
    data = _data()
    state = strat.init_state(jax.random.PRNGKey(2), CFG, D)
    state = state._replace(degenerate=jnp.asarray(
        np.random.default_rng(3).random((W, K)) < 0.4))
    cfg = strat.HPClustConfig(k=K, sample_size=S, workers=W, rounds=1,
                              strategy="competitive", impl="ref")
    _, metrics = hpclust._jit_run_from_state(state, data, cfg=cfg)
    assert metrics.reseed_rows.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(metrics.reseed_rows[0]),
                                  np.asarray(state.degenerate).sum(axis=1))


def test_round_program_holds_the_reseed_conditional():
    data = _data()
    state = strat.init_state(jax.random.PRNGKey(0), CFG, D)
    lowered = hpclust._jit_run_from_state.lower(state, data, cfg=CFG)
    # Under the vmap over workers the cond would lower to a select.
    assert re.search(r"stablehlo\.(case|if)\b", lowered.as_text())
    names = [n for line in lowered.compile().as_text().splitlines()
             if " conditional(" in line
             for n in re.findall(r'op_name="([^"]*)"', line)]
    assert any("round.reseed/cond" in n and "vmap(" not in n for n in names)
