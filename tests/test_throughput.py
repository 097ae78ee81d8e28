"""Streaming throughput engine: prefetch, donation, autotune, bf16.

The performance layers added for docs/performance.md must be *invisible* to
results: prefetch on/off and donation on/off are bit-identical; autotune only
changes tile choices (padding makes every tile numerically exact); bf16 is
opt-in and bounded. These tests pin those contracts plus the machinery
itself (donation actually aliases buffers, the autotune cache round-trips,
the ragged objective tail no longer retraces).

    PYTHONPATH=src JAX_PLATFORMS=cpu pytest tests/test_throughput.py -q
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import flags
from repro.core import HPClust, HPClustConfig
from repro.core import hpclust as hp_mod
from repro.core import strategies
from repro.data import device_stream
from repro.data.pipeline import blob_stream
from repro.kernels import autotune, ops
from repro.kernels.assign import assign_pallas

CFG = HPClustConfig(k=4, sample_size=256, workers=2, rounds=3)


def _windows(n=3, m=2048, d=8, seed=0):
    gen = blob_stream(m, n=d, k=4, seed=seed)
    return [np.asarray(next(gen), np.float32) for _ in range(n)]


# ---------------------------------------------------------------------------
# donation
# ---------------------------------------------------------------------------


def _state(cfg=CFG, d=8, seed=0):
    return strategies.init_state(jax.random.PRNGKey(seed), cfg, d)


def test_donated_runner_lowering_aliases_output():
    data = jnp.asarray(_windows(1)[0])
    lowered = hp_mod._jit_run_from_state_donated.lower(
        _state(), data, cfg=CFG)
    # jax 0.4.37's donation marker in StableHLO: input aliased to an output.
    assert "tf.aliasing_output" in lowered.as_text()
    plain = hp_mod._jit_run_from_state.lower(_state(), data, cfg=CFG)
    assert "tf.aliasing_output" not in plain.as_text()


def test_donation_deletes_input_and_matches_copying_path():
    data = jnp.asarray(_windows(1)[0])
    s_copy, s_don = _state(), _state()
    out_copy, _ = hp_mod._jit_run_from_state(s_copy, data, cfg=CFG)
    out_don, _ = hp_mod._jit_run_from_state_donated(s_don, data, cfg=CFG)
    for a, b in zip(jax.tree_util.tree_leaves(out_copy),
                    jax.tree_util.tree_leaves(out_don)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert s_don.centroids.is_deleted()     # buffers were really donated
    assert not s_copy.centroids.is_deleted()


def test_fit_stream_bit_identical_across_prefetch_and_donation(monkeypatch):
    wins = _windows(3)
    results = []
    for prefetch, donate in ((0, "0"), (0, "1"), (2, "0"), (3, "1")):
        monkeypatch.setenv("REPRO_DONATE", donate)
        r = HPClust(CFG, seed=7, prefetch=prefetch).fit_stream(iter(wins))
        results.append(r)
    ref = results[0]
    for r in results[1:]:
        np.testing.assert_array_equal(r.centroids, ref.centroids)
        np.testing.assert_array_equal(r.history, ref.history)
        assert r.objective == ref.objective


def test_checkpoint_resume_bitforbit_with_donation_on(monkeypatch, tmp_path):
    from repro.resilience import chaos

    monkeypatch.setenv("REPRO_DONATE", "1")
    wins = _windows(4)
    full = HPClust(CFG, seed=3).fit_stream(iter(wins))

    # Crash at window 2: the pre-donation host snapshot must keep the
    # crash-save checkpoint readable (donation deletes the device buffers).
    with pytest.raises(chaos.ChaosError):
        HPClust(CFG, seed=3).fit_stream(
            chaos.crash_stream(iter(wins), at_window=2),
            checkpoint_dir=str(tmp_path), checkpoint_every=1,
        )
    resumed = HPClust(CFG, seed=3).fit_stream(
        iter(wins), checkpoint_dir=str(tmp_path), resume=True)
    np.testing.assert_array_equal(resumed.centroids, full.centroids)
    assert resumed.objective == full.objective


# ---------------------------------------------------------------------------
# prefetch
# ---------------------------------------------------------------------------


def test_device_stream_matches_sync_path():
    wins = _windows(3)
    wins[1][5] = np.nan  # one row for sanitize to repair
    sync = list(device_stream(iter(wins), depth=0))
    pref = list(device_stream(iter(wins), depth=2))
    assert [i.index for i in pref] == [i.index for i in sync]
    for a, b in zip(pref, sync):
        np.testing.assert_array_equal(a.host, b.host)
        np.testing.assert_array_equal(
            np.asarray(a.device), np.asarray(b.device))
        assert a.n_bad == b.n_bad


def test_device_stream_start_at_skips_without_preparing():
    wins = _windows(4)
    got = list(device_stream(iter(wins), depth=2, start_at=2))
    assert [i.index for i in got] == [2, 3]


def test_device_stream_reraises_original_exception():
    class Boom(RuntimeError):
        pass

    def gen():
        yield _windows(1)[0]
        raise Boom("producer died")

    with pytest.raises(Boom, match="producer died"):
        list(device_stream(gen(), depth=2))


class _Copy:
    """What a placement returns: a copy that lands when waited on."""

    def __init__(self, i, log, fails):
        self.i, self.log, self.fails = i, log, fails

    def block_until_ready(self):
        self.log.append(("landed", self.i))
        if self.fails:  # left for the consumer, not raised here
            raise jax.errors.JaxRuntimeError("copy failed")
        return self


def _logging_place(log, fails=False):
    def place(w):
        i = len([e for e in log if e[0] == "put"])
        log.append(("put", i))
        return _Copy(i, log, fails)
    return place


@pytest.mark.parametrize("copy_fails", [False, True])
def test_device_stream_starts_a_copy_once_the_last_has_landed(
        monkeypatch, copy_fails):
    from repro.data import device_prefetch

    log = []
    monkeypatch.setattr(device_prefetch, "default_place",
                        _logging_place(log, copy_fails))
    got = list(device_stream(iter(_windows(3)), depth=2))
    assert [i.device.i for i in got] == [0, 1, 2]
    assert log == [("put", 0), ("landed", 0), ("put", 1), ("landed", 1),
                   ("put", 2)]


def test_device_stream_leaves_a_given_placement_unsettled():
    # The sharded tier passes its own ``place``; its copies are not waited on.
    log = []
    got = list(device_stream(iter(_windows(3)), depth=2,
                             place=_logging_place(log)))
    assert [i.device.i for i in got] == [0, 1, 2]
    assert log == [("put", 0), ("put", 1), ("put", 2)]


def test_device_stream_flags_in_pull_order_and_stops():
    pulls = {"n": 0}
    wins = _windows(5)

    def gen():
        for w in wins:
            pulls["n"] += 1
            yield w

    # Preemption fires when the 3rd window is pulled; with depth 4 the
    # producer could run far ahead, but the flag must still land on index 2
    # and production must stop there.
    got = list(device_stream(
        gen(), depth=4, flag_fn=lambda: pulls["n"] >= 3))
    assert [i.flagged for i in got] == [False, False, True]
    assert pulls["n"] == 3


# ---------------------------------------------------------------------------
# autotune
# ---------------------------------------------------------------------------


def test_autotune_off_is_default_and_returns_none(monkeypatch):
    monkeypatch.delenv("REPRO_AUTOTUNE", raising=False)
    assert flags.autotune_mode() == "off"
    assert autotune.lookup("assign", 4096, 16, 64) is None


def test_autotune_cache_roundtrip_and_corrupt_fallback(monkeypatch, tmp_path):
    path = tmp_path / "autotune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    autotune.invalidate_memory_cache()
    key = autotune.cache_key("assign", 4096, 16, 64, backend="cpu")
    autotune._store(str(path), key, (256, 128, 128), 123.4)
    autotune.invalidate_memory_cache()
    assert autotune.lookup("assign", 4096, 16, 64, backend="cpu") == (
        256, 128, 128)
    # Bucketing: a nearby shape maps to the same entry.
    assert autotune.cache_key("assign", 3000, 16, 64, backend="cpu") == key
    # Corrupt cache file == empty cache == heuristic fallback, no raise.
    path.write_text("{not json")
    autotune.invalidate_memory_cache()
    assert autotune.lookup("assign", 4096, 16, 64, backend="cpu") is None
    autotune.invalidate_memory_cache()


def test_autotune_candidates_fit_budget_and_alignment():
    cands = autotune.candidates("assign", 4096, 16, 64)
    assert cands
    for bs, bk, bd in cands:
        assert bs % 8 == 0 and bk % 128 == 0 and bd % 128 == 0
        assert autotune.vmem_bytes(
            "assign", bs, bk, bd) <= autotune.VMEM_BUDGET_BYTES


def test_autotune_probe_persists_and_results_stay_exact(monkeypatch, tmp_path):
    path = tmp_path / "autotune.json"
    # A shape no other test compiles: block choice happens at TRACE time, so
    # probing needs a cold jit-cache entry for this (shape, impl) pair.
    x = np.asarray(_windows(1, m=301, d=24)[0])
    rng = np.random.default_rng(1)
    c = np.asarray(rng.normal(size=(6, 24)), np.float32)

    monkeypatch.delenv("REPRO_AUTOTUNE", raising=False)
    ref_idx, ref_d2 = ops.assign_clusters(
        jnp.asarray(x), jnp.asarray(c), impl="ref")

    # The registered factories time compiled TPU kernels only; stand in an
    # interpret-mode factory so the probe -> persist -> lookup path runs here.
    def interpret_probe(s, k, d, dtype, blocks):
        bs, bk, bd = blocks
        xp, cp = ops._probe_data(ops._round_up(s, bs), ops._round_up(d, bd),
                                 ops._round_up(k, bk))
        return lambda: assign_pallas(
            xp, cp, k_valid=k, block_s=bs, block_k=bk, block_d=bd,
            compute_dtype=dtype, interpret=True)

    monkeypatch.setitem(autotune._PROBE_FACTORIES, "assign", interpret_probe)
    monkeypatch.setenv("REPRO_AUTOTUNE", "probe")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    autotune.invalidate_memory_cache()
    try:
        idx, d2 = ops.assign_clusters(
            jnp.asarray(x), jnp.asarray(c), impl="interpret")
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(ref_idx))
        # ref reduces in a different order than the tiled kernel: ulp-level
        # drift is expected, tile choice must not add more than that.
        np.testing.assert_allclose(
            np.asarray(d2), np.asarray(ref_d2), rtol=1e-5)
        blob = json.loads(path.read_text())
        assert blob["version"] == 1
        [(key, entry)] = [(k, v) for k, v in blob["entries"].items()
                          if "/assign/" in k]
        assert len(entry["blocks"]) == 3 and entry["us"] > 0
    finally:
        autotune.invalidate_memory_cache()


def test_autotune_probe_refuses_off_tpu(monkeypatch, tmp_path):
    """The registered probes never time interpret mode into the cache."""
    path = tmp_path / "autotune.json"
    # A shape no other test compiles: tiles are chosen at trace time.
    x = np.asarray(_windows(1, m=299, d=20)[0])
    c = np.asarray(np.random.default_rng(2).normal(size=(5, 20)), np.float32)
    monkeypatch.setenv("REPRO_AUTOTUNE", "probe")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    autotune.invalidate_memory_cache()
    try:
        with pytest.raises(RuntimeError, match="backend is 'cpu'"):
            ops.assign_clusters(jnp.asarray(x), jnp.asarray(c),
                                impl="interpret")
        assert not path.exists()
    finally:
        autotune.invalidate_memory_cache()


# ---------------------------------------------------------------------------
# bf16 compute dtype
# ---------------------------------------------------------------------------


def test_bf16_assign_matches_f32_within_tolerance():
    x = jnp.asarray(_windows(1, m=300, d=16)[0])
    c = jnp.asarray(
        np.random.default_rng(2).normal(size=(5, 16)), jnp.float32)
    i32, d32 = ops.assign_clusters(x, c, impl="interpret")
    i16, d16 = ops.assign_clusters(
        x, c, impl="interpret", compute_dtype="bf16")
    agree = float(np.mean(np.asarray(i32) == np.asarray(i16)))
    assert agree >= 0.99  # ties may flip under bf16 rounding
    np.testing.assert_allclose(
        np.asarray(d16), np.asarray(d32), rtol=2e-2, atol=2e-2)


def test_bf16_lloyd_counts_accumulate_in_f32():
    # 3000 rows into one cluster would saturate a bf16 count (max 256 steps
    # of +1 at 256); f32 accumulation must count exactly.
    x = jnp.asarray(np.zeros((3000, 8), np.float32))
    c = jnp.asarray(np.stack([np.zeros(8), np.full(8, 100.0)]), jnp.float32)
    _, _, _, counts = ops.lloyd_pass(x, c, impl="interpret",
                                     compute_dtype="bf16")
    np.testing.assert_array_equal(np.asarray(counts), [3000.0, 0.0])


# ---------------------------------------------------------------------------
# ragged objective tail
# ---------------------------------------------------------------------------


def test_objective_tail_batch_does_not_retrace():
    hp = HPClust(CFG, seed=0)
    c = np.asarray(
        np.random.default_rng(3).normal(size=(4, 8)), np.float32)
    rng = np.random.default_rng(4)
    batch = 512
    full = np.asarray(rng.normal(size=(batch, 8)), np.float32)
    v_full = hp.objective(full, c, batch=batch)

    before = ops._mssc_objective_jit._cache_size()
    for tail in (1, 17, 300):  # three different ragged tails
        hp.objective(
            np.asarray(rng.normal(size=(batch + tail, 8)), np.float32),
            c, batch=batch)
    # Padding pins the shapes to (batch, d) + the (1, d) probe: at most those
    # two new entries total, NOT one per tail length.
    assert ops._mssc_objective_jit._cache_size() - before <= 2

    # And the padded value equals the unpadded math.
    tail_rows = np.asarray(rng.normal(size=(3, 8)), np.float32)
    both = np.concatenate([full, tail_rows])
    expect = v_full + hp.objective(tail_rows, c, batch=batch)
    assert hp.objective(both, c, batch=batch) == pytest.approx(
        expect, rel=1e-5)
