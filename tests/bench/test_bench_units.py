"""Unit tests of the benchmark's yardstick: the work counted for each kernel,
the peak table, the generator against blob_stream, the float64 reference,
and the shape of BENCHMARK.json."""
from __future__ import annotations

import importlib.util
import json
import re
import sys

import numpy as np
import pytest

from benchkit import ROOT

sys.path.insert(0, str(ROOT))

from bench import check, draws, engines, gen, peaks, reference, spec  # noqa: E402


def _metric_module(name: str):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    s = importlib.util.spec_from_file_location(f"m_{name}", path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


def test_assign_work_matches_hand_count():
    # s=4 rows, k=3 centroids, d=2: 4*3 distances of 2 multiply-adds each;
    # x (4*2 f32) and c (3*2 f32) read, 4 labels and 4 distances written.
    flops, nbytes = _metric_module("assign_roofline_pct").work(4, 3, 2)
    assert flops == 48
    assert nbytes == 4 * 2 * 4 + 3 * 2 * 4 + 4 * 4 + 4 * 4


def test_update_work_matches_hand_count():
    # One add per element of x (4*2); x and 4 int32 labels read, 3*2 sums
    # and 3 counts written.
    flops, nbytes = _metric_module("update_roofline_pct").work(4, 3, 2)
    assert flops == 8
    assert nbytes == 4 * 2 * 4 + 4 * 4 + 3 * 2 * 4 + 3 * 4


def test_peak_table_refuses_an_unknown_device_kind():
    with pytest.raises(KeyError, match="no peaks for device kind 'cpu'"):
        peaks.peak("cpu")
    pk = peaks.peak("TPU v5 lite")
    assert pk.flops_per_s == 197e12 and pk.hbm_bytes_per_s == 819e9


def test_roofline_names_its_bound():
    pk = peaks.Peak(flops_per_s=100.0, hbm_bytes_per_s=10.0)
    assert peaks.roofline_s(1000.0, 10.0, pk) == (10.0, "compute")
    assert peaks.roofline_s(10.0, 1000.0, pk) == (100.0, "memory")


def _split(x, centres, sigmas, noise_dist):
    """Rows near a centre, with their standardized residuals; and the rest."""
    d2 = reference.sq_dists(x, centres)
    near = d2.min(axis=1) < noise_dist ** 2
    lab = d2.argmin(axis=1)
    z = (x[near] - centres[lab[near]]) / sigmas[lab[near], None]
    return near, z


def test_generator_matches_blob_stream_at_a_tiny_size():
    from repro.data import blob_stream

    m, d, k, seed = 8192, 64, 5, 3
    cfg = {"k": k, "d": d, "mixture": {"seed": 11, "box": 40.0,
                                       "sigma_max": 10.0, "noise_frac": 0.05,
                                       "noise_box": 50.0}}
    mix = gen.mixture(cfg)
    ours = gen.rows(gen.run_key(seed), mix, m, block=4096)
    theirs = next(blob_stream(m, n=d, k=k, seed=seed))
    # blob_stream's own mixture, drawn as it draws it.
    rng = np.random.default_rng(seed)
    t_centres = rng.uniform(-40, 40, size=(k, d))
    t_sigmas = rng.uniform(0.0, 10.0, size=(k,))

    assert ours.dtype == np.float32 and ours.shape == (m, d)
    assert np.all(np.abs(mix.centres) <= 40) and np.all(np.abs(t_centres) <= 40)
    assert np.all((mix.sigmas >= 0) & (mix.sigmas <= 10))
    # A noise row lies ~sqrt(d * (833 + 533)) ~ 300 from every centre, a
    # cluster row within ~10 * sqrt(d) * 1.5 = 120 of its own.
    for x, c, s in ((ours, mix.centres, mix.sigmas),
                    (theirs, t_centres, t_sigmas)):
        near, z = _split(x, c, s, noise_dist=180.0)
        assert (~near).sum() == int(m * 0.05)
        assert np.all(np.abs(x[~near]) <= 50.0)
        assert abs(z.mean()) < 0.01 and abs(z.std() - 1.0) < 0.01


def test_generator_is_a_function_of_the_seed():
    cfg = {"k": 3, "d": 8, "mixture": {"seed": 1, "box": 40.0,
                                       "sigma_max": 10.0, "noise_frac": 0.05,
                                       "noise_box": 50.0}}
    mix = gen.mixture(cfg)
    big = 2 ** 33 + 17
    a = gen.rows(gen.run_key(big), mix, 1024, block=512)
    b = gen.rows(gen.run_key(big), mix, 1024, block=512)
    c = gen.rows(gen.run_key(17), mix, 1024, block=512)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)


def test_reference_is_float64_on_the_host():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(300, 7)).astype(np.float32)
    c = rng.normal(size=(4, 7)).astype(np.float32)
    d2 = reference.sq_dists(x, c)
    assert d2.dtype == np.float64
    brute = ((x.astype(np.float64)[:, None, :] - c[None]) ** 2).sum(-1)
    np.testing.assert_allclose(d2, brute, rtol=1e-12, atol=1e-12)
    assert reference.objective(x, c, batch=64) == pytest.approx(
        brute.min(axis=1).sum(), rel=1e-12)
    sums, counts = reference.cluster_sums(x, brute.argmin(axis=1), 4)
    assert sums.dtype == np.float64
    for j in range(4):
        rows = x[brute.argmin(axis=1) == j].astype(np.float64)
        np.testing.assert_allclose(sums[j], rows.sum(axis=0), rtol=1e-12)
        assert counts[j] == len(rows)


def test_incumbent_round_is_the_last_change():
    inf = np.inf
    h = np.array([[5.0, inf, 7.0], [4.0, inf, 7.0], [4.0, inf, 6.0],
                  [3.0, inf, 6.0]], np.float32)
    assert draws.incumbent_rounds(h).tolist() == [3, -1, 2]


def test_draws_replay_the_rows_each_worker_fitted():
    """The replayed rows give each worker's reported objective to float32
    rounding; under another seed's draws the same incumbents read far off."""
    from repro.core import HPClust, HPClustConfig

    cfg = {"k": 3, "d": 8, "sample_size": 512,
           "mixture": {"seed": 2, "box": 40.0, "sigma_max": 10.0,
                       "noise_frac": 0.05, "noise_box": 50.0}}
    traffic = {"rounds_per_window": 2}
    seed = 2 ** 33 + 9
    mix = gen.mixture(cfg)
    ring = [gen.rows(gen.run_key(seed + i), mix, 4096) for i in range(2)]
    hp = HPClust(HPClustConfig(k=3, sample_size=512, workers=3, rounds=2,
                               kmeans_iters=20, impl="ref"),
                 seed=seed & 0xFFFFFFFF)
    res = hp.fit_stream(iter([ring[0], ring[1], ring[0]]),
                        rounds_per_window=2)
    out = engines.Outcome(res.stats.windows, res.centroids, res.objective,
                          res.history, np.asarray(res.state.centroids),
                          np.asarray(res.state.best_obj))
    assert check._objective_gap(cfg, traffic, out, ring, seed) < 1e-5
    assert check._objective_gap(cfg, traffic, out, ring, seed + 1) > 1e-3


_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_json_names_every_part_it_needs():
    b = spec.load_spec()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    cfg_names = {c["name"] for c in b["configs"]}
    for c in b["configs"]:
        assert _NAME.match(c["name"]) and set(c) == {
            "name", "source", "file", "reduced", "why"}
        with open(ROOT / c["file"]) as f:
            body = json.load(f)
        assert body["reduced"] == c["reduced"]
        assert "assumed" in body and "limits" in body
    e2e = {m["name"] for m in b["end_to_end"]}
    assert {"rows_per_s", "setup_s"} <= e2e
    for w in b["workloads"]:
        assert _NAME.match(w["name"]) and w["config"] in cfg_names
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").exists()
        assert w["chips"] in (1, 4)
        cell = spec.load_cell(w["name"])
        assert cell.per_layer, w["name"]
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        assert callable(spec.metric_reader(m["name"]))
        assert set(m["workloads"]) <= {w["name"] for w in b["workloads"]}
