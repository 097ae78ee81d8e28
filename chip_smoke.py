#!/usr/bin/env python3
"""Smoke run of HPClust's streaming path on a TPU.

    python3 chip_smoke.py               # one chip
    python3 chip_smoke.py --four-chips  # the sharded engine on four chips

One chip: the Pallas kernels against the jnp reference at d=768 and d=28;
then ``HPClust.fit_stream``, called as ``python -m repro.launch.cluster``
calls it, over 3 seeded ``blob_stream`` windows of 2^20 x 768 rows at the
``hpclust-prod`` shape (k=25, 8 workers, s=16384, hybrid, 8 rounds per
window). The result is checked against a float64 objective computed on the
host and against a K-means++-seeded Lloyd run on the first window.

Four chips: ``run_elastic_sharded`` over the same windows on a (2, 2) mesh,
checked against the host float64 objective, and nothing else.

Every line but the last is a JSON record of one phase or check. The last
line, ``{"ok": true, "device": {...}}``, is printed only when every check
passed. Off a TPU the script exits non-zero before any work. The timings it
prints include compilation and host data generation: they are not metrics.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.launch.entry import device_info, enable_compile_cache  # noqa: E402

# The hpclust-prod shape (CORD-19-like embeddings), one chip's worth.
D, K, WORKERS, SAMPLE, ROUNDS = 768, 25, 8, 16384, 8
WINDOW, WINDOWS, HOLDOUT, SEED = 1 << 20, 3, 200_000, 0
D_LOW = 28  # HEPMASS-shaped low-d set, kernel phase only

# Tolerances. Relative errors are max|a - b| / max|b|.
KERNEL_AGREEMENT = 0.999  # labels that agree, or tie within KERNEL_RTOL
KERNEL_RTOL = 1e-4        # distances and sums, Pallas vs jnp reference
OBJECTIVE_RTOL = 1e-4     # holdout objective on the chip vs host float64
REFERENCE_RTOL = 0.01     # HPClust may trail the reference Lloyd by 1%
# Per-row sample objective vs per-row holdout objective. Keep-the-best
# favours samples that drew few of the far noise rows, so the sample side
# runs low by several percent; this bound only catches a wrong scale.
SHARDED_RTOL = 0.25


def host_objective(x: np.ndarray, c: np.ndarray, batch: int = 1 << 15) -> float:
    """f(C, X) in numpy float64, independent of the code under test."""
    c64 = np.asarray(c, np.float64)
    cc = np.sum(c64 * c64, axis=1)
    total = 0.0
    for i in range(0, len(x), batch):
        xb = np.asarray(x[i:i + batch], np.float64)
        d2 = np.sum(xb * xb, axis=1)[:, None] - 2.0 * xb @ c64.T + cc[None]
        total += float(np.maximum(d2, 0.0).min(axis=1).sum())
    return total


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def _monotone(history: np.ndarray) -> bool:
    return bool(np.isfinite(history).all()
                and (np.diff(history, axis=0) <= 0).all())


def kernel_phase(*, s: int, k: int, d: int, impl: str, seed: int = SEED) -> dict:
    """``ops.assign_clusters``/``ops.cluster_sums`` under ``impl`` against
    ``impl="ref"`` on the same arrays, and both against host float64."""
    import jax.numpy as jnp

    from repro.data import blob_stream
    from repro.kernels import ops

    gen = blob_stream(s, n=d, k=k, seed=seed)
    x = next(gen)
    c = next(gen)[:k]
    xd, cd = jnp.asarray(x), jnp.asarray(c)
    idx, dist = ops.assign_clusters(xd, cd, impl=impl)
    ridx, rdist = ops.assign_clusters(xd, cd, impl="ref")
    sums, counts = ops.cluster_sums(xd, ridx, k, impl=impl)
    rsums, rcounts = ops.cluster_sums(xd, ridx, k, impl="ref")

    x64, c64 = x.astype(np.float64), c.astype(np.float64)
    d2 = np.maximum(np.sum(x64 * x64, 1)[:, None] - 2.0 * x64 @ c64.T
                    + np.sum(c64 * c64, 1)[None], 0.0)
    idx, ridx = np.asarray(idx), np.asarray(ridx)
    rows = np.arange(s)
    # Two labels tie when both are within KERNEL_RTOL of the float64 minimum.
    tie = KERNEL_RTOL * d2.min(axis=1).max()
    ties = ((d2[rows, idx] - d2.min(axis=1) <= tie)
            & (d2[rows, ridx] - d2.min(axis=1) <= tie))
    sums64 = np.eye(k)[ridx].T @ x64
    return {
        "phase": "kernel", "impl": impl, "s": s, "k": k, "d": d,
        "label_agreement": float(np.mean(idx == ridx)),
        "label_agreement_up_to_ties": float(np.mean((idx == ridx) | ties)),
        "dist_rel_err": _rel(dist, rdist),
        "sums_rel_err": _rel(sums, rsums),
        "counts_equal": bool(np.array_equal(np.asarray(counts),
                                            np.asarray(rcounts))),
        "kernel_dist_vs_f64": _rel(dist, d2.min(axis=1)),
        "ref_dist_vs_f64": _rel(rdist, d2.min(axis=1)),
        "kernel_sums_vs_f64": _rel(sums, sums64),
        "ref_sums_vs_f64": _rel(rsums, sums64),
    }


def _windows(window: int, windows: int, d: int, k: int, seed: int,
             kept: list, gen_s: list):
    """The cluster CLI's stream. Keeps the first window on the host and the
    seconds each window took to generate."""
    from repro.core.hpclust import stream_from_generator
    from repro.data import blob_stream

    def timed(gen):
        while True:
            t0 = time.perf_counter()
            w = next(gen)
            gen_s.append(time.perf_counter() - t0)
            yield w

    for i, w in enumerate(stream_from_generator(
            timed(blob_stream(window, n=d, k=k, seed=seed)), windows)):
        if i == 0:
            kept.append(w)
        yield w


def _holdout(rows: int, d: int, k: int, seed: int) -> np.ndarray:
    """A fresh window of the same distribution, as the cluster CLI draws it."""
    from repro.data import blob_stream

    return next(iter(blob_stream(rows, n=d, k=k, seed=seed)))


def stream_phase(*, d: int, k: int, workers: int, sample: int, rounds: int,
                 window: int, windows: int, holdout: int, impl: str,
                 seed: int = SEED) -> tuple[dict, dict]:
    """``HPClust.fit_stream`` as the cluster CLI runs it, with ``impl``.

    Returns the printed record and the arrays the reference phase needs.
    """
    import jax
    import jax.numpy as jnp

    from repro import flags
    from repro.core import HPClust, HPClustConfig, hpclust, strategies

    cfg = HPClustConfig(k=k, sample_size=sample, workers=workers,
                        rounds=rounds, strategy="hybrid", impl=impl)
    hp = HPClust(cfg, seed=seed)

    # The round program fit_stream runs, compiled ahead from shapes: its
    # text shows whether the Pallas kernels are in it.
    run = (hpclust._jit_run_from_state_donated if flags.donate_enabled()
           else hpclust._jit_run_from_state)
    state = jax.eval_shape(
        lambda: strategies.init_state(jax.random.PRNGKey(seed), cfg, d))
    t0 = time.perf_counter()
    compiled = run.lower(state, jax.ShapeDtypeStruct((window, d), jnp.float32),
                         cfg=cfg).compile()
    round_compile_s = time.perf_counter() - t0
    custom_calls = compiled.as_text().count("tpu_custom_call")

    kept: list[np.ndarray] = []
    gen_s: list[float] = []
    t0 = time.perf_counter()
    res = hp.fit_stream(_windows(window, windows, d, k, seed, kept, gen_s))
    fit_s = time.perf_counter() - t0
    stats = jax.devices()[0].memory_stats() or {}

    x_hold = _holdout(holdout, d, k, seed)
    t0 = time.perf_counter()
    chip_obj = hp.objective(x_hold, res.centroids)
    objective_s = time.perf_counter() - t0
    host_obj = host_objective(x_hold, res.centroids)
    record = {
        "phase": "stream", "impl": impl, "d": d, "k": k, "workers": workers,
        "sample": sample, "rounds_per_window": rounds, "window_rows": window,
        "windows": res.stats.windows, "rounds_total": len(res.history),
        "round_program_compile_s": round_compile_s,
        "round_program_tpu_custom_calls": custom_calls,
        "fit_stream_s": fit_s,
        "host_window_generation_s": gen_s,
        "rows_per_s_incl_compile": windows * window / fit_s,
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "sample_objective": res.objective,
        "monotone": _monotone(res.history),
        "holdout_rows": holdout,
        "holdout_objective_chip": chip_obj,
        "holdout_objective_host_f64": host_obj,
        "holdout_rel_gap": abs(chip_obj - host_obj) / host_obj,
        "holdout_objective_s": objective_s,
    }
    return record, {"first_window": kept[0], "holdout": x_hold}


def reference_phase(*, x: np.ndarray, holdout: np.ndarray, k: int,
                    seed: int = SEED) -> dict:
    """K-means++ seeds and Lloyd on all of ``x`` with the jnp reference
    kernels; its holdout objective is computed on the host in float64."""
    from repro.core.baselines import kmeanspp_kmeans

    t0 = time.perf_counter()
    ref = kmeanspp_kmeans(x, k, seed=seed, impl="ref")
    return {
        "phase": "reference", "rows": len(x), "k": k,
        "lloyd_iterations": ref.iterations,
        "seconds": time.perf_counter() - t0,
        "holdout_objective_host_f64": host_objective(holdout, ref.centroids),
    }


def sharded_phase(*, d: int, k: int, sample: int, rounds: int, window: int,
                  windows: int, holdout: int, impl: str,
                  seed: int = SEED) -> dict:
    """``run_elastic_sharded`` on ``make_host_mesh()`` over every device,
    recording where each window's reservoir shards landed. The holdout
    objective of its centroids is taken on the chip with ``impl`` and on
    the host in float64."""
    import jax

    from repro.core import HPClust, HPClustConfig
    from repro.launch.elastic import run_elastic_sharded

    placements = []

    def record_placement(runner):
        def run(state, reservoir):
            placements.append(sorted(
                (sh.device.id, tuple(sh.data.shape))
                for sh in reservoir.addressable_shards))
            return runner(state, reservoir)
        return run

    gen_s: list[float] = []
    t0 = time.perf_counter()
    res = run_elastic_sharded(
        _windows(window, windows, d, k, seed, [], gen_s), k=k,
        sample_size=sample,
        rounds_per_window=rounds, strategy="hybrid", seed=seed,
        runner_wrapper=record_placement)
    seconds = time.perf_counter() - t0
    peak = [(dev.memory_stats() or {}).get("peak_bytes_in_use")
            for dev in jax.devices()]
    x_hold = _holdout(holdout, d, k, seed)
    chip_obj = HPClust(HPClustConfig(k=k, sample_size=sample, impl=impl)
                       ).objective(x_hold, res.centroids)
    host_obj = host_objective(x_hold, res.centroids)
    per_row_sample = res.objective / sample
    per_row_holdout = host_obj / holdout
    return {
        "phase": "sharded", "d": d, "k": k, "workers": res.workers,
        "sample": sample, "rounds_per_window": rounds, "window_rows": window,
        "windows": res.windows_done, "rounds_total": len(res.history),
        "seconds": seconds,
        "host_window_generation_s": gen_s,
        "reservoir_shards": placements[0] if placements else [],
        "reservoir_devices": sorted({i for p in placements for i, _ in p}),
        "peak_bytes_in_use": peak,
        "monotone": _monotone(res.history),
        "holdout_objective_chip": chip_obj,
        "holdout_objective_host_f64": host_obj,
        "holdout_rel_gap": abs(chip_obj - host_obj) / host_obj,
        "sample_objective_per_row": per_row_sample,
        "holdout_objective_host_f64_per_row": per_row_holdout,
        "per_row_rel_gap": abs(per_row_sample - per_row_holdout)
        / per_row_holdout,
    }


class Checks:
    """Prints one JSON line per check and remembers the failures."""

    def __init__(self):
        self.failed: list[str] = []

    def __call__(self, name: str, ok: bool, **values) -> None:
        print(json.dumps({"check": name, "ok": bool(ok), **values}), flush=True)
        if not ok:
            self.failed.append(name)


def _emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def run_one_chip(check: Checks) -> None:
    for d in (D, D_LOW):
        kr = kernel_phase(s=SAMPLE, k=K, d=d, impl="pallas")
        _emit(kr)
        check(f"kernel_d{d}",
              kr["label_agreement_up_to_ties"] >= KERNEL_AGREEMENT
              and kr["dist_rel_err"] <= KERNEL_RTOL
              and kr["sums_rel_err"] <= KERNEL_RTOL and kr["counts_equal"],
              agreement_min=KERNEL_AGREEMENT, rtol=KERNEL_RTOL)

    sr, arrays = stream_phase(d=D, k=K, workers=WORKERS, sample=SAMPLE,
                              rounds=ROUNDS, window=WINDOW, windows=WINDOWS,
                              holdout=HOLDOUT, impl="pallas")
    _emit(sr)
    check("kernels_in_round_program", sr["round_program_tpu_custom_calls"] > 0)
    check("windows_processed", sr["windows"] >= WINDOWS, want=WINDOWS)
    check("monotone_incumbents", sr["monotone"])
    check("holdout_chip_vs_host_f64", sr["holdout_rel_gap"] <= OBJECTIVE_RTOL,
          rtol=OBJECTIVE_RTOL)

    rr = reference_phase(x=arrays["first_window"], holdout=arrays["holdout"],
                         k=K)
    _emit(rr)
    ours = sr["holdout_objective_host_f64"]
    theirs = rr["holdout_objective_host_f64"]
    check("no_worse_than_reference_lloyd",
          ours <= (1.0 + REFERENCE_RTOL) * theirs,
          rel_gap=(ours - theirs) / theirs, rtol=REFERENCE_RTOL)


def run_four_chips(check: Checks) -> None:
    import jax

    n = len(jax.devices())
    if n != 4:
        raise SystemExit(f"chip_smoke: --four-chips needs 4 devices, JAX "
                         f"reports {n}")
    sh = sharded_phase(d=D, k=K, sample=SAMPLE, rounds=ROUNDS, window=WINDOW,
                       windows=WINDOWS, holdout=HOLDOUT, impl="pallas")
    _emit(sh)
    check("windows_processed", sh["windows"] >= WINDOWS, want=WINDOWS)
    check("reservoir_on_all_devices",
          sh["reservoir_devices"] == sorted(d.id for d in jax.devices()))
    check("monotone_incumbents", sh["monotone"])
    check("holdout_chip_vs_host_f64", sh["holdout_rel_gap"] <= OBJECTIVE_RTOL,
          rtol=OBJECTIVE_RTOL)
    check("sample_vs_holdout_per_row", sh["per_row_rel_gap"] <= SHARDED_RTOL,
          rtol=SHARDED_RTOL)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded engine on a (2, 2) mesh of "
                         "four chips")
    args = ap.parse_args(argv)

    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's first device is on platform "
              f"{platform!r}", file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    compile_s = []

    def on_duration(event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s.append(secs)

    jax.monitoring.register_event_duration_secs_listener(on_duration)

    t0 = time.perf_counter()
    _emit({"phase": "start", "device": device_info(), "cache_dir": cache_dir,
           "four_chips": args.four_chips})
    check = Checks()
    if args.four_chips:
        run_four_chips(check)
    else:
        run_one_chip(check)
    _emit({"phase": "end", "seconds": time.perf_counter() - t0,
           "backend_compile_s": sum(compile_s), "failed": check.failed})
    if check.failed:
        return 1
    print(json.dumps({"ok": True, "device": device_info()}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
