"""Benchmark orchestrator. One function per paper table.

Prints ``name,us_per_call,derived`` CSV rows:
  * per paper table: us_per_call = median wall time of the winning algorithm
    on that row, derived = its relative accuracy eps (%);
  * kernel rows: FlashAssign timing per implementation (``ref`` always,
    ``interpret`` to exercise the Pallas kernel body, ``pallas`` compiled
    when a TPU backend is attached), derived = points/s;
  * stream_throughput rows: end-to-end ``fit_stream`` points/s over an
    ingest-latency-bound window reader, synchronous vs prefetch+donation
    (the ``/speedup`` row's derived is the ratio, higher is better);
  * roofline rows (if dry-run artifacts exist): derived = dominant-term
    seconds per step.

Scale knob: REPRO_BENCH_SCALE (default 0.5). Run on the CPU, every row is
a host-clock CPU timing, never a device measurement.
Section filter: REPRO_BENCH_SECTIONS, a comma list of
``kernels,stream,tables,scaling,fig3,roofline`` (default: all). CI's bench
job runs ``kernels,stream`` at tiny scale and diffs against the committed
baseline (benchmarks/diff.py).

Besides the CSV on stdout, results are written machine-readably to
``BENCH_hpclust.json`` (override with REPRO_BENCH_JSON) as
``{name: {"us_per_call": ..., "derived": ...}}`` for diffing across runs.
"""
from __future__ import annotations

import json
import os
import sys
import time


def _rows_table3_4(scale):
    from benchmarks import tables

    for ds, strat, eps, t in tables.table3_4(n_exec=2, scale=scale):
        yield (f"table3_strategy_eps/{ds}/{strat}", t * 1e6, eps)


def _rows_table5_6(scale):
    from benchmarks import tables

    for ds, algo, eps, t in tables.table5_6(n_exec=2, scale=scale):
        yield (f"table5_vs_baselines/{ds}/{algo}", t * 1e6, eps)


def _rows_table7_8():
    from benchmarks import tables

    for m, algo, eps, t in tables.table7_8(max_pow=10, n_exec=1):
        yield (f"table7_scaling/m{m}/{algo}", t * 1e6, eps)


def _kernel_impls():
    import jax

    impls = ["ref", "interpret"]
    if jax.default_backend() == "tpu":
        impls.append("pallas")
    return impls


def _rows_kernels(scale):
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops

    rng = np.random.default_rng(0)
    shapes = ((4096, 16, 64), (8192, 64, 256))
    if scale < 0.5:  # tiny/CI scale: one shape keeps interpret mode cheap
        shapes = shapes[:1]
    for s, k, d in shapes:
        x = jnp.asarray(rng.normal(size=(s, d)), jnp.float32)
        c = jnp.asarray(rng.normal(size=(k, d)), jnp.float32)
        for impl in _kernel_impls():
            fn = lambda: ops.assign_clusters(x, c, impl=impl)[0].block_until_ready()
            fn()
            t0 = time.time()
            n = 5 if impl == "ref" else 3
            for _ in range(n):
                fn()
            us = (time.time() - t0) / n * 1e6
            yield (f"kernel_assign/{impl}/s{s}k{k}d{d}", us, s / (us / 1e6))


def _rows_stream(scale):
    """End-to-end fit_stream throughput: synchronous vs prefetch+donation.

    The reader serves PRE-STAGED windows behind an emulated per-window fetch
    latency (``io_s``) — the shape of the paper's infinitely-tall regime,
    where windows arrive from storage/network, not from an in-process
    generator. Prefetch overlaps that latency (plus sanitize + H2D) with
    device compute; donation reuses the state buffers across windows. The
    single-core CPU container cannot overlap CPU-bound synthesis with
    CPU-bound XLA compute, so synthesizing data inside the reader would
    measure core contention, not the engine.
    """
    import numpy as np

    from repro.core.hpclust import HPClust
    from repro.core.strategies import HPClustConfig
    from repro.data.pipeline import blob_stream

    cfg = HPClustConfig(k=10, sample_size=2048, workers=4, rounds=4)
    big = scale >= 0.5
    window = 1 << 17 if big else 1 << 15
    n_windows = 8 if big else 4
    io_s = 0.06
    reps = 3 if big else 2

    gen = blob_stream(window, n=10, k=10, seed=1)
    bufs = [np.asarray(next(gen), np.float32) for _ in range(3)]

    def reader():
        for i in range(n_windows):
            time.sleep(io_s)  # emulated shard-fetch latency
            yield bufs[i % len(bufs)]

    def run(prefetch: int, donate: bool) -> float:
        os.environ["REPRO_DONATE"] = "1" if donate else "0"
        try:
            hp = HPClust(cfg, seed=0, prefetch=prefetch)
            t0 = time.perf_counter()
            hp.fit_stream(reader())
            return time.perf_counter() - t0
        finally:
            os.environ.pop("REPRO_DONATE", None)

    run(0, False)  # warm the compile caches
    t_sync = min(run(0, False) for _ in range(reps))
    t_pref = min(run(2, True) for _ in range(reps))
    points = window * n_windows
    yield ("stream_throughput/sync", t_sync * 1e6, points / t_sync)
    yield ("stream_throughput/prefetch_donate", t_pref * 1e6, points / t_pref)
    yield ("stream_throughput/speedup", t_pref * 1e6, t_sync / t_pref)


def _rows_fig3():
    from benchmarks import tables

    for strat, w, eps, t in tables.fig3_workers(n_exec=1):
        yield (f"fig3_workers/{strat}/w{w}", t * 1e6, eps)


def _rows_roofline():
    try:
        from benchmarks import roofline

        rows = roofline.build_table()
    except Exception as e:  # pragma: no cover
        print(f"# roofline section unavailable: {e!r}", file=sys.stderr)
        return
    for r in rows:
        t_dom = max(r["t_compute_s"], r["t_memory_s"], r["t_collective_s"])
        yield (
            f"roofline/{r['arch']}/{r['shape']}/{r['mesh']}/{r['dominant']}",
            t_dom * 1e6,
            r["roofline_fraction"],
        )


def main() -> None:
    scale = float(os.environ.get("REPRO_BENCH_SCALE", "0.5"))
    json_path = os.environ.get("REPRO_BENCH_JSON", "BENCH_hpclust.json")
    wanted = os.environ.get("REPRO_BENCH_SECTIONS", "")
    wanted = {s.strip() for s in wanted.split(",") if s.strip()} or None
    sections = [
        ("kernels", lambda: _rows_kernels(scale)),
        ("stream", lambda: _rows_stream(scale)),
        ("tables", lambda: _rows_table3_4(scale)),
        ("tables", lambda: _rows_table5_6(scale)),
        ("scaling", _rows_table7_8),
        ("fig3", _rows_fig3),
        ("roofline", _rows_roofline),
    ]
    print("name,us_per_call,derived")
    results: dict[str, dict[str, float]] = {}
    for label, make_rows in sections:
        if wanted is not None and label not in wanted:
            continue
        for name, us, derived in make_rows():
            print(f"{name},{us:.1f},{derived:.4f}")
            sys.stdout.flush()
            results[name] = {"us_per_call": round(us, 1),
                             "derived": round(float(derived), 4)}
    if json_path:
        with open(json_path, "w", encoding="utf-8") as fh:
            json.dump(results, fh, indent=1, sort_keys=True)
            fh.write("\n")
        print(f"# wrote {len(results)} result(s) to {json_path}",
              file=sys.stderr)


if __name__ == "__main__":
    main()
