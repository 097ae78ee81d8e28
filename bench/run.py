#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator this process holds.

    python bench/run.py --workload cord19-search --seed 7 --seconds 30 --trace 0

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration and a
traffic mix. Set-up draws the cell's data from ``--seed`` on the device,
copies a ring of two windows and a holdout to the host, builds the program's
object and sends one window through the timed entry, so that every program
the window runs is compiled before it. The timed call then streams the ring
until ``--seconds`` have passed and returns once the windows already taken
are done. Afterwards the answers are compared with the float64 reference
(``bench/check.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``, every compared number beside its limit,
which are also the last lines of standard error. With ``--trace 0`` the
metrics are the cell's end-to-end ones; with ``--trace 1`` the per-layer
ones, read from a profiler trace of a slice of the window. Off a TPU, or on
fewer chips than the cell asks for, it exits with 2 and prints no result.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import threading
import time
from pathlib import Path

_T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import spec  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
RING = 2  # host windows the source cycles through


def require_accelerator(chips: int) -> None:
    """Exit with 2 unless JAX's devices are TPUs and at least ``chips``."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"bench: the cell needs {chips} TPU chip(s); JAX reports "
              f"{len(devs)} device(s) on platform {devs[0].platform!r}",
              file=sys.stderr)
        raise SystemExit(2)


def device_info() -> dict:
    import jax

    devs = jax.devices()
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def enable_compile_cache() -> None:
    """JAX's persistent cache, in the checkout at a fixed path unless
    ``JAX_COMPILATION_CACHE_DIR`` names one; every program is kept."""
    import os

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts programs compiled and programs loaded from the persistent
    cache, so that the window can show neither and a warm set-up no
    compilation. JAX times a load from the cache as a backend compile too,
    so ``n`` counts both and ``hits`` the loads."""

    def __init__(self):
        import jax

        self.n = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


class SliceTracer:
    """Profiles a slice of the window from a thread of its own: it starts
    ``lead`` seconds in and stops once the source has handed out
    ``windows`` more windows, or after ``cap`` seconds. Counting windows
    keeps the slice to a few executions of the round program in every
    cell, whatever a window costs."""

    def __init__(self, taken: list, lead: float, windows: int = 3,
                 cap: float = 6.0):
        self.taken, self.lead, self.windows, self.cap = taken, lead, windows, cap
        self.error: BaseException | None = None
        self._thread = threading.Thread(target=self._run, name="bench-trace")

    def start(self) -> None:
        self._thread.start()

    def _run(self) -> None:
        import shutil

        import jax

        try:
            time.sleep(self.lead)
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(str(TRACE_DIR))
            try:
                with jax.profiler.TraceAnnotation("bench.slice"):
                    first, t0 = self.taken[0], time.perf_counter()
                    while (self.taken[0] - first < self.windows
                           and time.perf_counter() - t0 < self.cap):
                        time.sleep(0.02)
            finally:
                jax.profiler.stop_trace()
        except BaseException as e:  # noqa: BLE001 — reported by join()
            self.error = e

    def join(self) -> None:
        self._thread.join()
        if self.error is not None:
            raise self.error


def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool,
             t_start: float = _T_START, compiles: CompileCounter | None = None
             ) -> dict:
    """Set up, time and check one run; returns the result line's object."""
    import jax

    from bench import check, engines, gen, peaks

    enable_compile_cache()
    compiles = compiles or CompileCounter()
    cfg, traffic = cell.config, cell.traffic
    m = int(traffic.get("window_rows", cfg["window_rows"]))
    parts = {"start_s": time.perf_counter() - t_start}
    mix = gen.mixture(cfg)
    key = gen.run_key(seed)
    ring = [gen.rows(jax.random.fold_in(key, i), mix, m) for i in range(RING)]
    holdout = gen.rows(jax.random.fold_in(key, RING), mix,
                       int(cfg["holdout_rows"]))
    parts["data_s"] = time.perf_counter() - t_start - parts["start_s"]
    engine = engines.FitStream(cfg, traffic, seed)
    # Two windows compile what the window runs: the second one starts from
    # the state the first returned, as every later window does.
    engine.run(iter(ring))
    setup_s = time.perf_counter() - t_start
    parts["warmup_s"] = setup_s - parts["data_s"] - parts["start_s"]
    # 0 compiled once the persistent cache is warm: every program is loaded.
    parts["compiled"] = compiles.n - compiles.hits
    parts["cache_hits"] = compiles.hits

    spans: list = []
    tracer = None
    taken = [0]
    if traced:
        from repro import obs

        sink = obs.ListSink()
        obs.configure(sinks=(sink,))
        spans = sink.records
        tracer = SliceTracer(taken, lead=seconds / 3)

    def source():
        deadline = time.perf_counter() + seconds
        while time.perf_counter() < deadline:
            yield ring[taken[0] % RING]
            taken[0] += 1

    n_compiles = compiles.n
    t0 = time.perf_counter()
    if tracer is not None:
        tracer.start()
    outcome = engine.run(source())
    wall = time.perf_counter() - t0
    window_compiles = compiles.n - n_compiles
    if tracer is not None:
        tracer.join()
        from repro import obs

        obs.set_recorder(None)
    device = device_info()
    numbers, hold = check.compare(cfg, traffic, outcome, ring, holdout, seed)
    correct = all(n.ok for n in numbers)

    result = {"correct": correct, "attempted": outcome.windows,
              "failed": 0 if correct else outcome.windows}
    if traced:
        from bench import trace

        tr = trace.load(str(TRACE_DIR))
        ctx = spec.Context(tr, spans, cfg, traffic,
                           peaks.peak(device["kind"]))
        metrics = {}
        for mdef in cell.per_layer:
            value = spec.metric_reader(mdef["name"])(ctx)
            if value is not None:
                metrics[mdef["name"]] = {"value": value, "unit": mdef["unit"]}
        device["busy_s"] = trace.mean_busy_s(tr)
        device["window_s"] = tr.window_ns / 1e9
        result["breakdown"] = {"device_ops": trace.top_ops(tr),
                               "idle_gaps": trace.idle_gaps(tr)}
    else:
        values = {"rows_per_s": outcome.windows * m / wall,
                  "setup_s": setup_s}
        metrics = {e["name"]: {"value": values[e["name"]], "unit": e["unit"]}
                   for e in cell.end_to_end
                   if math.isfinite(values.get(e["name"], math.nan))}
    result["metrics"] = metrics
    result["device"] = device
    # The answer's quality, reported beside the metrics and not bounded:
    # from seed to seed it reads the luck of the program's first seeding
    # (PERF.md, section 7).
    ratio = check.holdout_ratio(outcome, holdout, mix.centres, hold)
    result["window"] = {"seconds": wall, "windows": outcome.windows,
                        "window_rows": m, "compiles": window_compiles,
                        "holdout_obj_ratio": ratio if math.isfinite(ratio)
                        else None, "setup": parts}
    # A number that is not finite (no answer at all) prints as null.
    result["checks"] = {n.name: {"value": n.value if math.isfinite(n.value)
                                 else None, "limit": n.limit}
                        for n in numbers}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload)
    require_accelerator(cell.chips)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    dev = result["device"]
    tag = f"[{dev['platform']} {dev['kind']} x{dev['count']}]"
    print(f"bench: {cell.name} seed={args.seed} {tag} window "
          f"{json.dumps(result['window'])}", file=sys.stderr)
    for name, n in result["checks"].items():
        ok = n["value"] is not None and n["value"] <= n["limit"]
        print(f"check {name}={n['value']!r} limit={n['limit']!r} "
              f"{'ok' if ok else 'FAIL'} {tag}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
