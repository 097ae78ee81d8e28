"""Helpers for the benchmark's CPU rehearsals: a throwaway copy of the
benchmark with a tiny cell, and a way to run its entries there with the
accelerator check steered to the CPU."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

# d=32 keeps the mixture's noise rows far from every centre, as at the
# published widths; interpret mode runs the Pallas kernels on the CPU. At
# this size a sample holds ~100 noise rows, so the incumbent's per-row
# objective strays up to ~17% from the holdout's (at the published sizes
# ~5%), and the planted faults read 0.52 or more (CPU readings over six
# seeds): the limit sits between.
TINY = {"d": 32, "k": 4, "workers": 2, "sample_size": 2048,
        "window_rows": 8192, "holdout_rows": 2048, "impl": "interpret",
        "kmeans_iters": 30}
TINY_LIMITS = {"objective_gap": 1e-5, "answer_gap": 0, "incumbent_gap": 0.3,
               "lloyd_gain": 0.05, "monotone_violations": 0}

# Run an entry with the TPU check and the peak table steered to the CPU.
STEER = """
import sys
sys.path.insert(0, {root!r})
from bench import peaks, run
run.require_accelerator = lambda chips: None
_peak = peaks.peak
peaks.peak = lambda kind, *a: (peaks.Peak(1e12, 1e11) if kind == "cpu"
                               else _peak(kind, *a))
from bench import {entry} as entry
raise SystemExit(entry.main({argv!r}))
"""


def copy_bench(dst: Path, *, with_src: bool = True) -> Path:
    """The files the benchmark's checkout holds: BENCHMARK.json, bench/, and
    the program under src/."""
    shutil.copytree(ROOT / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    if with_src:
        shutil.copytree(ROOT / "src", dst / "src",
                        ignore=shutil.ignore_patterns("__pycache__"))
    return dst


def add_tiny_cell(root: Path, base: str, name: str = "tiny",
                  traffic: str = "search", **overrides) -> str:
    """Add a tiny configuration cut from ``base`` and a cell over it, by a
    new file and new BENCHMARK.json entries only. Returns the cell name."""
    with open(root / "bench" / "configs" / f"{base}.json") as f:
        cfg = json.load(f)
    cfg.update(TINY, name=name, limits=dict(TINY_LIMITS), **overrides)
    with open(root / "bench" / "configs" / f"{name}.json", "w") as f:
        json.dump(cfg, f)
    with open(root / "BENCHMARK.json") as f:
        spec = json.load(f)
    cell = f"{name}-{traffic}"
    spec["configs"].append({"name": name, "source": "test", "reduced": [],
                            "file": f"bench/configs/{name}.json",
                            "why": "tiny rehearsal"})
    spec["workloads"].append({"name": cell, "config": name, "chips": 1,
                              "traffic": traffic, "why": "tiny rehearsal"})
    for m in spec["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(cell)
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(spec, f)
    return cell


def run_entry(root: Path, entry: str, argv: list, *, steer: bool = True,
              env: dict | None = None, timeout: float = 240.0
              ) -> subprocess.CompletedProcess:
    """Run ``bench/<entry>.py`` from ``root`` in a fresh CPU process."""
    full_env = {**os.environ, "JAX_PLATFORMS": "cpu",
                "JAX_COMPILATION_CACHE_DIR": str(root / ".jax_cache"),
                **(env or {})}
    full_env.pop("PYTHONPATH", None)
    if steer:
        cmd = [sys.executable, "-c",
               STEER.format(root=str(root), entry=entry, argv=argv)]
    else:
        cmd = [sys.executable, str(root / "bench" / f"{entry}.py"), *argv]
    return subprocess.run(cmd, cwd=root, env=full_env, capture_output=True,
                          text=True, timeout=timeout)


def last_json(stdout: str) -> dict:
    return json.loads(stdout.strip().splitlines()[-1])
