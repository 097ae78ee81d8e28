#!/usr/bin/env python3
"""Readings that set the limits of ``correct``: sound runs, the control and
planted faults, for one cell, in one process.

    python bench/control.py --workload cord19-search --seconds 4 \
        --seeds 1,2,3,4,5,6,7,8,9,10,11,12 --variants sound,bf16_path,...

Each variant patches the program as the run starts and is undone after it;
compiled programs are dropped between variants, so each traces afresh. One
JSON line per (variant, seed) with every compared number, then one summary
line per number: the largest sound reading and each variant's smallest.

Variants:
  sound             the program as it is.
  bf16_path         the program's own lower-precision path,
                    ``REPRO_COMPUTE_DTYPE=bf16`` (bf16 inputs to the
                    distance kernel).
  reference_high    the reference put in the program's place: jnp
                    distances and sums at ``Precision.HIGH`` (three bf16
                    passes), one step below the configuration's
                    ``HIGHEST``; swapped in before the round program is
                    traced, so the timed program runs it.
  state_unchanged   the round program returns the state it was given.
  half_batch        every Lloyd step sees half of its sample rows.
  answer_altered    the answer's first centroid is overwritten by its
                    second where the best incumbent is picked.

Off a TPU it exits with 2, as ``bench/run.py`` does.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import run, spec  # noqa: E402


@contextlib.contextmanager
def _setattr(obj, name: str, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


@contextlib.contextmanager
def _env(name: str, value: str):
    old = os.environ.get(name)
    os.environ[name] = value
    try:
        yield
    finally:
        if old is None:
            os.environ.pop(name, None)
        else:
            os.environ[name] = old


def _high_assign(x, c, *, impl=None, compute_dtype=None):
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGH
    d2 = (jnp.sum(x * x, axis=1)[:, None]
          - 2.0 * jnp.dot(x, c.T, precision=hi)
          + jnp.sum(c * c, axis=1)[None, :])
    d2 = jnp.maximum(d2, 0.0)
    return jnp.argmin(d2, axis=1).astype(jnp.int32), jnp.min(d2, axis=1)


def _high_sums(x, idx, k, *, impl=None):
    import jax
    import jax.numpy as jnp

    onehot = (idx[:, None] == jnp.arange(k)[None, :]).astype(jnp.float32)
    sums = jnp.dot(onehot.T, x, precision=jax.lax.Precision.HIGH)
    return sums, jnp.sum(onehot, axis=0)


def _state_unchanged():
    import jax.numpy as jnp

    from repro.core import hpclust

    orig = hpclust._jit_run_from_state  # never donates its input

    def unchanged(state, data, *, cfg):
        _, metrics = orig(state, data, cfg=cfg)
        return state, metrics._replace(best_obj=jnp.broadcast_to(
            state.best_obj, metrics.best_obj.shape))

    return _multi((hpclust, "_jit_run_from_state", unchanged),
                  (hpclust, "_jit_run_from_state_donated", unchanged))


def _half_batch():
    from repro.core import kmeans

    orig = kmeans.lloyd_iteration

    def half(x, c, *, impl=None):
        return orig(x[: x.shape[0] // 2], c, impl=impl)

    return _multi((kmeans, "lloyd_iteration", half))


def _answer_altered():
    from repro.core import strategies

    orig = strategies.best_of

    def altered(state):
        c, obj = orig(state)
        return c.at[0].set(c[1]), obj

    return _multi((strategies, "best_of", altered))


@contextlib.contextmanager
def _multi(*patches):
    with contextlib.ExitStack() as stack:
        for obj, name, value in patches:
            stack.enter_context(_setattr(obj, name, value))
        yield


def variant(name: str):
    """A context manager that puts the variant in place."""
    if name == "sound":
        return contextlib.nullcontext()
    if name == "bf16_path":
        return _env("REPRO_COMPUTE_DTYPE", "bf16")
    if name == "reference_high":
        from repro.kernels import ops

        return _multi((ops, "assign_clusters", _high_assign),
                      (ops, "cluster_sums", _high_sums))
    if name == "state_unchanged":
        return _state_unchanged()
    if name == "half_batch":
        return _half_batch()
    if name == "answer_altered":
        return _answer_altered()
    raise ValueError(f"unknown variant {name!r}")


def readings(cell: spec.Cell, seeds: list, variants: dict, seconds: float,
             compiles=None) -> list:
    """One record per (variant, seed): the compared numbers, or the error a
    variant that crashed raised (a crash is a failure, and sets no
    reading)."""
    import jax

    out = []
    for name, vseeds in variants.items():
        jax.clear_caches()
        for seed in vseeds:
            t0 = time.perf_counter()
            rec = {"variant": name, "seed": seed}
            try:
                with variant(name):
                    res = run.run_cell(cell, seed, seconds, False,
                                       t_start=t0, compiles=compiles)
                # null marks a number with no finite reading: no answer
                rec.update(correct=res["correct"], checks={
                    k: (math.inf if v["value"] is None else v["value"])
                    for k, v in res["checks"].items()})
            except Exception as e:  # noqa: BLE001 — recorded as a failure
                rec.update(correct=False, error=f"{type(e).__name__}: {e}")
            rec["seconds"] = time.perf_counter() - t0
            print(json.dumps(rec), flush=True)
            out.append(rec)
        jax.clear_caches()
    return out


def summary(recs: list) -> dict:
    """Per number: the largest sound reading and each other variant's
    smallest."""
    out: dict = {}
    for r in recs:
        for num, v in r.get("checks", {}).items():
            row = out.setdefault(num, {})
            if r["variant"] == "sound":
                row["sound_max"] = max(row.get("sound_max", v), v)
            else:
                key = f"{r['variant']}_min"
                row[key] = min(row.get(key, v), v)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds of the sound runs")
    ap.add_argument("--variants", default="sound,bf16_path",
                    help="comma-separated variants; all but sound run on "
                         "the first --fault-seeds seeds")
    ap.add_argument("--fault-seeds", type=int, default=3)
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload)
    run.require_accelerator(cell.chips)
    seeds = [int(s) for s in args.seeds.split(",")]
    plan = {v: (seeds if v == "sound" else seeds[:args.fault_seeds])
            for v in args.variants.split(",")}
    recs = readings(cell, seeds, plan, args.seconds,
                    compiles=run.CompileCounter())
    print(json.dumps({"summary": summary(recs), "workload": cell.name,
                      "device": run.device_info()}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
