"""The paper's end-to-end driver: HPClust over an infinite synthetic stream.

  PYTHONPATH=src python -m repro.launch.cluster --strategy hybrid \
      --k 10 --sample 2048 --workers 4 --rounds 24 --windows 4
"""
from __future__ import annotations

import argparse
import json
import time

from repro import obs
from repro.core import HPClust, HPClustConfig
from repro.core.hpclust import stream_from_generator
from repro.data import blob_stream
from repro.launch.entry import device_info, enable_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--strategy", default="hybrid",
                    choices=("inner", "competitive", "cooperative", "hybrid",
                             "hybrid2"))
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--dim", type=int, default=10)
    ap.add_argument("--sample", type=int, default=2048)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=8, help="rounds per window")
    ap.add_argument("--windows", type=int, default=4)
    ap.add_argument("--window-size", type=int, default=65536)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint worker state every window (resumable)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the latest checkpoint in --ckpt-dir")
    ap.add_argument("--ckpt-every", type=int, default=1,
                    help="checkpoint every N windows (with --ckpt-dir)")
    ap.add_argument("--sharded", action="store_true",
                    help="run the shard_map SPMD engine over the local "
                         "devices (the production code path at host scale)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a repro.obs JSONL trace to PATH (read with "
                         "`python -m repro.obs summarize PATH`)")
    args = ap.parse_args(argv)

    enable_compile_cache()
    if args.trace:
        obs.configure(jsonl=args.trace)
    try:
        if args.sharded:
            return _main_sharded(args)
        return _main_stream(args)
    finally:
        obs.shutdown()


def _main_stream(args):
    cfg = HPClustConfig(
        k=args.k, sample_size=args.sample, workers=args.workers,
        rounds=args.rounds, strategy=args.strategy,
        groups=2 if args.strategy == "hybrid2" else 1,
    )
    hp = HPClust(cfg, seed=args.seed)
    stream = stream_from_generator(
        blob_stream(args.window_size, n=args.dim, k=args.k, seed=args.seed),
        args.windows,
    )
    t0 = time.time()
    res = hp.fit_stream(
        stream, checkpoint_dir=args.ckpt_dir,
        checkpoint_every=args.ckpt_every, resume=args.resume,
    )
    dt = time.time() - t0
    # evaluate on a fresh holdout window from the SAME stream distribution
    holdout = next(iter(
        blob_stream(200000, n=args.dim, k=args.k, seed=args.seed)
    ))
    full_obj = hp.objective(holdout, res.centroids)
    print(json.dumps({
        "strategy": args.strategy,
        "sample_objective": res.objective,
        "holdout_objective": full_obj,
        "rounds_total": int(res.history.shape[0]),
        "windows": res.stats.windows if res.stats else None,
        "sanitized_rows": res.stats.sanitized_rows if res.stats else None,
        "resumed_at": res.stats.resumed_at if res.stats else None,
        "wall_s": round(dt, 2),
        "device": device_info(),
    }, indent=1))
    return 0


def _main_sharded(args):
    """The production (shard_map) engine over whatever devices exist.

    Workers over the `data` axis, inner (distance) parallelism over `model`.
    With one CPU device this degrades to a 1x1 mesh — same program the
    512-chip dry-run lowers. Runs through the elastic driver, so
    --ckpt-dir/--resume/--ckpt-every behave exactly like the single-host
    path and a device loss mid-stream degrades the mesh instead of killing
    the run (see repro.launch.elastic).
    """
    import numpy as np

    from repro.launch.elastic import run_elastic_sharded

    stream = stream_from_generator(
        blob_stream(args.window_size, n=args.dim, k=args.k, seed=args.seed),
        args.windows,
    )
    t0 = time.time()
    res = run_elastic_sharded(
        stream,
        k=args.k, sample_size=args.sample,
        rounds_per_window=args.rounds, strategy=args.strategy,
        seed=args.seed,
        checkpoint_dir=args.ckpt_dir, resume=args.resume,
        ckpt_every=args.ckpt_every,
    )
    print(json.dumps({
        "strategy": args.strategy, "engine": "shard_map",
        "workers": res.workers,
        "best_sample_objective": res.objective,
        "monotone": bool(
            (np.diff(res.history, axis=0) <= 1e-3).all()
        ) if res.history.size else True,
        "rounds_total": int(res.history.shape[0]),
        "windows": res.windows_done,
        "recoveries": res.recoveries,
        "resumed_at": res.resumed_at,
        "wall_s": round(time.time() - t0, 2),
        "device": device_info(),
    }, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
