import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This proves the distribution config is coherent without hardware: the full
parameter/optimizer/cache pytrees exist only as ShapeDtypeStructs; jit
lowering + GSPMD partitioning + backend compilation run for the production
meshes (16x16 single-pod, 2x16x16 multi-pod). Per cell we record:

  * memory_analysis()  — per-device argument/output/temp bytes (proves fit);
  * cost_analysis()    — HLO FLOPs / bytes accessed for the roofline;
  * collective bytes   — parsed from the post-SPMD HLO text: summed operand
    bytes of all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute (per-device program => per-device bytes).

Artifacts: one JSON per cell under --out (default experiments/dryrun).
benchmarks/roofline.py consumes them. Also supports the paper's own
HPClust production configs (arch "hpclust-prod").

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-0.6b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import functools
import json
import re
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.configs import ARCHS, get_config
from repro.distributed import sharding as shd
from repro.launch import steps as S
from repro.launch.mesh import make_production_mesh
from repro.models import model as M

COLLECTIVE_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\b"
)
SHAPE_RE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")

DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum operand bytes per collective kind from (post-SPMD) HLO text."""
    out: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = COLLECTIVE_RE.search(line)
        if not m or "=" not in line:
            continue
        if "-done" in line:  # the -start op already carried the operands
            continue
        kind = m.group(1)
        # shapes on the line: first (lhs result), rest are operand types.
        shapes = SHAPE_RE.findall(line)
        if len(shapes) < 2:
            continue
        rhs = line.split("=", 1)[1]
        operands = SHAPE_RE.findall(rhs.split("(", 1)[1]) if "(" in rhs else []
        nbytes = sum(_shape_bytes(dt, dims) for dt, dims in operands)
        out[kind] = out.get(kind, 0) + nbytes
    return out


def _mem_dict(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        ma = None
    if ma is None:
        return {"available": False}
    keys = (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "alias_size_in_bytes",
        "generated_code_size_in_bytes",
    )
    d = {"available": True}
    for k in keys:
        v = getattr(ma, k, None)
        if v is not None:
            d[k] = int(v)
    return d


def _analytic_bytes(tree, shardings, mesh) -> int:
    """Per-device bytes of a pytree given its shardings (exact, analytic)."""
    total = 0
    leaves, treedef = jax.tree.flatten(tree)
    shard_leaves = jax.tree.flatten(shardings)[0]
    for leaf, sh in zip(leaves, shard_leaves):
        n = 1
        for d in leaf.shape:
            n *= d
        denom = 1
        if isinstance(sh, NamedSharding):
            for ax in sh.spec:
                if ax is None:
                    continue
                for a in (ax,) if isinstance(ax, str) else ax:
                    denom *= mesh.shape[a]
        total += n * jnp.dtype(leaf.dtype).itemsize // max(denom, 1)
    return total


def build_cell(arch: str, shape: str, mesh, cfg=None):
    """Returns (jitted fn, example abstract args tuple, static meta)."""
    cfg = cfg if cfg is not None else get_config(arch)
    meta = S.SHAPES[shape]
    dp = shd.dp_axes(mesh)
    # Pin the residual stream to DP sharding at every block boundary: the
    # scanned carry/residual stacks otherwise default to replicated.
    M.set_activation_spec(P(dp, None, None) if meta["global_batch"] > 1 else None)
    M.set_cache_spec_fn(None)
    p_shard = shd.param_shardings(cfg, mesh)
    specs = S.input_specs(cfg, shape)
    param_structs = M.param_shapes(cfg)

    def batch_shardings(batch):
        out = {}
        for k, v in batch.items():
            extra = (None,) * (len(v.shape) - 1)
            out[k] = NamedSharding(mesh, P(dp, *extra))
        return out

    if meta["kind"] == "train":
        step = S.make_train_step(cfg)
        opt = step.optimizer
        opt_structs = S.opt_state_structs(cfg, opt)
        pspecs = M.param_specs(cfg, shd.logical_rules(mesh))
        pspecs = {k: shd.dedupe_spec(s) for k, s in pspecs.items()}
        o_specs = opt.state_specs(pspecs)
        o_shard = jax.tree.map(
            lambda s, struct: NamedSharding(
                mesh,
                shd._drop_indivisible(shd.dedupe_spec(s), struct.shape, mesh),
            ),
            o_specs, opt_structs,
            is_leaf=lambda x: isinstance(x, P),
        )
        b_shard = batch_shardings(specs["batch"])
        # The sharding pytrees closed over here are unhashable, so a cache
        # key cannot be formed.
        # analysis: allow JH003 — one lowering per cell
        fn = jax.jit(
            step,
            in_shardings=(p_shard, o_shard, b_shard),
            out_shardings=(p_shard, o_shard, None),
            donate_argnums=(0, 1),
        )
        args = (param_structs, opt_structs, specs["batch"])
        arg_sharding_trees = (p_shard, o_shard, b_shard)
    elif meta["kind"] == "prefill":
        step = S.make_prefill_step(cfg)
        b_shard = batch_shardings(specs["batch"])
        dp_size = 1
        for a in dp:
            dp_size *= mesh.shape[a]
        model_size = mesh.shape["model"]

        def cache_spec(shape, _dp=dp, _dps=dp_size, _ms=model_size):
            # per-layer cache leaves inside the scan: (B, S, ...) — batch
            # over DP, trailing feature dim over model when divisible.
            if len(shape) < 2:
                return None
            axes = [None] * len(shape)
            if shape[0] % _dps == 0:
                axes[0] = _dp
            if len(shape) >= 3 and shape[-1] % _ms == 0 and shape[-1] >= 2 * _ms:
                axes[-1] = "model"
            return P(*axes)

        M.set_cache_spec_fn(cache_spec)
        # analysis: allow JH003 — one lowering per cell (see above)
        fn = jax.jit(step, in_shardings=(p_shard, b_shard))
        args = (param_structs, specs["batch"])
        arg_sharding_trees = (p_shard, b_shard)
    else:
        step = S.make_decode_step(cfg)
        cfg_local = cfg
        seq_par = meta["global_batch"] == 1
        c_shard = shd.cache_sharding(cfg_local, mesh, specs["caches"],
                                     seq_parallel=seq_par)
        t_shard = NamedSharding(mesh, P(dp, None)) if meta["global_batch"] > 1 \
            else NamedSharding(mesh, P())
        # analysis: allow JH003 — one lowering per cell (see above)
        fn = jax.jit(
            step,
            in_shardings=(p_shard, t_shard, NamedSharding(mesh, P()), c_shard),
            donate_argnums=(3,),
        )
        args = (param_structs, specs["tokens"], specs["pos"], specs["caches"])
        arg_sharding_trees = (p_shard, t_shard, None, c_shard)

    return cfg, fn, args, arg_sharding_trees


def _compile_cost(arch: str, shape: str, mesh, cfg_v) -> dict:
    """Compile a (small, unrolled) variant; return cost + collectives."""
    _, fn, args_, _sh = build_cell(arch, shape, mesh, cfg=cfg_v)
    with mesh:
        compiled = fn.lower(*args_).compile()
    ca = compiled.cost_analysis() or {}
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "collectives": collective_bytes(compiled.as_text()),
    }


def calibrate_cell(arch: str, shape: str, *, multi_pod: bool) -> dict:
    """Affine extrapolation of per-segment (and per-microbatch) costs.

    XLA cost analysis counts while bodies ONCE regardless of trip count, so
    scanned models under-report. We compile small *unrolled* variants
    (flat HLO, counted exactly): a base with every segment at n=1 (and
    grad_accum=1), one variant per segment at n=2, and — for training with
    accumulation — an accum=2 variant. FLOPs/bytes/collectives are affine in
    each count, so:

        cost(N_1..N_k, A) = base + sum_s (N_s-1) * Delta_s + (A-1) * Delta_a
    """
    import dataclasses as _dc

    from repro.models import model as _m

    cfg = get_config(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    plan = _m.build_plan(cfg)
    is_train = S.SHAPES[shape]["kind"] == "train"

    def variant(counts, accum=1):
        v = _dc.replace(cfg, plan_override=tuple(counts), unroll=True,
                        grad_accum=accum if is_train else cfg.grad_accum)
        return _compile_cost(arch, shape, mesh, v)

    base_counts = [(s.name, 1) for s in plan]
    base = variant(base_counts)

    def combine(tot, var, scale):
        tot["flops"] += (var["flops"] - base["flops"]) * scale
        tot["bytes"] += (var["bytes"] - base["bytes"]) * scale
        for k in set(var["collectives"]) | set(base["collectives"]):
            d = var["collectives"].get(k, 0) - base["collectives"].get(k, 0)
            tot["collectives"][k] = tot["collectives"].get(k, 0) + d * scale

    total = {
        "flops": base["flops"], "bytes": base["bytes"],
        "collectives": dict(base["collectives"]),
    }
    per_seg = {}
    for s in plan:
        if s.n <= 1:
            continue
        counts = [(x.name, 2 if x.name == s.name else 1) for x in plan]
        var = variant(counts)
        per_seg[s.name] = {"flops": var["flops"] - base["flops"],
                           "bytes": var["bytes"] - base["bytes"]}
        combine(total, var, s.n - 1)
    if is_train and cfg.grad_accum > 1:
        var_a = variant(base_counts, accum=2)
        per_seg["_accum"] = {"flops": var_a["flops"] - base["flops"]}
        combine(total, var_a, cfg.grad_accum - 1)
    total["collectives"] = {k: max(0, int(v)) for k, v in total["collectives"].items()}
    total["collective_bytes_total"] = int(sum(total["collectives"].values()))
    total["per_segment"] = per_seg
    total["plan"] = [(s.name, s.n) for s in plan]
    return total


def run_cell(arch: str, shape: str, *, multi_pod: bool, out_dir: Path,
             hlo_dir: Path | None = None, calibrate: bool = True) -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg, fn, args, arg_shardings = build_cell(arch, shape, mesh)
    rec: dict = {
        "arch": arch, "shape": shape, "mesh": mesh_name,
        "chips": mesh.size, "status": "ok",
    }
    with mesh:
        lowered = fn.lower(*args)
        t_lower = time.time()
        compiled = lowered.compile()
        t_compile = time.time()
    rec["lower_s"] = round(t_lower - t0, 2)
    rec["compile_s"] = round(t_compile - t_lower, 2)
    ca = compiled.cost_analysis() or {}
    rec["cost"] = {
        "flops": float(ca.get("flops", -1.0)),
        "bytes_accessed": float(ca.get("bytes accessed", -1.0)),
        "transcendentals": float(ca.get("transcendentals", -1.0)),
    }
    rec["memory_analysis"] = _mem_dict(compiled)
    # analytic per-device sizes for the big operands
    mesh_obj = mesh
    rec["arg_bytes_per_device"] = int(
        sum(
            _analytic_bytes(a, s if s is not None else jax.tree.map(
                lambda _: NamedSharding(mesh_obj, P()), a), mesh_obj)
            for a, s in zip(args, arg_shardings)
        )
    )
    hlo = compiled.as_text()
    rec["collectives"] = collective_bytes(hlo)
    rec["collective_bytes_total"] = int(sum(rec["collectives"].values()))
    rec["n_params"] = int(
        sum(int(jnp.prod(jnp.array(v.shape))) for v in M.param_shapes(cfg).values())
    )
    if hlo_dir is not None:
        hlo_dir.mkdir(parents=True, exist_ok=True)
        (hlo_dir / f"{arch}__{shape}__{mesh_name}.hlo.txt").write_text(hlo)
    # Roofline calibration is a single-pod deliverable (the multi-pod pass
    # only proves the `pod` axis shards); skip the extra compiles there.
    if calibrate and not multi_pod:
        try:
            rec["cost_calibrated"] = calibrate_cell(arch, shape, multi_pod=multi_pod)
        except Exception as e:  # noqa: BLE001
            rec["cost_calibrated"] = {"error": repr(e)}
    out_dir.mkdir(parents=True, exist_ok=True)
    path = out_dir / f"{arch}__{shape}__{mesh_name}.json"
    path.write_text(json.dumps(rec, indent=1))
    return rec


@functools.lru_cache(maxsize=None)
def _jit_hpclust_runner(mesh, cfg, pod_axis):
    """One compiled SPMD runner per (mesh, cfg, pod_axis) cell — both the
    faithful and optimized hpclust-prod cells re-lower through this cache."""
    from repro.core.sharded import build_sharded_runner

    fn, in_sh, out_sh = build_sharded_runner(mesh, cfg, pod_axis=pod_axis)
    return jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)


def run_hpclust_cell(*, multi_pod: bool, out_dir: Path,
                     optimized: bool = False) -> dict:
    """Dry-run the paper's own workload on the production mesh.

    optimized=False -> paper-faithful: f32 reservoir, hybrid (T1/T2).
    optimized=True  -> beyond-paper: bf16 reservoir (distance math still
    accumulates in f32), hierarchical hybrid2 on multi-pod, one fused stats
    pass per round (kmeans_iters trimmed to the observed convergence
    budget). Recorded separately per the assignment.
    """
    from repro.core.sharded import state_shapes
    from repro.core.strategies import HPClustConfig

    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    mesh = make_production_mesh(multi_pod=multi_pod)
    workers = mesh.size // mesh.shape["model"]
    strategy = ("hybrid2" if multi_pod else "hybrid")
    cfg = HPClustConfig(
        k=25, sample_size=1 << 17, workers=workers, rounds=8,
        strategy=strategy,
        groups=2 if multi_pod else 1, fixed_schedule=True,
        kmeans_iters=24 if optimized else 32, impl="ref",
    )
    d, m_shard = 768, 1 << 20  # CORD-19-like dims; 1M-row reservoir/worker
    jfn = _jit_hpclust_runner(mesh, cfg, "pod" if multi_pod else None)
    state = state_shapes(cfg, d)
    res_dtype = jnp.bfloat16 if optimized else jnp.float32
    reservoir = jax.ShapeDtypeStruct((workers, m_shard, d), res_dtype)
    t0 = time.time()
    with mesh:
        lowered = jfn.lower(state, reservoir)
        compiled = lowered.compile()
    hlo = compiled.as_text()
    ca = compiled.cost_analysis() or {}
    name = "hpclust-prod-opt" if optimized else "hpclust-prod"
    rec = {
        "arch": name, "shape": f"k25_s131072_w{workers}",
        "mesh": mesh_name, "chips": mesh.size, "status": "ok",
        "strategy": strategy, "reservoir_dtype": str(res_dtype.__name__),
        "lower_compile_s": round(time.time() - t0, 2),
        "cost": {"flops": float(ca.get("flops", -1.0)),
                 "bytes_accessed": float(ca.get("bytes accessed", -1.0))},
        "memory_analysis": _mem_dict(compiled),
        "collectives": collective_bytes(hlo),
    }
    rec["collective_bytes_total"] = int(sum(rec["collectives"].values()))
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / f"{name}__{mesh_name}.json").write_text(json.dumps(rec, indent=1))
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="arch id or 'hpclust-prod'")
    ap.add_argument("--shape", default=None, choices=list(S.SHAPES) + [None])
    ap.add_argument("--mesh", default="single", choices=("single", "multi", "both"))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--dump-hlo", action="store_true")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a repro.obs JSONL trace to PATH (read with "
                         "`python -m repro.obs summarize PATH`)")
    args = ap.parse_args(argv)

    if args.trace:
        obs.configure(jsonl=args.trace)
    try:
        return _run_cells(args)
    finally:
        obs.shutdown()


def _run_cells(args):
    out_dir = Path(args.out)
    hlo_dir = Path("experiments/hlo") if args.dump_hlo else None
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    cells = []
    archs = ARCHS if (args.all or args.arch is None) else [args.arch]
    shapes = list(S.SHAPES) if args.shape is None else [args.shape]
    for arch in archs:
        if arch == "hpclust-prod":
            for mp in meshes:
                cells.append(("hpclust-prod", None, mp))
            continue
        cfg = get_config(arch)
        for shape in shapes:
            if not S.cell_is_applicable(cfg, shape):
                print(f"SKIP {arch} x {shape}: long-context N/A "
                      f"(full attention; DESIGN.md SS5)")
                continue
            for mp in meshes:
                cells.append((arch, shape, mp))

    failures = 0
    for arch, shape, mp in cells:
        name = f"{arch} x {shape or '-'} x {'multi' if mp else 'single'}"
        try:
            with obs.span("dryrun.cell", arch=arch, shape=shape,
                          mesh="multi" if mp else "single"):
                if arch == "hpclust-prod":
                    rec = run_hpclust_cell(multi_pod=mp, out_dir=out_dir)
                    run_hpclust_cell(multi_pod=mp, out_dir=out_dir,
                                     optimized=True)
                elif arch == "hpclust-prod-opt":
                    rec = run_hpclust_cell(multi_pod=mp, out_dir=out_dir,
                                           optimized=True)
                else:
                    rec = run_cell(arch, shape, multi_pod=mp, out_dir=out_dir,
                                   hlo_dir=hlo_dir)
            obs.inc("dryrun.cells_ok")
            print(f"OK   {name}: flops={rec['cost']['flops']:.3e} "
                  f"coll={rec['collective_bytes_total']:.3e}B "
                  f"compile={rec.get('compile_s', rec.get('lower_compile_s'))}s",
                  flush=True)
        except Exception as e:  # noqa: BLE001 — record and continue the sweep
            failures += 1
            obs.inc("dryrun.cells_failed")
            obs.event("dryrun.cell_failed", cell=name,
                      error=type(e).__name__)
            print(f"FAIL {name}: {type(e).__name__}: {e}", flush=True)
            traceback.print_exc(limit=3)
            out_dir.mkdir(parents=True, exist_ok=True)
            mesh_name = "pod2x16x16" if mp else "pod16x16"
            (out_dir / f"{arch}__{shape}__{mesh_name}.json").write_text(
                json.dumps({"arch": arch, "shape": shape, "mesh": mesh_name,
                            "status": "fail", "error": repr(e)}, indent=1))
    print(f"dry-run complete: {len(cells) - failures}/{len(cells)} cells OK")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
