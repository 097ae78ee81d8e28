"""Distributed (shard_map) HPClust + small-mesh dry-run checks.

These spawn subprocesses where needed to control the forced device count;
in-process tests use a (1,1) mesh over the single CPU device.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src")}

SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp, numpy as np
from repro.core.strategies import HPClustConfig
from repro.core import sharded
from repro.launch.mesh import make_host_mesh

mesh = make_host_mesh((4, 2))
cfg = HPClustConfig(k=5, sample_size=64, workers=4, rounds=6,
                    strategy="%s", fixed_schedule=True, kmeans_iters=16,
                    groups=2)
rng = np.random.default_rng(0)
centers = rng.uniform(-10, 10, size=(5, 8))
x = np.concatenate([c + rng.normal(scale=0.5, size=(500, 8)) for c in centers]).astype(np.float32)
rng.shuffle(x)
res = np.broadcast_to(x, (4, 2500, 8)).copy()
fn, in_sh, out_sh = sharded.build_sharded_runner(mesh, cfg)
state = sharded.init_sharded_state(cfg, 8, seed=0)
jfn = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
st, objs = jfn(state, jnp.asarray(res))
objs = np.asarray(objs)
print(json.dumps({
    "monotone": bool((np.diff(objs, axis=0) <= 1e-3).all()),
    "best": float(np.min(np.asarray(st.best_obj))),
    "finite": bool(np.isfinite(objs).all()),
    "rounds_done": int(np.asarray(st.rounds_done)),
}))
"""


@pytest.mark.parametrize("strategy", ["competitive", "cooperative", "hybrid"])
def test_sharded_runner_on_8_devices(strategy):
    out = subprocess.run(
        [sys.executable, "-c", SHARDED_SCRIPT % strategy],
        capture_output=True, text=True, env=ENV, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["finite"]
    assert rec["monotone"]
    assert rec["rounds_done"] == 6
    # blobs: optimal sample objective ~ 64 points * d * sigma^2 = 128
    assert rec["best"] < 500.0, rec


MULTIPOD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp, numpy as np
from repro.core.strategies import HPClustConfig
from repro.core import sharded
from repro.launch.mesh import make_host_mesh

mesh = make_host_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = HPClustConfig(k=4, sample_size=32, workers=4, rounds=6,
                    strategy="hybrid2", fixed_schedule=True, kmeans_iters=8,
                    groups=2, sync_every=2)
rng = np.random.default_rng(0)
x = rng.normal(size=(1000, 6)).astype(np.float32)
res = np.broadcast_to(x, (4, 1000, 6)).copy()
fn, in_sh, out_sh = sharded.build_sharded_runner(mesh, cfg, pod_axis="pod")
state = sharded.init_sharded_state(cfg, 6, seed=0)
jfn = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
st, objs = jfn(state, jnp.asarray(res))
print(json.dumps({"finite": bool(np.isfinite(np.asarray(objs)).all()),
                  "monotone": bool((np.diff(np.asarray(objs), axis=0) <= 1e-3).all())}))
"""


def test_hybrid2_multipod_mesh():
    out = subprocess.run(
        [sys.executable, "-c", MULTIPOD_SCRIPT],
        capture_output=True, text=True, env=ENV, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["finite"] and rec["monotone"]


def test_dryrun_cell_compiles_on_host_mesh():
    """Full-size qwen3-0.6b train cell lowers+compiles on a (1,1) mesh —
    the in-process analogue of the 512-device dry-run."""
    import jax

    from repro.launch.dryrun import build_cell
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh((1, 1))
    cfg, fn, args, _ = build_cell("qwen3-0.6b", "train_4k", mesh)
    with mesh:
        compiled = fn.lower(*args).compile()
    ca = compiled.cost_analysis() or {}
    assert ca.get("flops", 0) > 1e12


def test_collective_parser():
    from repro.launch.dryrun import collective_bytes

    hlo = """
  %ar = f32[128,256]{1,0} all-reduce(f32[128,256]{1,0} %x), replica_groups={}
  %ag.1 = bf16[64]{0} all-gather(bf16[32]{0} %y), dimensions={0}
  %noise = f32[2,2]{1,0} add(f32[2,2]{1,0} %a, f32[2,2]{1,0} %b)
"""
    out = collective_bytes(hlo)
    assert out["all-reduce"] == 128 * 256 * 4
    assert out["all-gather"] == 32 * 2
    assert "add" not in out
