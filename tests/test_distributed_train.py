"""Multi-device SPMD integration: sharded training + sharded clustering
actually RUN (not just compile) on 8 forced host devices, and checkpoints
round-trip across device counts (elastic restart)."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src")}

TRAIN_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.distributed import sharding as shd
from repro.launch import steps as S
from repro.launch.mesh import make_host_mesh
from repro.models import model as M

# small real mesh: 4-way DP x 2-way TP. Built with Auto axes: a bare
# jax.make_mesh defaults to Explicit, which the model's sharding rules reject.
mesh = make_host_mesh((4, 2))
cfg = get_config("qwen3-0.6b", smoke=True)

p_shard = shd.param_shardings(cfg, mesh)
step = S.make_train_step(cfg, grad_accum=1)
opt = step.optimizer

params_host = M.init_params(cfg, jax.random.PRNGKey(0))
with mesh:
    params = {k: jax.device_put(v, p_shard[k]) for k, v in params_host.items()}
    opt_state = opt.init(params)
    b_shard = NamedSharding(mesh, P(("data",), None))
    M.set_activation_spec(P(("data",), None, None))
    jstep = jax.jit(step, donate_argnums=(0, 1))
    rng = np.random.default_rng(0)
    losses = []
    for i in range(6):
        batch = {"tokens": jax.device_put(
            jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32))), b_shard)}
        params, opt_state, m = jstep(params, opt_state, batch)
        losses.append(float(m["loss"]))

# params remain sharded as requested
sharded_ok = all(
    params[k].sharding == p_shard[k] for k in list(params)[:10]
)
print(json.dumps({
    "losses": losses,
    "finite": all(np.isfinite(losses)),
    "decreasing": losses[-1] < losses[0] + 0.5,
    "sharded_ok": bool(sharded_ok),
    "n_devices": len(jax.devices()),
}))
"""


def test_sharded_training_runs_on_8_devices():
    out = subprocess.run(
        [sys.executable, "-c", TRAIN_SCRIPT],
        capture_output=True, text=True, env=ENV, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["n_devices"] == 8
    assert rec["finite"], rec
    assert rec["decreasing"], rec
    assert rec["sharded_ok"]


ELASTIC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d"
import json, sys
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import CheckpointManager
from repro.launch.mesh import make_host_mesh

mgr = CheckpointManager("%s")
tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
if "%s" == "save":
    mesh = make_host_mesh((len(jax.devices()),), ("data",))
    sh = NamedSharding(mesh, P("data", None))
    mgr.save(3, {"w": jax.device_put(tree["w"], sh)})
    print(json.dumps({"saved": True}))
else:
    mesh = make_host_mesh((len(jax.devices()),), ("data",))
    sh = {"w": NamedSharding(mesh, P("data", None))}
    step, out = mgr.restore(tree, shardings=sh)
    print(json.dumps({
        "step": step,
        "match": bool(np.allclose(np.asarray(out["w"]), np.asarray(tree["w"]))),
        "devices": len(jax.devices()),
    }))
"""


def test_elastic_restart_across_device_counts(tmp_path):
    """Save sharded over 8 devices, restore sharded over 2 — the elastic
    restart path end to end."""
    d = str(tmp_path / "ck")
    r1 = subprocess.run(
        [sys.executable, "-c", ELASTIC_SCRIPT % (8, d, "save")],
        capture_output=True, text=True, env=ENV, timeout=600,
    )
    assert r1.returncode == 0, r1.stderr[-2000:]
    r2 = subprocess.run(
        [sys.executable, "-c", ELASTIC_SCRIPT % (2, d, "load")],
        capture_output=True, text=True, env=ENV, timeout=600,
    )
    assert r2.returncode == 0, r2.stderr[-2000:]
    rec = json.loads(r2.stdout.strip().splitlines()[-1])
    assert rec == {"step": 3, "match": True, "devices": 2}
