"""Compiles for a described TPU v5e 2x2 topology, with no chip attached.

The TPU compiler refuses what interpret mode accepts: unaligned slices,
more VMEM than a kernel may use, a program that does not fit the device.
These tests compile, at real widths, the Pallas kernels, the whole round
program ``fit_stream`` runs, and the sharded runner on a 2x2 mesh. Nothing
runs, so they say nothing about results or times.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding

from repro.core import hpclust, sharded, strategies
from repro.core.strategies import HPClustConfig
from repro.kernels.assign import assign_pallas
from repro.kernels.lloyd import lloyd_pass_pallas
from repro.kernels.update import cluster_sums_pallas
from repro.launch.mesh import _make_mesh

# The hpclust-prod shape chip_smoke.py runs: k=25 (padded to one 128-lane
# tile), d=768, s=16384, 8 workers, 2^20-row windows.
K, KP, D, S, W, M = 25, 128, 768, 16384, 8, 1 << 20
HBM_BYTES = 16 * 1024**3  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # Entries compiled for a described chip cannot be read back without one.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", ["f32", "bf16"])
def test_assign_kernel_compiles(one_chip, dtype):
    x = _sds((S, D), jnp.float32, one_chip)
    c = _sds((KP, D), jnp.float32, one_chip)
    compiled = jax.jit(
        lambda x, c: assign_pallas(x, c, k_valid=K, compute_dtype=dtype)
    ).lower(x, c).compile()
    assert _has_kernel(compiled)


def test_cluster_sums_kernel_compiles(one_chip):
    x = _sds((S, D), jnp.float32, one_chip)
    idx = _sds((S,), jnp.int32, one_chip)
    compiled = jax.jit(
        lambda x, idx: cluster_sums_pallas(x, idx, K)).lower(x, idx).compile()
    assert _has_kernel(compiled)


def test_lloyd_pass_kernel_compiles(one_chip):
    x = _sds((S, D), jnp.float32, one_chip)
    c = _sds((KP, D), jnp.float32, one_chip)
    compiled = jax.jit(
        lambda x, c: lloyd_pass_pallas(x, c, k_valid=K, s_valid=S)
    ).lower(x, c).compile()
    assert _has_kernel(compiled)


def test_round_program_compiles_with_kernels_and_fits(one_chip):
    cfg = HPClustConfig(k=K, sample_size=S, workers=W, rounds=8,
                        strategy="hybrid", impl="pallas")
    state = jax.tree.map(
        lambda a: _sds(a.shape, a.dtype, one_chip),
        jax.eval_shape(
            lambda: strategies.init_state(jax.random.PRNGKey(0), cfg, D)))
    data = _sds((M, D), jnp.float32, one_chip)
    compiled = hpclust._jit_run_from_state_donated.lower(
        state, data, cfg=cfg).compile()
    assert _has_kernel(compiled)
    # The reseed stays a conditional the chip skips, not a select of both.
    assert " conditional(" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


def test_sharded_runner_compiles_on_2x2_mesh(topo):
    mesh = _make_mesh((2, 2), ("data", "model"), devices=topo.devices)
    cfg = HPClustConfig(k=K, sample_size=S, workers=2, rounds=8,
                        strategy="hybrid", fixed_schedule=True,
                        kmeans_iters=32)
    fn, in_sh, out_sh = sharded.build_sharded_runner(mesh, cfg)
    state = jax.tree.map(
        lambda a, sh: _sds(a.shape, a.dtype, sh),
        sharded.state_shapes(cfg, D), in_sh[0])
    reservoir = _sds((2, M, D), jnp.float32, in_sh[1])
    compiled = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh).lower(
        state, reservoir).compile()
    text = compiled.as_text()
    assert "all-reduce" in text
    # Each chip holds a quarter of the reservoir.
    assert isinstance(in_sh[1], NamedSharding)
    shard = in_sh[1].shard_shape((2, M, D))
    assert np.prod(shard) * 4 == 2 * M * D


def test_topology_has_four_chips(topo):
    assert len(topo.devices) == 4
