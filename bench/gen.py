"""Seeded stand-in data: blob_stream's mixture, in float32, made on the device.

The mixture is ``repro.data.blob_stream``'s, copied here so that the
yardstick does not move with the program: k centres drawn from
U(-box, box)^d, one sigma per centre from U(0, sigma_max), each row a centre
plus sigma times standard normal noise, and a fixed share of rows (exactly
``int(rows * noise_frac)`` per block) replaced by U(-noise_box, noise_box)^d.

The mixture itself (centres and sigmas) belongs to the configuration: it is
drawn from the configuration's ``mixture.seed``, as a dataset is fixed. The
rows are drawn from the run's ``--seed``, on the device, and copied once to
the host.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

BLOCK_ROWS = 1 << 18


class Mixture(NamedTuple):
    centres: np.ndarray  # (k, d) float32
    sigmas: np.ndarray   # (k,) float32
    noise_frac: float
    noise_box: float


def run_key(seed: int):
    """A PRNG key that uses all 64 bits of ``seed`` (``PRNGKey`` keeps 32)."""
    if not 0 <= seed < 1 << 64:
        raise ValueError(f"seed must lie in [0, 2**64), got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def mixture(cfg: dict) -> Mixture:
    """The configuration's mixture, drawn from its ``mixture.seed``."""
    mx = cfg["mixture"]
    k, d = cfg["k"], cfg["d"]
    kc, ks = jax.random.split(jax.random.PRNGKey(mx["seed"]))
    box, smax = mx["box"], mx["sigma_max"]
    centres = jax.random.uniform(kc, (k, d), minval=-box, maxval=box)
    sigmas = jax.random.uniform(ks, (k,), minval=0.0, maxval=smax)
    return Mixture(np.asarray(centres, np.float32),
                   np.asarray(sigmas, np.float32),
                   float(mx["noise_frac"]), float(mx["noise_box"]))


@functools.partial(jax.jit, static_argnames=(
    "rows", "block", "n_noise", "noise_box"))
def _rows(key, centres, sigmas, *, rows: int, block: int, n_noise: int,
          noise_box: float):
    k, d = centres.shape
    key, k_noise = jax.random.split(key)
    # Exactly n_noise noise rows, picked as blob_stream picks them: without
    # replacement, uniformly over the whole array.
    pick = jax.random.permutation(k_noise, rows)[:n_noise]
    noisy = jnp.zeros((rows,), jnp.bool_).at[pick].set(True)

    def one(args):
        b, nz = args
        kc, kn, ku = jax.random.split(jax.random.fold_in(key, b), 3)
        comp = jax.random.randint(kc, (block,), 0, k)
        x = centres[comp] + sigmas[comp, None] * jax.random.normal(
            kn, (block, d), jnp.float32)
        u = jax.random.uniform(ku, (block, d), minval=-noise_box,
                               maxval=noise_box)
        return jnp.where(nz[:, None], u, x)

    blocks = jax.lax.map(one, (jnp.arange(rows // block),
                               noisy.reshape(rows // block, block)))
    return blocks.reshape(rows, d)


def rows(key, mix: Mixture, n: int, *, block: int = BLOCK_ROWS) -> np.ndarray:
    """``n`` rows of the mixture as a host float32 array: made on the
    default device by one program, block by block to bound its memory, and
    copied to the host once. Exactly ``int(n * noise_frac)`` are noise."""
    block = min(block, n)
    if n % block:
        raise ValueError(f"{n} rows do not split into blocks of {block}")
    out = _rows(key, jax.device_put(mix.centres), jax.device_put(mix.sigmas),
                rows=n, block=block, n_noise=int(n * mix.noise_frac),
                noise_box=mix.noise_box)
    return np.asarray(out)
