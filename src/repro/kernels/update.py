"""Cluster-sum Pallas TPU kernel: one-hot(assignment)^T @ X on the MXU.

The centroid-update half of a Lloyd iteration needs, per cluster j,
``sum_{i: a_i = j} x_i`` and ``|{i: a_i = j}|``. A scatter-add is the GPU
idiom; TPUs have no fast scatter, but the same quantity is a matmul against
the one-hot assignment matrix — which the MXU eats. We build the one-hot
tile on the fly in VMEM (an iota==idx compare), so the (s, k) one-hot matrix
never exists in HBM either.

Grid: (k/bk, d/bd, s/bs), s innermost, so each (bk, bd) output block stays
resident in VMEM while all point tiles stream through it. Counts are
accumulated only on the d==0 slice of the grid (they do not depend on d).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_S = 512
DEFAULT_BLOCK_K = 128
DEFAULT_BLOCK_D = 256


def _update_kernel(
    idx_ref,    # (bs, 1)  int32 assignments
    x_ref,      # (bs, bd) f32 point tile
    sums_ref,   # out (bk, bd) f32
    counts_ref, # out (bk, 1)  f32
    *,
    bk: int,
):
    ki = pl.program_id(0)
    di = pl.program_id(1)
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        sums_ref[...] = jnp.zeros_like(sums_ref)

        @pl.when(di == 0)
        def _init_counts():
            counts_ref[...] = jnp.zeros_like(counts_ref)

    ids = idx_ref[...]  # (bs, 1)
    # Global centroid ids covered by this k-tile, as a (1, bk) row.
    kk = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    onehot = (ids == kk).astype(jnp.float32)  # (bs, bk)

    # (bk, bs) x (bs, bd) on the MXU, in f32: Mosaic's default for an f32
    # product is one bf16 pass, which would round x.
    sums_ref[...] += jax.lax.dot_general(
        onehot,
        x_ref[...],
        dimension_numbers=(((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )

    @pl.when(di == 0)
    def _counts():
        counts_ref[...] += jnp.sum(onehot, axis=0)[:, None]


@functools.partial(
    jax.jit,
    static_argnames=("k", "block_s", "block_k", "block_d", "interpret"),
)
def cluster_sums_pallas(
    x: jax.Array,
    idx: jax.Array,
    k: int,
    *,
    block_s: int = DEFAULT_BLOCK_S,
    block_k: int = DEFAULT_BLOCK_K,
    block_d: int = DEFAULT_BLOCK_D,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Per-cluster sums/counts. x: (s, d) padded, idx: (s,) int32 in [0, k_pad).

    Padding rows must carry an out-of-range assignment (ops.py uses ``k_pad``)
    so they fall outside every one-hot tile and contribute nothing.
    """
    s, d = x.shape
    assert idx.shape == (s,), (idx.shape, s)
    bs, bd = min(block_s, s), min(block_d, d)
    # K pads up to the block (kp >= bk always), unlike s/d where the block
    # shrinks to the data: out-of-range padding assignments need kp > k.
    bk = block_k
    kp = k + (-k) % bk
    assert s % bs == 0 and d % bd == 0 and kp % bk == 0, (s, d, kp, bs, bd, bk)

    sums, counts = pl.pallas_call(
        functools.partial(_update_kernel, bk=bk),
        grid=(kp // bk, d // bd, s // bs),
        in_specs=[
            pl.BlockSpec((bs, 1), lambda ki, di, si: (si, 0)),
            pl.BlockSpec((bs, bd), lambda ki, di, si: (si, di)),
        ],
        out_specs=[
            pl.BlockSpec((bk, bd), lambda ki, di, si: (ki, di)),
            pl.BlockSpec((bk, 1), lambda ki, di, si: (ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((kp, d), jnp.float32),
            jax.ShapeDtypeStruct((kp, 1), jnp.float32),
        ],
        interpret=interpret,
    )(idx[:, None].astype(jnp.int32), x.astype(jnp.float32))
    return sums[:k], counts[:k, 0]
