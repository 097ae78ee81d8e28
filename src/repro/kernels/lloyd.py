"""Fused Lloyd-pass Pallas kernel: assign + cluster-sums in ONE x read.

A Lloyd iteration needs (argmin over centroids) and (per-cluster sums).
Running FlashAssign then cluster-sum streams the points twice from HBM; at
clustering dimensions (k <= a few hundred, d <= a few thousand) the whole
(K, D) sums accumulator fits VMEM, so both halves fuse: for each point tile
we loop centroid tiles with the online argmin carry, and once the winner is
known we accumulate one-hot(winner)^T @ x into the resident (K, D) block.
Memory traffic per Lloyd iteration halves — the dominant term of the
hpclust-prod roofline cell (EXPERIMENTS.md §Perf It.3).

Constraint: D is untiled (the x row-block (bs, D) must fit VMEM — true for
the paper's regimes, d <= 5000). ops.lloyd_pass falls back to the two-kernel
path otherwise.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _lloyd_kernel(
    cn_ref,     # (1, bk)  f32 centroid norms (+inf padding)
    x_ref,      # (bs, D)  f32 point tile (full D)
    c_ref,      # (bk, D)  f32 centroid tile
    idx_ref,    # out (bs, 1) int32
    dist_ref,   # out (bs, 1) f32
    sums_ref,   # out (K, D) f32 — constant index map, VMEM resident
    counts_ref, # out (K, 1) f32
    best_ref,   # scratch (bs, 1) f32
    bidx_ref,   # scratch (bs, 1) int32
    *,
    nk: int,
    bk: int,
    k_total: int,
    bs: int,
    s_valid: int,
    precision,
):
    si = pl.program_id(0)
    ki = pl.program_id(1)

    x = x_ref[...]
    xf = x.astype(jnp.float32)
    xn = jnp.sum(xf * xf, axis=1, keepdims=True)  # (bs, 1) — norms in f32
    dots = jax.lax.dot_general(
        x, c_ref[...], (((1,), (1,)), ((), ())),
        precision=precision, preferred_element_type=jnp.float32,
    )  # (bs, bk) — bf16 inputs still accumulate in f32
    d2 = jnp.maximum(xn - 2.0 * dots + cn_ref[...], 0.0)
    local_min = jnp.min(d2, axis=1, keepdims=True)
    local_arg = jnp.argmin(d2, axis=1).astype(jnp.int32)[:, None] + ki * bk

    @pl.when(ki == 0)
    def _first():
        best_ref[...] = local_min
        bidx_ref[...] = local_arg

    @pl.when(ki > 0)
    def _online():
        take = local_min < best_ref[...]
        best_ref[...] = jnp.where(take, local_min, best_ref[...])
        bidx_ref[...] = jnp.where(take, local_arg, bidx_ref[...])

    @pl.when(ki == nk - 1)
    def _emit_and_accumulate():
        idx_ref[...] = bidx_ref[...]
        dist_ref[...] = best_ref[...]

        @pl.when(si == 0)
        def _init_outs():
            sums_ref[...] = jnp.zeros_like(sums_ref)
            counts_ref[...] = jnp.zeros_like(counts_ref)

        winners = bidx_ref[...]  # (bs, 1)
        kk = jax.lax.broadcasted_iota(jnp.int32, (1, k_total), 1)
        # Mask padding rows (global row id >= s_valid): they must not
        # contribute to sums/counts.
        row_id = si * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, 1), 0)
        live = row_id < s_valid
        # One-hot in x's dtype so the MXU sees matching operands (0/1 are
        # exact in bf16); the dot still accumulates f32 into sums_ref.
        onehot = ((winners == kk) & live).astype(x.dtype)  # (bs, K)
        sums_ref[...] += jax.lax.dot_general(
            onehot, x, (((0,), (0,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32,
        )
        # Counts reduce in f32: a bf16 running count saturates at 256.
        counts_ref[...] += jnp.sum(
            onehot.astype(jnp.float32), axis=0)[:, None]


@functools.partial(
    jax.jit,
    static_argnames=(
        "k_valid", "s_valid", "block_s", "block_k", "compute_dtype",
        "interpret",
    ),
)
def lloyd_pass_pallas(
    x: jax.Array,
    c: jax.Array,
    *,
    k_valid: int | None = None,
    s_valid: int | None = None,
    block_s: int = 256,
    block_k: int = 128,
    compute_dtype: str = "f32",
    interpret: bool = False,
):
    """One fused Lloyd pass. x (s, d), c (k, d) padded to tile multiples.

    Returns (idx (s,), dist (s,), sums (k, d) f32, counts (k,) f32).
    ``compute_dtype="bf16"`` streams bf16 point/centroid tiles; norms,
    distances, sums and counts all still accumulate in f32.
    """
    s, d = x.shape
    k = c.shape[0]
    bs, bk = min(block_s, s), min(block_k, k)
    assert s % bs == 0 and k % bk == 0, (s, k, bs, bk)
    ns, nk = s // bs, k // bk

    cf = c.astype(jnp.float32)
    cn = jnp.sum(cf * cf, axis=1)[None, :]  # centroid norms stay f32
    if k_valid is not None and k_valid < k:
        cn = jnp.where(jnp.arange(k)[None, :] >= k_valid, jnp.inf, cn)
    if compute_dtype == "bf16":
        xk, ck = x.astype(jnp.bfloat16), cf.astype(jnp.bfloat16)
        precision = None
    else:
        # Mosaic's default for an f32 product is one bf16 pass.
        xk, ck = x.astype(jnp.float32), cf
        precision = jax.lax.Precision.HIGHEST

    kernel = functools.partial(
        _lloyd_kernel, nk=nk, bk=bk, k_total=k, bs=bs,
        s_valid=s_valid if s_valid is not None else s, precision=precision,
    )
    idx, dist, sums, counts = pl.pallas_call(
        kernel,
        grid=(ns, nk),
        in_specs=[
            pl.BlockSpec((1, bk), lambda si, ki: (0, ki)),
            pl.BlockSpec((bs, d), lambda si, ki: (si, 0)),
            pl.BlockSpec((bk, d), lambda si, ki: (ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bs, 1), lambda si, ki: (si, 0)),
            pl.BlockSpec((bs, 1), lambda si, ki: (si, 0)),
            pl.BlockSpec((k, d), lambda si, ki: (0, 0)),
            pl.BlockSpec((k, 1), lambda si, ki: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s, 1), jnp.int32),
            jax.ShapeDtypeStruct((s, 1), jnp.float32),
            jax.ShapeDtypeStruct((k, d), jnp.float32),
            jax.ShapeDtypeStruct((k, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bs, 1), jnp.float32),
            pltpu.VMEM((bs, 1), jnp.int32),
        ],
        interpret=interpret,
    )(cn, xk, ck)
    return idx[:, 0], dist[:, 0], sums, counts[:, 0]
