"""FlashAssign: fused pairwise-distance + running argmin Pallas TPU kernel.

The hot loop of every K-means-family algorithm (and the operation the paper's
SS5.3 vectorizes on CPU SIMD) is: for each point, find the nearest centroid.
The naive formulation materializes an (s, k) distance matrix in HBM; for the
paper's big-data regimes (s up to 1.3e5, k up to 25, d up to 5000 — and far
larger inside this framework) that matrix is pure memory traffic.

TPU adaptation: stream centroid tiles through VMEM and keep an *online*
(min, argmin) carry per point row — the same trick FlashAttention uses for
the online softmax, applied to argmin. The (s, k) matrix never exists.

Grid: (s/bs, k/bk, d/bd), d innermost so the (bs, bk) dot-product
accumulator lives in a VMEM scratch across d-tiles (MXU matmuls of shape
(bs, bd) x (bd, bk)). On the last d-tile the partial dots fold with the
precomputed row norms into squared distances, which update the per-row
running (best, best_idx) scratch across k-tiles. Outputs are written once,
on the final (k, d) tile.

All tile shapes are multiples of (8, 128) so both the MXU matmul and the
VPU select run on hardware-aligned lanes. Padding is handled by the ops.py
wrapper: K is padded with +inf norms (never wins), D with zeros (no-op in the
dot), S with arbitrary rows that are sliced off.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_S = 256
DEFAULT_BLOCK_K = 128
DEFAULT_BLOCK_D = 256


def _assign_kernel(
    xn_ref,  # (bs, 1)  f32  row norms ||x||^2
    cn_ref,  # (1, bk)  f32  centroid norms ||c||^2 (+inf on padding)
    x_ref,   # (bs, bd) f32/bf16 point tile
    c_ref,   # (bk, bd) f32/bf16 centroid tile
    idx_ref,   # out (bs, 1) int32
    dist_ref,  # out (bs, 1) f32
    acc_ref,   # scratch (bs, bk) f32 — partial 2*x.c
    best_ref,  # scratch (bs, 1) f32 — running min distance
    bidx_ref,  # scratch (bs, 1) int32 — running argmin
    *,
    nk: int,
    nd: int,
    bk: int,
    precision,
):
    ki = pl.program_id(1)
    di = pl.program_id(2)

    @pl.when(di == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # (bs, bd) x (bk, bd)^T on the MXU, f32 accumulation.
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...],
        c_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=precision,
        preferred_element_type=jnp.float32,
    )

    @pl.when(di == nd - 1)
    def _fold_distances():
        # ||x||^2 - 2 x.c + ||c||^2, clamped at 0.
        d2 = jnp.maximum(xn_ref[...] - 2.0 * acc_ref[...] + cn_ref[...], 0.0)
        local_min = jnp.min(d2, axis=1, keepdims=True)  # (bs, 1)
        local_arg = (
            jnp.argmin(d2, axis=1).astype(jnp.int32)[:, None] + ki * bk
        )  # (bs, 1) global centroid index

        @pl.when(ki == 0)
        def _first_tile():
            best_ref[...] = local_min
            bidx_ref[...] = local_arg

        @pl.when(ki > 0)
        def _online_min():
            take_new = local_min < best_ref[...]
            best_ref[...] = jnp.where(take_new, local_min, best_ref[...])
            bidx_ref[...] = jnp.where(take_new, local_arg, bidx_ref[...])

        @pl.when(ki == nk - 1)
        def _emit():
            idx_ref[...] = bidx_ref[...]
            dist_ref[...] = best_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=(
        "k_valid", "block_s", "block_k", "block_d", "compute_dtype",
        "interpret",
    ),
)
def assign_pallas(
    x: jax.Array,
    c: jax.Array,
    *,
    k_valid: int | None = None,
    block_s: int = DEFAULT_BLOCK_S,
    block_k: int = DEFAULT_BLOCK_K,
    block_d: int = DEFAULT_BLOCK_D,
    compute_dtype: str = "f32",
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Nearest-centroid assignment. x: (s, d), c: (k, d) -> (idx, dist).

    Inputs must already be padded to tile multiples (ops.py does this);
    ``k_valid`` marks how many leading rows of ``c`` are real — padded rows
    get +inf norms so they can never win the argmin.

    ``compute_dtype="bf16"`` feeds the MXU bf16 point/centroid tiles (half
    the VMEM traffic) while norms and the distance accumulator stay f32 —
    the dot itself always uses ``preferred_element_type=f32``. With f32
    tiles the dot runs at ``HIGHEST`` precision, so it is f32 throughout.
    """
    s, d = x.shape
    k, d2 = c.shape
    assert d == d2, (x.shape, c.shape)
    bs, bk, bd = min(block_s, s), min(block_k, k), min(block_d, d)
    assert s % bs == 0 and k % bk == 0 and d % bd == 0, (
        f"padded shapes required: {(s, k, d)} vs blocks {(bs, bk, bd)}"
    )
    ns, nk, nd = s // bs, k // bk, d // bd

    xf = x.astype(jnp.float32)
    cf = c.astype(jnp.float32)
    xn = jnp.sum(xf * xf, axis=1, keepdims=True)  # (s, 1) — norms stay f32
    cn = jnp.sum(cf * cf, axis=1)[None, :]  # (1, k)
    if k_valid is not None and k_valid < k:
        pad_mask = jnp.arange(k)[None, :] >= k_valid
        cn = jnp.where(pad_mask, jnp.inf, cn)
    if compute_dtype == "bf16":
        xk, ck = xf.astype(jnp.bfloat16), cf.astype(jnp.bfloat16)
        precision = None
    else:
        # Mosaic's default for an f32 product is one bf16 pass.
        xk, ck, precision = xf, cf, jax.lax.Precision.HIGHEST

    kernel = functools.partial(_assign_kernel, nk=nk, nd=nd, bk=bk,
                               precision=precision)
    idx, dist = pl.pallas_call(
        kernel,
        grid=(ns, nk, nd),
        in_specs=[
            pl.BlockSpec((bs, 1), lambda si, ki, di: (si, 0)),
            pl.BlockSpec((1, bk), lambda si, ki, di: (0, ki)),
            pl.BlockSpec((bs, bd), lambda si, ki, di: (si, di)),
            pl.BlockSpec((bk, bd), lambda si, ki, di: (ki, di)),
        ],
        out_specs=[
            pl.BlockSpec((bs, 1), lambda si, ki, di: (si, 0)),
            pl.BlockSpec((bs, 1), lambda si, ki, di: (si, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s, 1), jnp.int32),
            jax.ShapeDtypeStruct((s, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bs, bk), jnp.float32),
            pltpu.VMEM((bs, 1), jnp.float32),
            pltpu.VMEM((bs, 1), jnp.int32),
        ],
        interpret=interpret,
    )(xn, cn, xk, ck)
    return idx[:, 0], dist[:, 0]
