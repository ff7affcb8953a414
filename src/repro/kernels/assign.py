"""Pallas TPU kernel for the k-means assignment step — the compute hot spot
the paper parallelises (every Lloyd round is one (M,K) distance matrix).

TPU adaptation of the paper's CUDA distance loop:
  * ``dist^2 = |x|^2 + |c|^2 - 2 x.c^T`` — the cross term is a (bm, d) x
    (d, bk) matmul on the MXU with fp32 accumulation;
  * the (M, K) matrix is never materialised in HBM: the grid walks K tiles
    sequentially per M tile, carrying a running (min distance, argmin) pair
    in the output VMEM blocks — the analogue of the CUDA kernel keeping its
    running best in registers/SMEM;
  * block shapes are 128-aligned for the MXU/VREG layout; the K-minor grid
    order makes the HBM walk over ``c`` contiguous (the paper's row-major
    flattening concern, solved by BlockSpec index maps).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .tiles import clamp_block_k, compiler_params, require_block_m

_BIG = 3.0e38  # ~f32 max; used to mask padded center rows


def distance_tile(x: jax.Array, c: jax.Array, ki, *, block_k: int,
                  k_actual: int) -> tuple[jax.Array, jax.Array]:
    """Nearest center of one K tile for one M tile, shared by the assign
    and fused Lloyd kernels: (bm, d) points, (bk, d) centers ->
    ((1, bm) f32 min distance, (1, bm) int32 global argmin).

    The distance tile is laid out (bk, bm) — centers on sublanes, points
    on lanes — so the min/argmin reduce over sublanes and land as
    lane-dense rows, the layout the (1, M) outputs are stored in.  Rows
    past ``k_actual`` (K padding) are masked out of the argmin."""
    x = x.astype(jnp.float32)
    c = c.astype(jnp.float32)
    x2 = jnp.sum(x * x, axis=-1, keepdims=True).T          # (1, bm)
    c2 = jnp.sum(c * c, axis=-1, keepdims=True)            # (bk, 1)
    # full-f32 MXU passes: the expansion cancels |x|² against 2x·c, which
    # a single bf16 pass (the TPU default) would swamp
    xc = jax.lax.dot_general(
        c, x, (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    d2 = jnp.maximum(x2 + c2 - 2.0 * xc, 0.0)              # (bk, bm)
    row = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, d2.shape, 0)
    d2 = jnp.where(row < k_actual, d2, _BIG)
    local_min = jnp.min(d2, axis=0, keepdims=True)
    local_arg = ki * block_k + jnp.argmin(
        d2, axis=0, keepdims=True).astype(jnp.int32)
    return local_min, local_arg


def _assign_kernel(x_ref, c_ref, idx_ref, dist_ref, *, block_k: int, k_actual: int):
    ki = pl.program_id(1)
    local_min, local_arg = distance_tile(x_ref[...], c_ref[...], ki,
                                         block_k=block_k, k_actual=k_actual)

    @pl.when(ki == 0)
    def _init():
        dist_ref[...] = local_min
        idx_ref[...] = local_arg

    @pl.when(ki > 0)
    def _update():
        best = dist_ref[...]
        better = local_min < best
        dist_ref[...] = jnp.where(better, local_min, best)
        idx_ref[...] = jnp.where(better, local_arg, idx_ref[...])


def assign_argmin_pallas(
    x: jax.Array,
    c: jax.Array,
    *,
    block_m: int = 256,
    block_k: int = 256,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Nearest-center assignment: (M, d), (K, d) -> ((M,) int32, (M,) f32).

    Inputs must already be padded so M % block_m == 0, d % 128 == 0 and
    K % block_k == 0 *except* that ``k_actual`` masking handles ragged K;
    :mod:`repro.kernels.ops` does the padding.  An unpadded M raises a
    :class:`repro.kernels.tiles.TileError` with the pad recipe, and
    ``block_k`` clamps to the effective tile.
    """
    from . import default_interpret
    if interpret is None:
        interpret = default_interpret()
    m, d = x.shape
    k = c.shape[0]
    require_block_m(m, block_m, kernel="assign_argmin_pallas")
    block_k = clamp_block_k(k, block_k)
    kp = -(-k // block_k) * block_k
    if kp != k:
        c = jnp.pad(c, ((0, kp - k), (0, 0)))
    grid = (m // block_m, kp // block_k)

    idx, dist = pl.pallas_call(
        functools.partial(_assign_kernel, block_k=block_k, k_actual=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_k, d), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_m), lambda i, j: (0, i)),
            pl.BlockSpec((1, block_m), lambda i, j: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, m), jnp.int32),
            jax.ShapeDtypeStruct((1, m), jnp.float32),
        ],
        compiler_params=compiler_params(),
        interpret=interpret,
        name="assign",
    )(x, c)
    return idx[0], dist[0]
