"""Pallas TPU kernel for the weighted centroid update (segment-sum).

TPU has no efficient scatter; the idiomatic replacement is a one-hot matmul:
``sums = onehot(idx)^T @ x`` hits the MXU and the (K, d) accumulator lives in
VMEM across the sequential grid walk over M tiles — the analogue of the CUDA
kernel accumulating per-cluster sums in shared memory, then atomics to HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .tiles import compiler_params, require_block_m, require_lloyd_vmem


def weighted_onehot_t(idx: jax.Array, w: jax.Array, kp: int) -> jax.Array:
    """Transposed weighted one-hot of one M tile, shared by the centroid
    and fused Lloyd kernels: (1, bm) int32 ids, (1, bm) f32 weights ->
    (kp, bm) with ``w`` where row == id.  ``onehot @ x`` is then the
    tile's per-cluster weighted sum as a plain MXU matmul, and a lane
    reduction gives the counts."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (kp, idx.shape[1]), 0)
    return jnp.where(rows == idx, w, 0.0)


def _centroid_kernel(x_ref, idx_ref, w_ref, sums_ref, counts_ref, *, k: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        counts_ref[...] = jnp.zeros_like(counts_ref)

    x = x_ref[...].astype(jnp.float32)                       # (bm, d)
    onehot = weighted_onehot_t(idx_ref[...],
                               w_ref[...].astype(jnp.float32), k)  # (k, bm)
    sums_ref[...] += jax.lax.dot_general(
        onehot, x, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)                  # (k, d)
    counts_ref[...] += jnp.sum(onehot, axis=1, keepdims=True)  # (k, 1)


def centroid_update_pallas(
    x: jax.Array,
    idx: jax.Array,
    w: jax.Array,
    k: int,
    *,
    block_m: int = 512,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Weighted per-cluster sums and counts.

    (M, d) points, (M,) int32 assignment, (M,) weights -> ((K, d), (K,)).
    M must be a multiple of block_m (ops.py pads with w=0 rows; an
    unpadded M raises a :class:`repro.kernels.tiles.TileError`).
    """
    from . import default_interpret
    if interpret is None:
        interpret = default_interpret()
    m, d = x.shape
    require_block_m(m, block_m, kernel="centroid_update_pallas")
    require_lloyd_vmem(block_m, 0, k, d, x.dtype.itemsize,
                       kernel="centroid_update_pallas")
    grid = (m // block_m,)

    sums, counts = pl.pallas_call(
        functools.partial(_centroid_kernel, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, d), lambda i: (i, 0)),
            pl.BlockSpec((1, block_m), lambda i: (0, i)),
            pl.BlockSpec((1, block_m), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((k, d), lambda i: (0, 0)),
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, d), jnp.float32),
            jax.ShapeDtypeStruct((k, 1), jnp.float32),
        ],
        compiler_params=compiler_params(),
        interpret=interpret,
        name="centroid",
    )(x, idx.reshape(1, m), w.reshape(1, m))
    return sums, counts[:, 0]
