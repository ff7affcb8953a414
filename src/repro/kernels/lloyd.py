"""Fused Lloyd-step Pallas kernel: assignment + weighted centroid
accumulation in ONE pass over the points.

The unfused path (assign.py + centroid.py) walks ``x`` twice per Lloyd
iteration and materialises the (M,) assignment in HBM between the two
kernels.  Here a single grid fuses both halves — the register-resident
running-best trick of the paper's CUDA kernel, extended with the
single-pass sufficient-statistics aggregation of Scalable K-Means++
(arXiv:1203.6402):

  * grid = (M tiles, K tiles), K minor.  Per M-tile the kernel walks the
    K tiles sequentially carrying a running (min distance, argmin) pair in
    the per-tile output VMEM blocks (assign.py's ``distance_tile``); ids,
    distances and weights travel as lane-dense ``(1, M)`` rows;
  * on the *last* K tile the winner is final, so the kernel immediately
    folds the tile into the (K, d) ``sums`` / (K, 1) ``counts`` VMEM
    accumulators via a weighted (K, bm) one-hot matmul on the MXU — the
    assignment and the one-hot matrix never round-trip through HBM; the
    resident accumulators are what :func:`repro.kernels.tiles.
    require_lloyd_vmem` budgets;
  * the weighted SSE contribution ``sum(best_dist * w)`` is accumulated in
    the same place, so one pass yields everything a Lloyd iteration needs;
  * distances and accumulation are fp32 regardless of the input dtype
    (bf16 inputs are upcast tile-by-tile in VMEM).

Inputs must be padded (M to block_m, d to 128) by the caller — the
``LloydBackend`` registry in :mod:`repro.core.backend` pads once per
``kmeans()`` call, outside the iteration loop.  Tile sizes default to the
committed per-device table; :mod:`repro.kernels.autotune` sweeps better
ones per (M, d, K) shape bucket.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .assign import distance_tile
from .centroid import weighted_onehot_t
from .tiles import (clamp_block_k, compiler_params, require_block_m,
                    require_lloyd_vmem)


def _lloyd_kernel(x_ref, w_ref, c_ref, idx_ref, dist_ref, sums_ref,
                  counts_ref, sse_ref, *, block_k: int, k_actual: int,
                  nk: int):
    i = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when((i == 0) & (ki == 0))
    def _zero_accumulators():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        counts_ref[...] = jnp.zeros_like(counts_ref)
        sse_ref[...] = jnp.zeros_like(sse_ref)

    local_min, local_arg = distance_tile(x_ref[...], c_ref[...], ki,
                                         block_k=block_k, k_actual=k_actual)

    @pl.when(ki == 0)
    def _init_best():
        dist_ref[...] = local_min
        idx_ref[...] = local_arg

    @pl.when(ki > 0)
    def _update_best():
        best = dist_ref[...]
        better = local_min < best
        dist_ref[...] = jnp.where(better, local_min, best)
        idx_ref[...] = jnp.where(better, local_arg, idx_ref[...])

    @pl.when(ki == nk - 1)
    def _accumulate():
        # the running best is final for this M tile: fold it into the
        # (K, d) VMEM accumulators right here — no HBM round-trip
        x = x_ref[...].astype(jnp.float32)                # (bm, d)
        w = w_ref[...].astype(jnp.float32)                # (1, bm)
        best = dist_ref[...]                              # (1, bm)
        onehot = weighted_onehot_t(idx_ref[...], w, sums_ref.shape[0])
        sums_ref[...] += jax.lax.dot_general(
            onehot, x, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)           # (kp, d)
        counts_ref[...] += jnp.sum(onehot, axis=1, keepdims=True)
        sse_ref[...] = sse_ref[...] + jnp.sum(best * w)


def lloyd_step_pallas(
    x: jax.Array,
    w: jax.Array,
    c: jax.Array,
    *,
    block_m: int = 256,
    block_k: int = 256,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """One fused Lloyd pass: (M, d) points, (M,) weights, (K, d) centers ->
    (sums (K, d) f32, counts (K,) f32, sse () f32, idx (M,) i32,
    dist (M,) f32).

    ``sums``/``counts`` are the *raw* weighted per-cluster statistics (the
    caller divides and applies the empty-cluster fix-up), so the same
    primitive serves the single-device loop and the distributed merge
    (psum the raw stats, then divide).  M must be a multiple of block_m and
    d a multiple of 128 (pad with w=0 rows — a shape that isn't raises a
    :class:`repro.kernels.tiles.TileError` with the recipe); ragged K is
    masked in-kernel and ``block_k`` clamps to the effective tile
    (:func:`repro.kernels.tiles.clamp_block_k`), so ``k < 8`` always runs
    one 8-wide tile whatever was requested.
    """
    from . import default_interpret
    if interpret is None:
        interpret = default_interpret()
    m, d = x.shape
    k = c.shape[0]
    require_block_m(m, block_m, kernel="lloyd_step_pallas")
    block_k = clamp_block_k(k, block_k)
    kp = -(-k // block_k) * block_k
    require_lloyd_vmem(block_m, block_k, kp, d, x.dtype.itemsize,
                       kernel="lloyd_step_pallas")
    if kp != k:
        c = jnp.pad(c, ((0, kp - k), (0, 0)))
    nk = kp // block_k
    grid = (m // block_m, nk)

    idx, dist, sums, counts, sse = pl.pallas_call(
        functools.partial(_lloyd_kernel, block_k=block_k, k_actual=k, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, d), lambda i, j: (i, 0)),
            pl.BlockSpec((1, block_m), lambda i, j: (0, i)),
            pl.BlockSpec((block_k, d), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_m), lambda i, j: (0, i)),
            pl.BlockSpec((1, block_m), lambda i, j: (0, i)),
            pl.BlockSpec((kp, d), lambda i, j: (0, 0)),
            pl.BlockSpec((kp, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, m), jnp.int32),
            jax.ShapeDtypeStruct((1, m), jnp.float32),
            jax.ShapeDtypeStruct((kp, d), jnp.float32),
            jax.ShapeDtypeStruct((kp, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        compiler_params=compiler_params(),
        interpret=interpret,
        name="lloyd",
    )(x, w.reshape(1, m), c)
    return sums[:k], counts[:k, 0], sse[0, 0], idx[0], dist[0]
