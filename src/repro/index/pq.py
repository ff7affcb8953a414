"""Product quantization: the paper's local k-means stage, once per subspace.

Training vmaps the stock :func:`repro.core.kmeans.kmeans` over the
``n_subspaces`` axis — the same batched-fit shape the pipeline's local stage
uses across partitions, so every backend / init registered there works here
unchanged.  Codebooks are trained on **coarse residuals** (``x -
coarse_center(cell(x))``): residual PQ is what keeps the quantization error
well below nearest-neighbor gaps in the isotropic high-``d`` regime where
raw-vector PQ collapses (distance concentration).

Encoding is pointwise per row (each row's codes depend on that row and the
trained tables alone), which is the property the out-of-core build leans
on: an index streamed chunk-by-chunk encodes to exactly the bytes an
in-memory build produces, whatever the chunk size.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.backend import BackendSpec
from repro.core.kmeans import kmeans
from repro.core.metrics import HIGHEST, map_row_blocks, nearest

from .spec import PQSpec

Array = jax.Array

# default row-block for the bounded-memory encode path (matches the
# predict-side surfaces in repro.api)
ENCODE_BLOCK = 16384


def split_subspaces(x: Array, n_subspaces: int) -> Array:
    """(n, d) -> (m, n, d/m): subspace-major view for the vmapped fits."""
    n, d = x.shape
    if d % n_subspaces:
        raise ValueError(
            f"split_subspaces: n_subspaces={n_subspaces} does not divide "
            f"d={d}")
    return jnp.transpose(x.reshape(n, n_subspaces, d // n_subspaces),
                         (1, 0, 2))


def train_codebooks(residuals: Array, pq: PQSpec, key: Array, *,
                    backend: BackendSpec = None) -> Array:
    """Train the (n_subspaces, 2**bits, d_sub) codebooks: one weighted
    k-means per subspace, vmapped — the local-stage batched fit re-applied
    to the subspace axis.  ``residuals`` are the training rows already
    reduced by their coarse center."""
    sub = split_subspaces(residuals.astype(jnp.float32), pq.n_subspaces)
    keys = jax.random.split(key, pq.n_subspaces)
    fit = jax.vmap(
        lambda xs, kk: kmeans(xs, pq.n_codes, stop=pq.effective_stop,
                              key=kk, init="kmeans++", backend=backend,
                              restarts=1).centers)
    return fit(sub, keys)


def encode_residuals(residuals: Array, codebooks: Array, *,
                     block: Optional[int] = ENCODE_BLOCK) -> Array:
    """(n, d) residuals -> (n, n_subspaces) uint8 codes: per-subspace
    nearest codebook entry, ``block`` rows at a time (O(block · m · C)
    working set; values identical to the dense evaluation)."""
    m, c, ds = codebooks.shape
    cb = codebooks.astype(jnp.float32)
    cb2 = jnp.sum(cb * cb, axis=-1)                       # (m, C)

    def dense(rows: Array) -> Array:
        r = rows.astype(jnp.float32).reshape(rows.shape[0], m, ds)
        dots = jnp.einsum("nms,mcs->nmc", r, cb, precision=HIGHEST)
        d2 = jnp.sum(r * r, -1)[..., None] + cb2[None] - 2.0 * dots
        return nearest(d2).astype(jnp.uint8)

    return map_row_blocks(residuals, dense, block)


def decode(cells: Array, codes: Array, coarse_centers: Array,
           codebooks: Array) -> Array:
    """Reconstruct (n, d) approximate vectors: coarse center plus the
    per-subspace codebook entries — the inverse bound on quantization
    error the tests check."""
    m, c, ds = codebooks.shape
    sub = codebooks[jnp.arange(m)[None, :], codes.astype(jnp.int32)]
    return (coarse_centers[cells]
            + sub.reshape(codes.shape[0], m * ds).astype(jnp.float32))


def build_luts(queries: Array, probe_cells: Array, coarse_centers: Array,
               codebooks: Array) -> Array:
    """ADC lookup tables: (Q, d) queries × (Q, P) probed cells ->
    (Q, P, m, C) f32 where ``lut[q, p, j, c] = ||res_j - codebook[j, c]||²``
    with ``res = query - center(cell p)`` — one table per (query, cell)
    pair, shared by every candidate the scan kernel walks in that cell."""
    m, c, ds = codebooks.shape
    cb = codebooks.astype(jnp.float32)
    qr = (queries.astype(jnp.float32)[:, None, :]
          - coarse_centers[probe_cells])                  # (Q, P, d)
    qs = qr.reshape(qr.shape[0], qr.shape[1], m, ds)      # (Q, P, m, ds)
    dots = jnp.einsum("qpms,mcs->qpmc", qs, cb, precision=HIGHEST)
    cb2 = jnp.sum(cb * cb, axis=-1)                       # (m, C)
    return (jnp.sum(qs * qs, -1)[..., None]
            + cb2[None, None] - 2.0 * dots)
