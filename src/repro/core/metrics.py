"""Clustering quality metrics used by tests and the paper-table benchmarks.

The nearest-center reductions support **blocked** evaluation
(``block=``): the (N, K) distance matrix never materializes — ``lax.map``
walks fixed-size row blocks (plus one ragged tail) so peak memory is
O(block · K) regardless of N; the dense path remains the default for small
inputs.  Each row's result depends on that row alone, but every block size
is a different XLA program, free to round the expansion
``|x|² + |c|² − 2x·c`` and the final sum differently.  The contract is one
tolerance, :data:`BLOCKED_RTOL`, relative to the expansion's scale: per row,
``|blocked − dense| <= BLOCKED_RTOL · (|x|² + max|c|²)``; for the
(weighted) SSE, ``<= BLOCKED_RTOL · Σ w·(|x|² + max|c|²)``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

# blocked-vs-dense agreement, relative to |x|² + max|c|² (see above)
BLOCKED_RTOL = 1e-5

# every distance cross-term runs at full f32: the expansion cancels |x|²
# against 2x·c, and a TPU's default f32 matmul is a single bf16 pass (on a
# v5e it put the 500k paper fit's reported SSE 62% above the true one)
HIGHEST = jax.lax.Precision.HIGHEST


def nearest(d2: Array, axis: int = -1) -> Array:
    """Index of the smallest distance along ``axis`` (the first one on
    ties, like ``argmin``), compared in f32.

    XLA:TPU narrows the running minimum of an argmin whose value is unused
    to bf16, and then computes the distance expansion feeding it in bf16
    too: on a v5e the vmapped argmin over (7813, 1562) distance blocks
    picked a wrong center for half the points.  Taking the minimum as a
    value of its own, then the first index that attains it, keeps every
    comparison on the f32 distances."""
    low = jnp.min(d2, axis=axis, keepdims=True)
    return jnp.argmax(d2 == low, axis=axis)


def _min_sqdist_dense(x: Array, centers: Array) -> Array:
    """(m, d) -> (m,) squared distance to the nearest center (clamped)."""
    d = (
        jnp.sum(x * x, -1, keepdims=True)
        + jnp.sum(centers * centers, -1)[None, :]
        - 2.0 * jnp.matmul(x, centers.T, precision=HIGHEST)
    )
    return jnp.maximum(jnp.min(d, axis=-1), 0.0)


def map_row_blocks(x: Array, fn, block: int | None) -> Array:
    """Apply a row-wise ``fn((b, d)) -> (b, ...)`` over ``x`` in fixed-size
    row blocks: ``lax.map`` walks the reshaped head and the ragged tail
    gets one dense call, so peak memory is the per-block working set, not
    the full-N one.  Row results must be independent (every consumer here
    is a per-row reduction against a fixed center set), so the blocked
    output agrees with ``fn(x)`` up to rounding (:data:`BLOCKED_RTOL`).
    ``block=None`` (or ``m <= block``) is the dense path."""
    m = x.shape[0]
    if block is None or m <= block:
        return fn(x)
    nb = m // block
    head = jax.lax.map(fn, x[:nb * block].reshape(nb, block, x.shape[1]))
    head = head.reshape((nb * block,) + head.shape[2:])
    if m % block == 0:
        return head
    return jnp.concatenate([head, fn(x[nb * block:])], axis=0)


def min_sqdist(x: Array, centers: Array, *, block: int | None = None
               ) -> Array:
    """Nearest-center squared distance per point.

    With ``block`` the rows are processed ``block`` at a time (see
    :func:`map_row_blocks`) — memory O(block · k) instead of O(N · k),
    the dense values up to :data:`BLOCKED_RTOL`."""
    return map_row_blocks(x, lambda b: _min_sqdist_dense(b, centers), block)


def sse(x: Array, centers: Array, weights: Array | None = None, *,
        block: int | None = None) -> Array:
    """Weighted sum of squared distances to the nearest center — the paper's
    accuracy number (133 / 187 columns in Table 1).  ``block`` bounds the
    working set at O(block · k) (see :func:`min_sqdist`); the result is
    the dense one up to :data:`BLOCKED_RTOL`."""
    mind = min_sqdist(x, centers, block=block)
    if weights is not None:
        mind = mind * weights
    return jnp.sum(mind)


def relative_error(sse_method: float, sse_baseline: float) -> float:
    """Paper-style approximation error of a sampled clustering vs full k-means."""
    return float((sse_method - sse_baseline) / max(sse_baseline, 1e-12))


def clustering_accuracy(labels_true: np.ndarray, labels_pred: np.ndarray) -> float:
    """Best label-permutation accuracy (Hungarian matching)."""
    from scipy.optimize import linear_sum_assignment

    labels_true = np.asarray(labels_true)
    labels_pred = np.asarray(labels_pred)
    n_true = labels_true.max() + 1
    n_pred = labels_pred.max() + 1
    n = max(n_true, n_pred)
    cm = np.zeros((n, n), dtype=np.int64)
    np.add.at(cm, (labels_pred, labels_true), 1)
    row, col = linear_sum_assignment(-cm)
    return float(cm[row, col].sum()) / float(len(labels_true))
