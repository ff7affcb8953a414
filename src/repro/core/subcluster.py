"""The paper's two subclustering schemes (Algorithms 1 & 2), TPU-vectorized.

Algorithm 1 (equal sized): feature-scale, build landmark L = per-attribute
minimum, repeatedly gather the N points closest to L and remove them.  With a
*fixed* L (as the paper's iterative Algorithm 1 states) that loop is exactly
"sort all points by distance-to-L and cut into consecutive chunks of N" — so
the vectorized implementation below produces the *identical* partition while
being a single device-wide sort instead of a P-step host loop.

Algorithm 2 (unequal sized): landmarks are P evenly spaced points on the
segment [L, H] (per-attribute min / per-attribute max); each point joins its
nearest landmark.  Partition sizes are data-dependent, which XLA cannot
express — we bound them with a *capacity* (like MoE token routing):
``capacity = ceil(M/P * capacity_factor)`` slots per partition, overflow
points are dropped from the local stage (they are still counted, reported,
and — since dropped points are by construction in dense regions already well
covered by their partition — the approximation effect is tiny; the benchmark
sweeps validate this).
"""
from __future__ import annotations

import warnings
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from .metrics import HIGHEST, nearest

Array = jax.Array


class Partition(NamedTuple):
    """Static-shape partition: ``indices[p, s]`` is the point id of slot ``s``
    in partition ``p`` (arbitrary where ``mask`` is False)."""
    indices: Array    # (P, capacity) int32
    mask: Array       # (P, capacity) bool
    n_dropped: Array  # () int32 — points that exceeded capacity (Algo 2 only)


def feature_scale(x: Array, eps: float = 1e-9) -> tuple[Array, tuple[Array, Array]]:
    """Min-max feature scaling (paper step 2); returns scaled points and the
    (lo, span) pair needed to map centers back to the input space."""
    lo = jnp.min(x, axis=0)
    hi = jnp.max(x, axis=0)
    span = jnp.maximum(hi - lo, eps)
    return (x - lo) / span, (lo, span)


def unscale(centers: Array, params: tuple[Array, Array]) -> Array:
    lo, span = params
    return centers * span + lo


def equal_partition(x: Array, n_sub: int) -> Partition:
    """Algorithm 1.  Returns ``n_sub`` partitions of ceil(M/n_sub) slots; when
    M is not divisible the trailing slots of the last partition are masked."""
    m = x.shape[0]
    cap = -(-m // n_sub)  # ceil
    landmark = jnp.min(x, axis=0)
    d = jnp.sum((x - landmark[None, :]) ** 2, axis=-1)
    order = jnp.argsort(d).astype(jnp.int32)
    pad = n_sub * cap - m
    order = jnp.concatenate([order, jnp.full((pad,), -1, jnp.int32)])
    idx = order.reshape(n_sub, cap)
    mask = idx >= 0
    idx = jnp.where(mask, idx, 0)
    return Partition(idx, mask, jnp.asarray(0, jnp.int32))


def unequal_landmarks(x: Array, n_landmarks: int) -> Array:
    """P evenly spaced landmarks on the [per-attr min, per-attr max] segment."""
    lo = jnp.min(x, axis=0)
    hi = jnp.max(x, axis=0)
    t = jnp.linspace(0.0, 1.0, n_landmarks, dtype=x.dtype)[:, None]
    return lo[None, :] + t * (hi - lo)[None, :]


def unequal_partition(
    x: Array, n_landmarks: int, *, capacity_factor: float = 2.0,
    capacity: int | None = None,
) -> Partition:
    """Algorithm 2 with MoE-style capacity bounding (see module docstring)."""
    m = x.shape[0]
    if capacity is None:
        if capacity_factor < 1.0:
            # below-even-split capacity guarantees drops whenever any
            # landmark attracts at least its even share of points
            warnings.warn(
                f"unequal_partition: capacity_factor={capacity_factor} < 1 "
                f"bounds every partition below the even split "
                f"ceil(M/P)={-(-m // n_landmarks)}; overflow points WILL be "
                f"dropped from the local stage (n_dropped stays exact)",
                stacklevel=2)
        capacity = int(-(-m // n_landmarks) * capacity_factor)
        if capacity > m:
            # the min() clamp is about to engage: the requested capacity
            # exceeds the point count, so the factor is effectively inert
            warnings.warn(
                f"unequal_partition: capacity "
                f"ceil(M/P)*capacity_factor={capacity} exceeds M={m}; "
                f"clamping to M (capacity_factor={capacity_factor} has no "
                f"further effect at this size)", stacklevel=2)
        capacity = min(capacity, m)
    lms = unequal_landmarks(x, n_landmarks)
    d = (
        jnp.sum(x * x, -1, keepdims=True)
        + jnp.sum(lms * lms, -1)[None, :]
        - 2.0 * jnp.matmul(x, lms.T, precision=HIGHEST)
    )
    assign = nearest(d).astype(jnp.int32)

    order = jnp.argsort(assign, stable=True).astype(jnp.int32)
    sorted_assign = assign[order]
    # rank of each point within its landmark group
    starts = jnp.searchsorted(sorted_assign, jnp.arange(n_landmarks), side="left")
    rank = jnp.arange(m, dtype=jnp.int32) - starts[sorted_assign].astype(jnp.int32)
    keep = rank < capacity
    slot = jnp.where(keep, sorted_assign * capacity + rank, n_landmarks * capacity)
    flat = jnp.full((n_landmarks * capacity,), -1, jnp.int32)
    flat = flat.at[slot].set(order, mode="drop")
    idx = flat.reshape(n_landmarks, capacity)
    mask = idx >= 0
    idx = jnp.where(mask, idx, 0)
    n_dropped = jnp.asarray(m, jnp.int32) - keep.sum().astype(jnp.int32)
    return Partition(idx, mask, n_dropped)


def gather_partitions(x: Array, part: Partition,
                      weights: Array | None = None) -> tuple[Array, Array]:
    """Materialise (P, capacity, d) point blocks + (P, capacity) weights.

    With ``weights`` (per-point mass, e.g. the member counts of a weighted
    center pool in the hierarchical reduce tree) each slot carries
    ``mask * weights[index]`` instead of the 0/1 mask — dead pool entries
    (weight 0) land in some partition but contribute nothing to its
    k-means, so mass is conserved level to level.
    """
    pts = x[part.indices]
    w = part.mask.astype(x.dtype)
    if weights is not None:
        w = w * weights.astype(x.dtype)[part.indices]
    return pts, w


# ---------------------------------------------------------------------------
# Partitioner registry
# ---------------------------------------------------------------------------
# A partitioner maps ``(x, n_sub, capacity_factor) -> Partition``.  The
# registry is what :class:`repro.core.spec.PartitionSpec.scheme` resolves
# against, so new subclustering strategies plug into every surface (batch,
# shard_map, stream) by registering one callable.

PartitionerFn = Callable[[Array, int, float], Partition]

_PARTITIONERS: dict[str, PartitionerFn] = {
    "equal": lambda x, n_sub, capacity_factor: equal_partition(x, n_sub),
    "unequal": lambda x, n_sub, capacity_factor: unequal_partition(
        x, n_sub, capacity_factor=capacity_factor),
}


def register_partitioner(name: str, fn: PartitionerFn) -> None:
    """Register ``fn(x, n_sub, capacity_factor) -> Partition`` under
    ``name`` (resolvable from ``PartitionSpec.scheme``)."""
    _PARTITIONERS[name] = fn


def get_partitioner(name: str) -> PartitionerFn:
    try:
        return _PARTITIONERS[name]
    except KeyError:
        raise ValueError(
            f"unknown partition scheme {name!r}; known: "
            f"{sorted(_PARTITIONERS)}") from None


def available_partitioners() -> tuple[str, ...]:
    return tuple(sorted(_PARTITIONERS))
