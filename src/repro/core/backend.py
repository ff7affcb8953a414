"""The ``LloydBackend`` registry: one abstraction for every k-means hot loop.

Every layer that runs Lloyd iterations (batch pipeline, distributed merge,
streaming coreset fold, serve recompression, gradient compression) used to
plumb a bare ``assign_fn`` callable around and pay a one-hot centroid update
plus a fresh pad/copy *inside* the iteration loop.  A backend instead owns

  * ``prepare(x, weights)``  — pad/layout ONCE per ``kmeans()`` call, hoisted
    out of the Lloyd loop;
  * ``assign(prep, centers)``  — nearest-center id + squared distance;
  * ``step(prep, centers)``  — one Lloyd pass returning the RAW weighted
    per-cluster ``(sums, counts, sse)`` statistics (fp32).  Raw, so the
    distributed merge can psum them across the mesh before dividing;
  * ``sse(prep, centers)``  — weighted SSE only.

Built-in backends:

  ``jnp``           pure-jnp reference (pairwise matrix + one-hot matmul)
  ``pallas``        unfused Pallas kernels (assign + centroid, two passes)
  ``pallas_fused``  the fused single-pass kernel (kernels/lloyd.py)
  ``pallas_tuned``  the fused kernel with tile sizes resolved from the
                    autotune cache (kernels/autotune.py) per shape/device
  ``auto``          ``pallas_tuned`` on TPU, ``jnp`` elsewhere (the Pallas
                    interpreter is correctness-, not speed-, oriented)

Selection: pass ``backend="..."`` (or an instance) through any k-means entry
point; every entry point defaults to ``"auto"``, and ``"auto"`` consults the
``REPRO_KMEANS_BACKEND`` environment variable before falling back to
hardware autodetect — so the env var steers a whole process without code
changes while an explicit name in code still wins.  ``register_backend``
adds custom entries.
"""
from __future__ import annotations

import os
from typing import Callable, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp

from .metrics import HIGHEST, nearest

Array = jax.Array

ENV_VAR = "REPRO_KMEANS_BACKEND"


class Prepared(NamedTuple):
    """Padded point set, built once per ``kmeans()`` call.

    ``xp``/``wp`` are the (possibly) padded arrays the kernels consume;
    ``m``/``d`` the original static sizes (padding rows carry zero weight,
    so they contribute to no statistic).
    """
    xp: Array   # (Mp, dp)
    wp: Array   # (Mp,)
    m: int
    d: int


class LloydBackend:
    """Base class: the jnp reference implementation, and the contract."""

    name = "jnp"

    def prepare(self, x: Array, weights: Optional[Array] = None) -> Prepared:
        m, d = x.shape
        if weights is None:
            weights = jnp.ones((m,), x.dtype)
        return Prepared(x, weights.astype(x.dtype), m, d)

    def assign(self, prep: Prepared, centers: Array) -> tuple[Array, Array]:
        x = prep.xp[:prep.m, :prep.d]
        x2 = jnp.sum(x * x, axis=-1, keepdims=True)
        c2 = jnp.sum(centers * centers, axis=-1)
        xc = jnp.matmul(x, centers.T, precision=HIGHEST)
        d2 = jnp.maximum(x2 + c2[None, :] - 2.0 * xc, 0.0)
        idx = nearest(d2).astype(jnp.int32)
        mind = jnp.take_along_axis(d2, idx[:, None], axis=-1)[:, 0]
        return idx, mind

    def step(self, prep: Prepared, centers: Array
             ) -> tuple[Array, Array, Array]:
        idx, mind = self.assign(prep, centers)
        w = prep.wp[:prep.m].astype(jnp.float32)
        k = centers.shape[0]
        onehot = jax.nn.one_hot(idx, k, dtype=jnp.float32) * w[:, None]
        x = prep.xp[:prep.m, :prep.d].astype(jnp.float32)
        sums = jnp.matmul(onehot.T, x, precision=HIGHEST)
        counts = onehot.sum(axis=0)
        sse = jnp.sum(mind * w)
        return sums, counts, sse

    def sse(self, prep: Prepared, centers: Array) -> Array:
        _, mind = self.assign(prep, centers)
        return jnp.sum(mind * prep.wp[:prep.m].astype(jnp.float32))

    def check_fit(self, m: int, d: int, k: int) -> None:
        """Raise now if one f32 Lloyd pass over (m, d) points with k
        centers cannot run on this backend (the planner calls this before
        any stage runs).  The jnp reference has no tile limits."""

    # convenience for one-shot call sites (query paths, metrics)
    def assign_points(self, x: Array, centers: Array, *,
                      block: Optional[int] = None) -> tuple[Array, Array]:
        """Nearest-center id + squared distance per row.  With ``block``
        the rows are processed that many at a time (``lax.map`` over fixed
        blocks, one ragged tail) so the peak working set is
        O(block · k) however many points are assigned — each row's result
        depends on that row alone, so the values match the dense path up
        to :data:`repro.core.metrics.BLOCKED_RTOL`."""
        m = x.shape[0]
        if block is None or m <= block:
            return self.assign(self.prepare(x), centers)

        def dense(rows: Array) -> tuple[Array, Array]:
            return self.assign(self.prepare(rows), centers)

        nb = m // block
        head = jax.lax.map(dense,
                           x[:nb * block].reshape(nb, block, x.shape[1]))
        idx, dist = (part.reshape(nb * block) for part in head)
        if m % block:
            t_idx, t_dist = dense(x[nb * block:])
            idx = jnp.concatenate([idx, t_idx])
            dist = jnp.concatenate([dist, t_dist])
        return idx, dist

    # structural equality/hash: get_backend() returns a fresh instance per
    # resolution, but two same-type/same-config backends are the same
    # computation — jit caches keyed on a backend static arg must hit
    def __eq__(self, other):
        return type(self) is type(other) and self.__dict__ == other.__dict__

    def __hash__(self):
        return hash((type(self), tuple(sorted(self.__dict__.items(),
                                              key=lambda kv: kv[0]))))

    def __repr__(self):
        return f"<LloydBackend {self.name}>"


class PallasBackend(LloydBackend):
    """Unfused Pallas kernels: separate assignment and centroid passes.

    Padding still happens once (``prepare``), which already retires the
    per-iteration pad/copy the old ``ops.assign_argmin``-as-``assign_fn``
    route paid, but each Lloyd iteration reads ``x`` twice.
    """

    name = "pallas"

    def __init__(self, *, block_m: int = 256, block_k: int = 256,
                 interpret: bool | None = None):
        self.block_m = block_m
        self.block_k = block_k
        self.interpret = interpret

    def prepare(self, x: Array, weights: Optional[Array] = None) -> Prepared:
        from repro.kernels.ops import padded_layout
        m, d = x.shape
        _, mp, dp = padded_layout(m, d, self.block_m)
        xp = jnp.pad(x, ((0, mp - m), (0, dp - d)))
        if weights is None:
            wp = jnp.ones((m,), x.dtype)
        else:
            wp = weights.astype(x.dtype)
        wp = jnp.pad(wp, (0, mp - m))
        return Prepared(xp, wp, m, d)

    def _block_m(self, prep: Prepared) -> int:
        from repro.kernels.ops import padded_layout
        return padded_layout(prep.m, prep.d, self.block_m)[0]

    def _tiles(self, m: int, d: int, k: int) -> tuple[int, int]:
        """(block_m, block_k) one f32 pass at this shape runs with."""
        from repro.kernels.ops import padded_layout
        from repro.kernels.tiles import clamp_block_k
        return (padded_layout(m, d, self.block_m)[0],
                clamp_block_k(k, self.block_k))

    def check_fit(self, m: int, d: int, k: int) -> None:
        """Raise :class:`repro.kernels.tiles.TileError` when the (k, d)
        accumulators of the fused pass cannot stay in the VMEM budget."""
        from repro.kernels.tiles import pad_to, require_lloyd_vmem
        bm, bk = self._tiles(m, d, k)
        require_lloyd_vmem(bm, bk, pad_to(k, bk), d, 4,
                           kernel=f"{self.name} backend")

    def _pad_centers(self, prep: Prepared, centers: Array) -> Array:
        dp = prep.xp.shape[1]
        return jnp.pad(centers, ((0, 0), (0, dp - prep.d)))

    def assign(self, prep: Prepared, centers: Array) -> tuple[Array, Array]:
        from repro.kernels import pad_to
        from repro.kernels.assign import assign_argmin_pallas
        cp = self._pad_centers(prep, centers)
        idx, dist = assign_argmin_pallas(
            prep.xp, cp, block_m=self._block_m(prep),
            block_k=min(self.block_k, pad_to(centers.shape[0], 8)),
            interpret=self.interpret)
        return idx[:prep.m], dist[:prep.m]

    def step(self, prep: Prepared, centers: Array
             ) -> tuple[Array, Array, Array]:
        from repro.kernels import pad_to
        from repro.kernels.assign import assign_argmin_pallas
        from repro.kernels.centroid import centroid_update_pallas
        k = centers.shape[0]
        cp = self._pad_centers(prep, centers)
        idx, dist = assign_argmin_pallas(
            prep.xp, cp, block_m=self._block_m(prep),
            block_k=min(self.block_k, pad_to(k, 8)),
            interpret=self.interpret)
        sums, counts = centroid_update_pallas(
            prep.xp, idx, prep.wp, k,
            block_m=self._block_m(prep), interpret=self.interpret)
        sse = jnp.sum(dist[:prep.m]
                      * prep.wp[:prep.m].astype(jnp.float32))
        return sums[:, :prep.d], counts, sse


class PallasFusedBackend(PallasBackend):
    """Fused single-pass backend (kernels/lloyd.py): assignment, weighted
    accumulation, and SSE in ONE walk over ``x`` per Lloyd iteration — no
    assignment vector or one-hot matrix in HBM."""

    name = "pallas_fused"

    def step(self, prep: Prepared, centers: Array
             ) -> tuple[Array, Array, Array]:
        from repro.kernels.lloyd import lloyd_step_pallas
        cp = self._pad_centers(prep, centers)
        sums, counts, sse, _, _ = lloyd_step_pallas(
            prep.xp, prep.wp, cp, block_m=self._block_m(prep),
            block_k=self.block_k, interpret=self.interpret)
        return sums[:, :prep.d], counts, sse


class PallasTunedBackend(PallasFusedBackend):
    """The fused backend with tile sizes resolved from the autotune cache
    (:mod:`repro.kernels.autotune`) instead of constructor constants.

    Resolution is a host-side cache read on static shapes, so it is safe
    at jit trace time and the backend instance itself never mutates —
    structural ``__eq__``/``__hash__`` keep keying jit caches correctly.
    The cache key needs a K the point-side ``prepare()`` cannot see, so
    the planner threads ``spec.merge.k`` in as ``k_hint``
    (:func:`with_k_hint`); ``block_m`` is keyed on that hint everywhere
    (``prepare`` must pad with the same tile ``step`` later runs), while
    ``block_k`` re-keys on the *actual* K of each ``step``/``assign``
    call — different reduce levels reuse one prepared point set but get
    their own K tiling.
    """

    name = "pallas_tuned"

    # the K assumed when nobody supplied a hint (a mid-size merge); only
    # the M/d shape bucket is sensitive to it through block_m, and every
    # block_k decision re-keys on the real K anyway
    DEFAULT_K_HINT = 256

    def __init__(self, *, k_hint: int | None = None,
                 interpret: bool | None = None):
        self.k_hint = k_hint
        self.interpret = interpret

    def with_k_hint(self, k: int) -> "PallasTunedBackend":
        """A copy keyed for merges of ``k`` clusters (returns ``self`` if
        already so keyed — instances are immutable)."""
        if k == self.k_hint:
            return self
        return PallasTunedBackend(k_hint=k, interpret=self.interpret)

    def _config(self, m: int, d: int, k: int, dtype):
        from repro.kernels import autotune
        return autotune.lookup("lloyd", m=m, d=d, k=k, dtype=dtype)

    def _hint(self) -> int:
        return self.k_hint or self.DEFAULT_K_HINT

    def prepare(self, x: Array, weights: Optional[Array] = None) -> Prepared:
        from repro.kernels.ops import padded_layout
        m, d = x.shape
        cfg = self._config(m, d, self._hint(), x.dtype)
        _, mp, dp = padded_layout(m, d, cfg.block_m)
        xp = jnp.pad(x, ((0, mp - m), (0, dp - d)))
        if weights is None:
            wp = jnp.ones((m,), x.dtype)
        else:
            wp = weights.astype(x.dtype)
        wp = jnp.pad(wp, (0, mp - m))
        return Prepared(xp, wp, m, d)

    def _block_m(self, prep: Prepared) -> int:
        # keyed on the SAME hint as prepare(): the pad and the kernel tile
        # must agree whatever K a later step() brings
        from repro.kernels.ops import padded_layout
        cfg = self._config(prep.m, prep.d, self._hint(), prep.xp.dtype)
        return padded_layout(prep.m, prep.d, cfg.block_m)[0]

    def _block_k(self, prep: Prepared, k: int) -> int:
        return self._config(prep.m, prep.d, k, prep.xp.dtype).block_k

    def _tiles(self, m: int, d: int, k: int) -> tuple[int, int]:
        from repro.kernels.ops import padded_layout
        from repro.kernels.tiles import clamp_block_k
        bm = self._config(m, d, self._hint(), jnp.float32).block_m
        return (padded_layout(m, d, bm)[0],
                clamp_block_k(k, self._config(m, d, k, jnp.float32).block_k))

    def assign(self, prep: Prepared, centers: Array) -> tuple[Array, Array]:
        from repro.kernels import pad_to
        from repro.kernels.assign import assign_argmin_pallas
        k = centers.shape[0]
        cp = self._pad_centers(prep, centers)
        idx, dist = assign_argmin_pallas(
            prep.xp, cp, block_m=self._block_m(prep),
            block_k=min(self._block_k(prep, k), pad_to(k, 8)),
            interpret=self.interpret)
        return idx[:prep.m], dist[:prep.m]

    def step(self, prep: Prepared, centers: Array
             ) -> tuple[Array, Array, Array]:
        from repro.kernels.lloyd import lloyd_step_pallas
        cp = self._pad_centers(prep, centers)
        sums, counts, sse, _, _ = lloyd_step_pallas(
            prep.xp, prep.wp, cp, block_m=self._block_m(prep),
            block_k=self._block_k(prep, centers.shape[0]),
            interpret=self.interpret)
        return sums[:, :prep.d], counts, sse


class AssignFnBackend(LloydBackend):
    """Adapter for the legacy ``assign_fn`` callables — jnp statistics with
    a custom assignment step.  Exists so ``kmeans(assign_fn=...)`` keeps
    working; new code should pass ``backend=`` instead."""

    name = "assign_fn"

    def __init__(self, assign_fn: Callable[[Array, Array],
                                           tuple[Array, Array]]):
        self._assign_fn = assign_fn

    def assign(self, prep: Prepared, centers: Array) -> tuple[Array, Array]:
        return self._assign_fn(prep.xp[:prep.m, :prep.d], centers)


BackendSpec = Union[str, LloydBackend, None]

_REGISTRY: dict[str, Callable[[], LloydBackend]] = {
    "jnp": LloydBackend,
    "pallas": PallasBackend,
    "pallas_fused": PallasFusedBackend,
    "pallas_tuned": PallasTunedBackend,
}


def register_backend(name: str, factory: Callable[[], LloydBackend]) -> None:
    """Register a custom backend under ``name`` (callable returning an
    instance; called per ``get_backend`` resolution)."""
    _REGISTRY[name] = factory


def _resolve_auto() -> str:
    return "pallas_tuned" if jax.default_backend() == "tpu" else "jnp"


def get_backend(spec: BackendSpec = None) -> LloydBackend:
    """Resolve a backend: instance passthrough, name lookup, or ``None`` /
    ``"auto"`` -> ``REPRO_KMEANS_BACKEND`` env override, then hardware
    autodetect (fused on TPU, jnp elsewhere)."""
    if isinstance(spec, LloydBackend):
        return spec
    name = spec or "auto"
    if name == "auto":
        name = os.environ.get(ENV_VAR) or "auto"
    if name == "auto":
        name = _resolve_auto()
    try:
        return _REGISTRY[name]()
    except KeyError:
        raise ValueError(
            f"unknown k-means backend {name!r}; known: "
            f"{sorted(_REGISTRY)} + 'auto'") from None


def available_backends() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY)) + ("auto",)
