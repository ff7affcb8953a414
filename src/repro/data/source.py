"""Chunked data sources: the out-of-core primitive behind ``mode="chunked"``.

The paper's whole argument is that the dataset never has to exist in one
place — subdivide, cluster the pieces, merge the weighted representatives.
A :class:`DataSource` makes *chunked data* the first-class input type of the
library and the single resident array the special case:

  ``ArraySource``     wraps an in-memory array (the degenerate one-chunk —
                      or few-chunk — case; what plain-array calls auto-wrap
                      into).
  ``IterSource``      wraps ANY host iterator factory — a generator over
                      ``np.memmap`` slices, file shards, a database cursor —
                      and re-batches its pieces into fixed ``chunk_points``
                      rows so the device always sees the same shapes
                      (one ragged tail chunk at most).
  ``SyntheticSource`` generates paper-style Gaussian blobs chunk by chunk,
                      deterministically per (seed, chunk index), so
                      benchmark workloads far larger than host RAM never
                      materialize.

Sources may be traversed **multiple times** (`chunks()` restarts): the
chunked executor makes up to three passes (scale, fold, exact SSE).  That is
why :class:`IterSource` takes a zero-argument *factory* returning a fresh
iterator, not a bare generator object (which is single-use and rejected
with an explanatory error).

Every source also splits: ``source.shard(i, n)`` returns the ``i``-th of
``n`` disjoint sub-sources whose union (at a fixed ``chunk_points``) is
exactly the parent's point set.  Shards are themselves restartable
DataSources, which is what lets the sharded out-of-core executor
(``mode="chunked_dist"``) give every mesh device its own independent chunk
stream: :class:`ArraySource` shards by contiguous row range (exact
``shape`` preserved), :class:`SyntheticSource` by chunk index (each shard
generates only its own chunks — skipped chunks cost nothing),
:class:`IterSource` by striding over its re-batched chunk stream (or via a
user ``shard_factory`` when the underlying storage is natively split, e.g.
one file per shard).

:func:`prefetch_to_device` is the host→device double-buffer: it keeps
``depth`` chunks in flight via ``jax.device_put`` (asynchronous on
accelerators) so the device never waits on host-side chunk preparation.
``device=`` pins the buffers to one specific device — each shard of the
sharded executor prefetches onto its own device — and chunks that already
live committed on the target device skip the redundant transfer.
"""
from __future__ import annotations

import collections
from typing import Callable, Iterable, Iterator, Optional

import jax
import numpy as np

from repro.telemetry import scope

Array = jax.Array


class DataSource:
    """Protocol for chunked point sets (the out-of-core input type).

    Concrete sources expose

      * ``dim``       — point dimensionality, or ``None`` when not known
                        before iteration;
      * ``n_points``  — total row count, or ``None`` when unknown (e.g. an
                        unbounded file-shard iterator);
      * ``chunks(chunk_points)`` — a fresh iterator of ``(m, dim)`` host
        arrays with ``m <= chunk_points`` (only the final chunk may be
        ragged).  Must be restartable: the executor takes several passes.
      * ``shard(i, n)`` — the ``i``-th of ``n`` disjoint sub-sources whose
        union at any fixed ``chunk_points`` is the parent's point set.
    """

    dim: Optional[int] = None
    n_points: Optional[int] = None

    def chunks(self, chunk_points: int) -> Iterator[np.ndarray]:
        raise NotImplementedError

    def shard(self, index: int, count: int) -> "DataSource":
        """Split into ``count`` disjoint, restartable sub-sources and return
        the ``index``-th.  The default strides over the re-batched chunk
        stream (shard ``i`` keeps chunks ``i, i+count, i+2·count, ...`` at
        whatever ``chunk_points`` the consumer traverses with), so the
        shards are disjoint and union-complete by construction.  Subclasses
        override with cheaper splits (row ranges, chunk-index generation).
        """
        _check_shard(index, count)
        if count == 1:
            return self
        return _StridedShard(self, index, count)

    @property
    def shape(self) -> Optional[tuple]:
        """(n_points, dim) when both are known, else ``None`` — what the
        planner's fail-fast validation consumes."""
        if self.n_points is None or self.dim is None:
            return None
        return (self.n_points, self.dim)


def _check_shard(index: int, count: int) -> None:
    if count < 1:
        raise ValueError(f"shard: count must be >= 1, got {count}")
    if not 0 <= index < count:
        raise ValueError(f"shard: index {index} out of range for "
                         f"count {count}")


class _StridedShard(DataSource):
    """Generic ``shard(i, n)``: every ``n``-th chunk of the parent's
    re-batched stream, starting at chunk ``i``.  The parent stream is still
    traversed on this host (skipped chunks are produced and discarded), so
    this is the fallback for opaque iterators — sources that can address
    their pieces directly (arrays, synthetic generators, shard-aware
    factories) override :meth:`DataSource.shard` instead.
    """

    def __init__(self, parent: DataSource, index: int, count: int):
        self.parent, self.index, self.count = parent, index, count
        self.n_points = None        # per-shard rows depend on chunk_points

    @property
    def dim(self) -> Optional[int]:   # IterSource infers dim lazily
        return self.parent.dim

    def chunks(self, chunk_points: int) -> Iterator[np.ndarray]:
        for j, chunk in enumerate(self.parent.chunks(chunk_points)):
            if j % self.count == self.index:
                yield chunk


class ArraySource(DataSource):
    """A resident 2-D array as a source — the in-memory special case.

    ``chunks`` yields row slices (views for numpy, zero-copy device slices
    for jax arrays).  A ``chunk_points >= n_points`` traversal is exactly
    one chunk, which is the chunked executor's bit-for-bit parity case
    with :func:`repro.core.pipeline.fit_from_spec`.
    """

    def __init__(self, array):
        if array.ndim != 2:
            raise ValueError(
                f"ArraySource: need a (n_points, dim) array, got shape "
                f"{tuple(array.shape)}")
        self.array = array
        self.n_points, self.dim = (int(array.shape[0]), int(array.shape[1]))

    def chunks(self, chunk_points: int) -> Iterator:
        for start in range(0, self.n_points, chunk_points):
            yield self.array[start:start + chunk_points]

    def shard(self, index: int, count: int) -> "ArraySource":
        """Balanced contiguous row-range split: shard ``i`` holds rows
        ``[i·n/count, (i+1)·n/count)`` (numpy slices are views — no copy;
        jax slices stay on device).  Exact per-shard ``shape`` is preserved,
        so the planner's fail-fast accounting keeps working per shard."""
        _check_shard(index, count)
        if count == 1:
            return self
        lo = (index * self.n_points) // count
        hi = ((index + 1) * self.n_points) // count
        return ArraySource(self.array[lo:hi])


class IterSource(DataSource):
    """Any host iterator as a source, re-batched to fixed-size chunks.

    Parameters
    ----------
    factory:   zero-argument callable returning a fresh iterator/iterable of
               ``(m_i, dim)`` arrays (arbitrary, possibly ragged ``m_i`` —
               memmap slices, file shards, ...).  A re-iterable container
               (list, tuple) is also accepted and re-traversed per pass.  A
               bare generator object is rejected: the executor needs
               multiple passes and a generator is single-use.
    dim:       point dimensionality, when known up front (otherwise inferred
               on first traversal; ``plan`` validation that needs it is
               simply skipped).
    n_points:  total rows, when known (enables the planner's pool-schedule
               fail-fast check).
    shard_factory: optional ``(index, count) -> factory`` hook for storage
               that is natively split (one file per shard, a partitioned
               table): ``shard(i, n)`` then wraps
               ``shard_factory(i, n)`` in a fresh IterSource instead of
               striding over the whole re-batched stream on one host.
               The hook owns disjointness/completeness of the split.
    """

    def __init__(self, factory: Callable[[], Iterable] | Iterable, *,
                 dim: Optional[int] = None, n_points: Optional[int] = None,
                 shard_factory: Optional[Callable] = None):
        if callable(factory):
            self._factory = factory
        elif iter(factory) is factory:
            raise ValueError(
                "IterSource: got a single-use iterator (e.g. a bare "
                "generator object) — the chunked executor traverses the "
                "source several times (scale pass, fold pass, exact-SSE "
                "pass).  Pass a zero-argument factory instead: "
                "IterSource(lambda: my_generator(...))")
        else:
            seq = factory
            self._factory = lambda: iter(seq)
        if shard_factory is not None and not callable(shard_factory):
            raise ValueError(
                "IterSource: shard_factory must be a callable "
                "(index, count) -> iterator factory")
        self._shard_factory = shard_factory
        self.dim = dim
        self.n_points = n_points

    def shard(self, index: int, count: int) -> DataSource:
        """With a ``shard_factory``, shard ``i`` is a fresh IterSource over
        ``shard_factory(i, count)`` (natively split storage — row counts per
        shard are unknown unless the factory's pieces say so).  Without
        one, falls back to the generic strided-chunk split."""
        _check_shard(index, count)
        if count == 1:
            return self
        if self._shard_factory is not None:
            return IterSource(self._shard_factory(index, count),
                              dim=self.dim)
        return _StridedShard(self, index, count)

    def chunks(self, chunk_points: int) -> Iterator[np.ndarray]:
        buf: list[np.ndarray] = []
        have = 0
        for piece in self._factory():
            piece = np.asarray(piece)
            if piece.ndim != 2:
                raise ValueError(
                    f"IterSource: every piece must be (m, dim), got shape "
                    f"{tuple(piece.shape)}")
            if self.dim is None:
                self.dim = int(piece.shape[1])
            elif piece.shape[1] != self.dim:
                raise ValueError(
                    f"IterSource: piece dim {piece.shape[1]} != source dim "
                    f"{self.dim}")
            while piece.shape[0]:
                take = min(chunk_points - have, piece.shape[0])
                buf.append(piece[:take])
                have += take
                piece = piece[take:]
                if have == chunk_points:
                    yield (buf[0] if len(buf) == 1
                           else np.concatenate(buf, axis=0))
                    buf, have = [], 0
        if have:
            yield buf[0] if len(buf) == 1 else np.concatenate(buf, axis=0)


class SyntheticSource(DataSource):
    """Paper-style Gaussian blobs, generated chunk by chunk.

    Cluster centers are drawn once from ``seed``; chunk ``i``'s points are
    drawn from ``(seed, i)`` — fully deterministic and identical across the
    executor's multiple passes, with no more than one chunk of points ever
    resident on the host.  This is how the 5M-point benchmarks run on
    machines whose RAM could not hold the flat array.
    """

    def __init__(self, n_points: int, dim: int = 2,
                 n_clusters: Optional[int] = None, seed: int = 0,
                 spread: float = 0.04):
        self.n_points = int(n_points)
        self.dim = int(dim)
        self.n_clusters = n_clusters or max(2, n_points // 500)
        self.seed = seed
        self.spread = spread
        rng = np.random.default_rng(seed)
        self.centers = rng.uniform(
            0.0, 10.0, (self.n_clusters, dim)).astype(np.float32)

    def _chunk(self, i: int, chunk_points: int) -> np.ndarray:
        """Chunk ``i`` of the ``chunk_points`` traversal — addressable by
        index, deterministic per (seed, i), which is what makes both the
        executor's multiple passes and :meth:`shard` exact."""
        start = i * chunk_points
        m = min(chunk_points, self.n_points - start)
        rng = np.random.default_rng((self.seed, 1 + i))
        ids = rng.integers(0, self.n_clusters, m)
        return (self.centers[ids]
                + rng.normal(0.0, self.spread * 10.0, (m, self.dim))
                ).astype(np.float32)

    def chunks(self, chunk_points: int) -> Iterator[np.ndarray]:
        for i in range(-(-self.n_points // chunk_points)):
            yield self._chunk(i, chunk_points)

    def shard(self, index: int, count: int) -> DataSource:
        """Chunk-index partition: shard ``i`` generates exactly the chunks
        ``i, i+count, ...`` of the parent traversal — unlike the generic
        strided fallback, skipped chunks are never synthesized, so ``n``
        shards cost the same total work as one full traversal."""
        _check_shard(index, count)
        if count == 1:
            return self
        return _SyntheticShard(self, index, count)


class _SyntheticShard(DataSource):
    """Every ``count``-th chunk of a :class:`SyntheticSource`, generated
    directly by chunk index — skipped chunks are never materialized, and
    chunk ``j``'s bytes are identical to the parent's chunk ``j``."""

    def __init__(self, parent: SyntheticSource, index: int, count: int):
        self.parent = parent
        self.index = index
        self.count = count
        # the executor sizes shard chunks by count, not by a row total
        self.n_points = None

    @property
    def dim(self) -> int:
        return self.parent.dim

    def chunks(self, chunk_points: int) -> Iterator[np.ndarray]:
        n_chunks = -(-self.parent.n_points // chunk_points)
        for j in range(self.index, n_chunks, self.count):
            yield self.parent._chunk(j, chunk_points)


def as_source(x) -> DataSource:
    """Coerce to a :class:`DataSource`: sources pass through, 2-D arrays
    (numpy or jax) auto-wrap into :class:`ArraySource`."""
    if isinstance(x, DataSource):
        return x
    if hasattr(x, "ndim") and hasattr(x, "shape"):
        return ArraySource(x)
    raise TypeError(
        f"as_source: expected a DataSource or a (n, d) array, got "
        f"{type(x).__name__} (wrap host iterators in IterSource)")


def _device_resident(x, device) -> bool:
    """True when ``x`` is already a single-device jax array that a
    ``jax.device_put`` would leave untouched — committed to ``device``
    when one is requested, anywhere when the placement is unconstrained."""
    if not isinstance(x, jax.Array) or len(x.devices()) != 1:
        return False
    if device is None:
        return True
    return bool(x.committed) and next(iter(x.devices())) == device


def prefetch_to_device(chunks: Iterable, depth: int = 2, *,
                       device=None) -> Iterator[Array]:
    """Double-buffered host→device pipeline.

    Keeps up to ``depth`` chunks in flight: each is handed to
    ``jax.device_put`` (which enqueues the H2D copy asynchronously on
    accelerators) before the previous chunk's compute is consumed, so
    host-side chunk preparation (memmap reads, re-batching, synthesis)
    overlaps device compute.  ``depth=1`` degenerates to plain sequential
    transfer.  At most ``depth`` chunks are resident at once — this bound
    is what the out-of-core accounting (``ChunkStats``) reports.

    ``device`` pins every transfer to a specific device (the sharded
    executor gives each shard its own device this way).  Chunks that are
    already single-device jax arrays in the right place are yielded as-is
    instead of paying a redundant copy — the ``ArraySource``-over-jax-array
    case.  Each transfer's dispatch is the host span ``feed`` on the
    profiler's clock (``device_put`` may return before the copy ends).
    """
    if depth < 1:
        raise ValueError(f"prefetch_to_device: depth must be >= 1, "
                         f"got {depth}")

    def _put(x):
        if _device_resident(x, device):
            return x
        with scope("feed"):
            return jax.device_put(x, device)

    it = iter(chunks)
    buf: collections.deque = collections.deque()
    try:
        while len(buf) < depth:
            buf.append(_put(next(it)))
    except StopIteration:
        pass
    while buf:
        # refill AFTER the consumer resumes (not before the yield): during
        # the consumer's compute exactly depth chunks are alive — the
        # yielded one plus depth-1 buffered — honoring the documented bound
        yield buf.popleft()
        try:
            buf.append(_put(next(it)))
        except StopIteration:
            pass
