"""`SampledKMeans` — the estimator facade over the paper's pipeline, with a
plan/execute split.

One declarative :class:`~repro.core.spec.ClusterSpec` drives every engine in
the repo; :func:`plan` resolves it ONCE (execution mode, Lloyd backend,
registry lookups) into an :class:`ExecutionPlan`, and :func:`execute` runs
the plan:

    from repro.api import SampledKMeans
    from repro.core import ClusterSpec, MergeSpec, PartitionSpec

    spec = ClusterSpec(merge=MergeSpec(k=40),
                       partition=PartitionSpec(scheme="equal", n_sub=16))
    est = SampledKMeans(spec).fit(x)        # == sampled_kmeans(x, spec=spec)
    labels = est.predict(x)
    for chunk in stream:                    # or: incremental
        est.partial_fit(chunk)

Execution modes (``spec.execution.mode``):

  ``single``     the one-device vmap pipeline (`core.pipeline.fit_from_spec`)
  ``shard_map``  the pod-scale mesh version (`core.distributed`) — pass
                 ``mesh=`` to the estimator / planner
  ``stream``     the incremental coreset engine (`stream.engine`); ``fit``
                 feeds the data chunk-wise, ``partial_fit`` is one update
  ``chunked``    the out-of-core executor (`core.pipeline.fit_chunked`) —
                 the data arrives as a DataSource, chunk by chunk
  ``chunked_dist``  out-of-core × multi-device
                 (`core.distributed.fit_chunked_dist`): one source shard
                 per mesh device, pools merged across the mesh
  ``auto``       ``chunked_dist`` when a mesh AND a non-resident source are
                 supplied, ``shard_map`` for a mesh with resident data,
                 ``chunked`` for a non-resident source, else ``single``

``fit`` under ``single`` reproduces ``sampled_kmeans(x, spec=spec)``
bit-for-bit under the same PRNG key: both run the identical
``fit_from_spec`` trace.  The shard_map and stream paths are likewise the
exact engines their direct entry points build — the facade adds dispatch,
not computation.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.backend import LloydBackend, get_backend
from repro.core.kmeans import get_init, pairwise_sqdist
from repro.core.metrics import map_row_blocks, min_sqdist
from repro.core.pipeline import (ChunkStats, SampledClusteringResult,
                                 fit_chunked, fit_from_spec, sse_pass)
from repro.core.spec import ClusterSpec
from repro.core.subcluster import get_partitioner
from repro.data.source import ArraySource, DataSource, as_source
from repro.telemetry import NULL, RunLogger, get_run_logger

Array = jax.Array

# default row-block for the predict-side surfaces (transform/score): the
# working set stays O(block · k) however large the query set is
PREDICT_BLOCK = 16384


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """A resolved spec: concrete mode, one backend instance, validated
    registry entries.  ``schedule`` is the reduce-tree level schedule
    (base stage + ``spec.levels``) the planner validated, exposed for
    introspection/sizing — the executors themselves derive the identical
    schedule from ``spec`` (the spec is the jit-static source of truth).
    Build with :func:`plan`, run with :func:`execute`."""
    spec: ClusterSpec
    mode: str                      # "single" | "shard_map" | "stream"
    backend: LloydBackend          # resolved once, shared by every stage
    mesh: Optional[jax.sharding.Mesh] = None
    data_shape: Optional[tuple] = None
    schedule: tuple = ()           # tuple[LevelSpec, ...], base level first
    logger: RunLogger = NULL       # resolved spec.execution.telemetry

    @property
    def k(self) -> int:
        return self.spec.merge.k

    @property
    def n_levels(self) -> int:
        return len(self.schedule)


def plan(spec: ClusterSpec, data_shape: Optional[tuple] = None, *,
         mesh: Optional[jax.sharding.Mesh] = None,
         source: Optional[DataSource] = None,
         logger: "RunLogger | str | None" = None) -> ExecutionPlan:
    """Resolve a declarative spec into an executable plan.

    Validates every registry name (partitioner, init schemes, backend) up
    front — a typo fails here with the known-names list, not deep inside a
    jit trace — and picks the execution mode: an explicit
    ``spec.execution.mode`` wins; ``"auto"`` selects ``shard_map`` when a
    mesh is supplied, ``chunked`` when ``source`` is a non-resident
    :class:`~repro.data.source.DataSource` (anything but an ArraySource),
    and ``single`` otherwise.  ``data_shape`` (the (M, d) of the points,
    when known) is recorded for downstream sizing and lets the planner
    reject shard_map runs whose rows don't divide over the mesh and
    chunked runs whose chunk schedule starves the merge.
    """
    # registry validation: fail fast, with the known-names list (the extra
    # reduce levels resolve against the same partitioner/init registries)
    get_partitioner(spec.partition.scheme)
    get_init(spec.local.init)
    get_init(spec.merge.init)
    for lvl in spec.levels:
        get_partitioner(lvl.scheme)
        get_init(lvl.init)
    backend = get_backend(spec.execution.backend)
    # tile-tuned backends resolve their schedule at plan time like every
    # other registry decision: key the backend on the spec's merge K, and
    # pull this job's tile config through the autotune cache layers into
    # the in-process LRU so the first jit trace is a pure memory hit
    if hasattr(backend, "with_k_hint"):
        backend = backend.with_k_hint(spec.merge.k)
        if data_shape is not None and len(data_shape) >= 2:
            from repro.kernels import autotune
            autotune.prewarm("lloyd", m=int(data_shape[0]),
                             d=int(data_shape[1]), k=spec.merge.k)
    if data_shape is not None and len(data_shape) >= 2:
        # a (K, d) the kernels cannot hold fails here, before any stage
        # runs: the merge's K in every mode, and each level's local K
        # where the pool accounting is exact (single mode)
        m, d = int(data_shape[0]), int(data_shape[1])
        ks = [spec.merge.k]
        if spec.execution.mode in ("single", "auto") and mesh is None \
                and source is None:
            ks += [n // lv.n_sub for n, lv in
                   zip(spec.pool_schedule(m), spec.level_schedule())]
        for k in ks:
            backend.check_fit(m, d, k)
    # telemetry resolves like the backend: the declarative string becomes a
    # live RunLogger exactly once, here
    run_logger = get_run_logger(logger if logger is not None
                                else spec.execution.telemetry)
    schedule = spec.level_schedule()

    mode = spec.execution.mode
    non_resident = source is not None and not isinstance(source, ArraySource)
    if mode == "auto":
        if mesh is not None and non_resident:
            mode = "chunked_dist"   # both axes: shard the source over the mesh
        elif mesh is not None:
            mode = "shard_map"
        elif non_resident:
            mode = "chunked"
        else:
            mode = "single"
    if (mode == "chunked" and data_shape is not None and data_shape[0]
            and spec.chunked_pool_schedule(int(data_shape[0]))[-1]
            < spec.merge.k):
        raise ValueError(
            f"plan: the chunked schedule leaves only "
            f"{spec.chunked_pool_schedule(int(data_shape[0]))[-1]} "
            f"representatives for a k={spec.merge.k} merge — use larger "
            f"chunks, drop a level, or lower its compression (chunked pool "
            f"schedule: {spec.chunked_pool_schedule(int(data_shape[0]))})")
    if (mode == "single" and data_shape is not None and len(data_shape) >= 1
            and spec.pool_schedule(int(data_shape[0]))[-1] < spec.merge.k):
        # the equal-scheme pool accounting is exact for single mode; the
        # shard_map pool sizes per device and the stream merge runs on the
        # coreset buffer, so only reject here where the math is certain
        raise ValueError(
            f"plan: the reduce tree leaves only "
            f"{spec.pool_schedule(int(data_shape[0]))[-1]} representatives "
            f"for a k={spec.merge.k} merge — drop a level or lower its "
            f"compression (pool schedule: "
            f"{spec.pool_schedule(int(data_shape[0]))})")
    if mode == "shard_map":
        if mesh is None:
            raise ValueError("plan: mode='shard_map' needs a mesh= "
                             "(see repro.compat.make_mesh)")
        axis = spec.execution.mesh_axis
        if axis not in mesh.axis_names:
            raise ValueError(f"plan: mesh has no {axis!r} axis "
                             f"(axes: {mesh.axis_names})")
        if data_shape is not None:
            n_dev = mesh.shape[axis]
            if data_shape[0] % n_dev:
                raise ValueError(
                    f"plan: {data_shape[0]} rows do not divide over "
                    f"{n_dev} devices along {axis!r}")
    if mode == "chunked_dist":
        if mesh is None:
            raise ValueError("plan: mode='chunked_dist' needs a mesh= "
                             "(see repro.compat.make_mesh)")
        axis = spec.execution.mesh_axis
        if tuple(mesh.axis_names) != (axis,):
            raise ValueError(
                f"plan: mode='chunked_dist' needs a 1-D mesh over the "
                f"{axis!r} axis (spec.execution.mesh_axis), got axes "
                f"{mesh.axis_names}")
        if data_shape is not None and data_shape[0]:
            n = int(data_shape[0])
            n_dev = int(mesh.shape[axis])
            n_chunks = -(-n // spec.chunk.chunk_points)
            if n_chunks < n_dev:
                raise ValueError(
                    f"plan: {n} rows make only {n_chunks} chunks of "
                    f"{spec.chunk.chunk_points} — not enough to feed "
                    f"{n_dev} devices one shard each (shrink chunk_points "
                    f"or the mesh)")
            sched = spec.chunked_dist_pool_schedule(n, n_dev)
            if sched[-1] < spec.merge.k:
                raise ValueError(
                    f"plan: the sharded chunk schedule leaves only "
                    f"{sched[-1]} representatives for a k={spec.merge.k} "
                    f"merge — use larger chunks, drop a level, or lower "
                    f"its compression (per-shard + global schedule: "
                    f"{sched})")
    return ExecutionPlan(spec=spec, mode=mode, backend=backend, mesh=mesh,
                         data_shape=data_shape, schedule=schedule,
                         logger=run_logger)


def execute(pl: ExecutionPlan, x, key: Optional[Array] = None, *,
            return_stats: bool = False):
    """Run a plan on ``x`` — a resident array or a
    :class:`~repro.data.source.DataSource`.  Single and shard_map modes are
    one-shot fits over a resident array (an ArraySource unwraps; other
    sources are rejected — they exist precisely because the data does not
    fit); chunked mode folds the source chunk-by-chunk
    (:func:`repro.core.pipeline.fit_chunked`); chunked_dist splits the
    source into one shard per mesh device
    (:func:`repro.core.distributed.fit_chunked_dist`); stream mode folds
    ``x`` through the incremental engine — as one chunk for arrays,
    chunk-wise for sources (use :class:`SampledKMeans.partial_fit` for
    live feeds).

    Returns a :class:`SampledClusteringResult`; with ``return_stats=True``
    returns ``(result, ChunkStats | ChunkDistStats | None)`` — the
    out-of-core accounting for the chunked modes, ``None`` for the
    resident modes."""
    if key is None:
        key = jax.random.PRNGKey(0)
    if pl.mode == "chunked":
        res, stats = fit_chunked(as_source(x), pl.spec, key,
                                 backend=pl.backend, logger=pl.logger)
        return (res, stats) if return_stats else res
    if pl.mode == "chunked_dist":
        from repro.core.distributed import fit_chunked_dist
        res, stats = fit_chunked_dist(as_source(x), pl.spec, pl.mesh, key,
                                      backend=pl.backend, logger=pl.logger)
        return (res, stats) if return_stats else res
    if return_stats:
        return execute(pl, x, key), None
    if isinstance(x, DataSource) and pl.mode != "stream":
        if not isinstance(x, ArraySource):
            raise ValueError(
                f"execute: mode={pl.mode!r} needs a resident array, but the "
                f"input is a {type(x).__name__} — use mode='chunked' (or "
                f"'auto') for out-of-core sources")
        x = x.array
    if pl.mode == "single":
        if pl.spec.execution.donate:
            # under jit the host-side stage timers inside fit_from_spec
            # disable themselves (trace-time noise; its scopes stay in the
            # program); the compiled call is the "fit" stage out here
            fit = jax.jit(fit_from_spec,
                          static_argnames=("spec", "backend"),
                          donate_argnums=0)
            with pl.logger.stage("fit", n=int(x.shape[0]),
                                 k=pl.spec.merge.k) as st:
                return st.ready(fit(x, pl.spec, key, backend=pl.backend))
        return fit_from_spec(x, pl.spec, key, backend=pl.backend,
                             logger=pl.logger)
    if pl.mode == "shard_map":
        from repro.core.distributed import make_distributed_sampled_kmeans
        fn = make_distributed_sampled_kmeans(pl.mesh, spec=pl.spec,
                                             backend=pl.backend,
                                             logger=pl.logger)
        res = fn(x, key)
        return SampledClusteringResult(
            centers=res.centers, sse=res.sse, local_centers=res.local_centers,
            local_weights=res.local_weights,
            n_dropped=jnp.asarray(0, jnp.int32))
    if pl.mode == "stream":
        from repro.stream.engine import StreamConfig, StreamingClusterer
        sc = StreamingClusterer(StreamConfig.from_spec(pl.spec),
                                backend=pl.backend, logger=pl.logger)
        if isinstance(x, DataSource):
            state = None
            for chunk in x.chunks(pl.spec.chunk.chunk_points):
                chunk = jnp.asarray(chunk)
                if state is None:
                    state = sc.init(dim=chunk.shape[-1], key=key,
                                    dtype=chunk.dtype)
                state = sc.update(state, chunk)
            if state is None:
                raise ValueError("execute: the source yielded no chunks")
            total = sse_pass(x, state.centers, pl.spec.chunk.chunk_points,
                             prefetch=pl.spec.chunk.prefetch)
        else:
            state = sc.init(dim=x.shape[-1], key=key, dtype=x.dtype)
            state = sc.update(state, x)
            _, total = sc.query(state, x)
        return SampledClusteringResult(
            centers=state.centers, sse=total, local_centers=state.coreset,
            local_weights=state.coreset_w, n_dropped=jnp.asarray(0, jnp.int32))
    raise ValueError(f"unknown plan mode {pl.mode!r}")


class SampledKMeans:
    """Estimator-style facade: one object, every execution mode.

    Stateful in the sklearn sense (``fit`` populates ``centers_``, ``sse_``,
    ``result_``; ``partial_fit`` keeps a live stream state) but every
    underlying computation is the repo's pure-functional machinery.

    Parameters
    ----------
    spec:        the declarative job (or an int — shorthand for
                 ``ClusterSpec.make(k)``)
    mesh:        optional device mesh; enables/steers shard_map mode
    buffer_size, decay: stream-engine knobs used by ``partial_fit`` (and by
                 ``fit`` under ``mode="stream"``)
    logger:      a :class:`repro.telemetry.RunLogger` instance or registry
                 name; overrides ``spec.execution.telemetry`` for every
                 fit/partial_fit this estimator runs
    """

    def __init__(self, spec: ClusterSpec | int, *,
                 mesh: Optional[jax.sharding.Mesh] = None,
                 buffer_size: int = 1024, decay: float = 0.97,
                 logger: "RunLogger | str | None" = None):
        if isinstance(spec, int):
            spec = ClusterSpec.make(spec)
        self.spec = spec
        self.mesh = mesh
        self.logger = get_run_logger(logger if logger is not None
                                     else spec.execution.telemetry)
        self._stream_overrides = dict(buffer_size=buffer_size, decay=decay)
        self._clusterer = None      # lazy StreamingClusterer for partial_fit
        self._stream_state = None
        self.result_: Optional[SampledClusteringResult] = None
        self.centers_: Optional[Array] = None
        self.sse_: Optional[Array] = None
        self.chunk_stats_: Optional[ChunkStats] = None

    # -- planning ---------------------------------------------------------
    def plan(self, data_shape: Optional[tuple] = None, *,
             source: Optional[DataSource] = None) -> ExecutionPlan:
        return plan(self.spec, data_shape, mesh=self.mesh, source=source,
                    logger=self.logger)

    @property
    def backend(self) -> LloydBackend:
        return self.plan().backend

    # -- one-shot fit -----------------------------------------------------
    def fit(self, x, key: Optional[Array] = None) -> "SampledKMeans":
        """One-shot fit of ``x``: a resident ``(n, d)`` array (any mode) or
        a :class:`~repro.data.source.DataSource` (out-of-core; ``auto``
        resolves non-resident sources to ``chunked``, or ``chunked_dist``
        when the estimator also has a ``mesh``).  Always starts
        fresh: any live ``partial_fit`` stream state is discarded, so a
        later ``partial_fit`` begins a new stream.

        On the profiler's host trace the call is the span
        ``SampledKMeans.fit``, with ``plan`` and the executor's stages
        (``fit``: the dispatch of the compiled fit) inside it."""
        with jax.profiler.TraceAnnotation("SampledKMeans.fit"):
            return self._fit(x, key)

    def _fit(self, x, key: Optional[Array]) -> "SampledKMeans":
        src = x if isinstance(x, DataSource) else None
        with jax.profiler.TraceAnnotation("plan"):
            if src is not None:
                pl = self.plan(src.shape, source=src)
            else:
                pl = self.plan(tuple(x.shape))
        self._reset_stream()    # fit is a fresh estimator state, every mode
        self.chunk_stats_ = None
        if pl.mode == "stream":
            # honor the stream-only knobs by going through partial_fit
            if src is None:
                return self.partial_fit(x, key=key)
            for chunk in src.chunks(self.spec.chunk.chunk_points):
                self.partial_fit(jnp.asarray(chunk), key=key)
            if self.centers_ is None:
                raise ValueError("fit: the source yielded no chunks")
            # unlike partial_fit (which leaves sse_ stale on purpose), a
            # completed fit always reports quality — one chunked pass
            self.sse_ = sse_pass(src, self.centers_,
                                 self.spec.chunk.chunk_points,
                                 prefetch=self.spec.chunk.prefetch)
            return self
        self.result_, self.chunk_stats_ = execute(pl, x, key,
                                                  return_stats=True)
        self.centers_ = self.result_.centers
        self.sse_ = self.result_.sse
        return self

    def fit_predict(self, x: Array,
                    key: Optional[Array] = None) -> Array:
        return self.fit(x, key).predict(x)

    # -- incremental fit --------------------------------------------------
    def _reset_stream(self):
        self._clusterer = None
        self._stream_state = None

    def partial_fit(self, chunk: Array,
                    key: Optional[Array] = None) -> "SampledKMeans":
        """Fold one chunk through the streaming engine (delegates to
        :class:`repro.stream.StreamingClusterer`).  The first call
        initialises the stream state; chunks must keep a fixed size (the
        update is jit-compiled per shape)."""
        from repro.stream.engine import StreamConfig, StreamingClusterer
        if self._clusterer is None:
            cfg = StreamConfig.from_spec(self.spec,
                                         **self._stream_overrides)
            self._clusterer = StreamingClusterer(cfg, logger=self.logger)
            self._stream_state = self._clusterer.init(
                dim=chunk.shape[-1], key=key, dtype=chunk.dtype)
        self._stream_state = self._clusterer.update(self._stream_state,
                                                    chunk)
        self.centers_ = self._stream_state.centers
        self.sse_ = None   # stale until the next score()/fit()
        return self

    @property
    def stream_state(self):
        return self._stream_state

    # -- inference --------------------------------------------------------
    def _check_fitted(self):
        if self.centers_ is None:
            raise RuntimeError("SampledKMeans: call fit/partial_fit first")

    def predict(self, x, *, block: int | None = PREDICT_BLOCK) -> Array:
        """Nearest-center id per point (through the planned backend).

        Memory-bounded like ``transform``/``score``: the assignment runs
        ``block`` rows at a time (O(block · k) working set, the dense
        evaluation's labels up to rounding ties; ``block=None`` forces the
        dense path).  Accepts a resident array or a
        :class:`~repro.data.source.DataSource` (assigned chunk-by-chunk,
        so ``fit_predict`` works out-of-core — only the (n,) label vector
        materializes)."""
        self._check_fitted()
        be = self.plan().backend
        if isinstance(x, DataSource):
            parts = [be.assign_points(jnp.asarray(c), self.centers_,
                                      block=block)[0]
                     for c in x.chunks(self.spec.chunk.chunk_points)]
            if not parts:
                raise ValueError("predict: the source yielded no chunks")
            return parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        idx, _ = be.assign_points(x, self.centers_, block=block)
        return idx

    def transform(self, x: Array, *, block: int = PREDICT_BLOCK) -> Array:
        """(m, k) squared distances to the fitted centers.

        Computed ``block`` rows at a time so the peak *intermediate*
        working set is O(block · k) however many points are scored (the
        (m, k) return value is inherent); the dense values up to
        :data:`repro.core.metrics.BLOCKED_RTOL`."""
        self._check_fitted()
        return map_row_blocks(
            x, lambda b: pairwise_sqdist(b, self.centers_), block)

    def score(self, x: Array, *, block: int = PREDICT_BLOCK) -> Array:
        """Negative SSE of ``x`` under the fitted centers (larger is
        better, sklearn convention).  Memory-bounded: the nearest-center
        reduction runs ``block`` rows at a time — no (m, k) distance
        matrix materializes."""
        self._check_fitted()
        return -jnp.sum(min_sqdist(x, self.centers_, block=block))

    def __repr__(self):
        fitted = "fitted" if self.centers_ is not None else "unfitted"
        return (f"<SampledKMeans k={self.spec.merge.k} "
                f"mode={self.spec.execution.mode} {fitted}>")
