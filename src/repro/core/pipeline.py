"""The paper's end-to-end method: partition -> local k-means -> merge k-means.

The method is factored into pure, reusable **stage functions** that every
executor composes instead of re-implementing:

  ``chunk_fold``   partition one (feature-scaled) block of points and run
                   the vmap'd local k-means on it — the paper's "device
                   part" as a unit of work over ONE chunk;
  ``reduce_pool``  one level of the hierarchical reduce tree over a
                   weighted center pool;
  ``merge_pool``   the merge ("host part") k-means over a weighted pool;
  ``scale_pass``   streaming per-attribute min/max (the feature-scale
                   parameters without a resident array);
  ``sse_pass``     chunked exact SSE of a source against fitted centers.

:func:`fit_from_spec` composes them over one resident array (the host
semantics of the paper); :func:`fit_chunked` composes the *same* stages
over a :class:`repro.data.source.DataSource` so the dataset only ever
exists chunk-by-chunk (``mode="chunked"`` — the out-of-core executor);
:mod:`repro.core.distributed` wraps the stages in shard_map for pod scale;
:mod:`repro.stream.engine` folds them incrementally; and :mod:`repro.api`
dispatches between all four.  ``sampled_kmeans`` / ``standard_kmeans``
remain as thin adapters that build a :class:`~repro.core.spec.ClusterSpec`
internally from the historical flat kwargs.
"""
from __future__ import annotations

import dataclasses
import functools
import time
import warnings
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

_now = time.perf_counter

from .backend import BackendSpec, get_backend
from .kmeans import KMeansResult, kmeans
from .metrics import sse as sse_fn
from .spec import ClusterSpec, LevelSpec, MergeSpec, StopSpec
from .subcluster import (Partition, feature_scale, gather_partitions,
                         get_partitioner, unscale)
from repro.telemetry import NULL, get_run_logger, peak_rss_mb, scope

Array = jax.Array

# per-chunk PRNG stream: chunk 0 reuses the base local key verbatim (the
# single-chunk bit-for-bit parity pin with fit_from_spec); later chunks fold
# in a large offset so they can never collide with the reduce-level streams
# fold_in(key_local, 1 + level_index)
_CHUNK_KEY_OFFSET = 1_000_003
# shard s > 0 of the sharded executor derives every per-device stream from
# fold_in(key_local, _SHARD_KEY_OFFSET + s); shard 0 reuses key_local's
# streams verbatim — the 1-device/1-shard bit-for-bit parity pin
_SHARD_KEY_OFFSET = 5_000_011
# bounded-accumulator flushes get their own stream so an early fold of
# pending chunk pools can never collide with the final reduce levels
_FLUSH_KEY_OFFSET = 7_000_003


class SampledClusteringResult(NamedTuple):
    centers: Array          # (k, d) final centers, in the *input* space
    sse: Array              # () SSE of the input points vs final centers
    local_centers: Array    # (pool, d) the representatives the merge saw
    #                         (P * k_local for the flat pipeline; the last
    #                         reduce level's pool when spec.levels is set)
    local_weights: Array    # (pool,) member counts (0 = dead slot)
    n_dropped: Array        # () capacity overflow, in original-point units
    #                         (Algorithm 2 partitions + unequal-scheme
    #                         reduce levels)


def local_stage(
    parts: Array,            # (P, cap, d)
    part_w: Array,           # (P, cap)
    k_local: int,
    *,
    iters: Optional[int] = None,
    key: Array,
    init: str = "kmeans++",
    backend: BackendSpec = None,
    stop: Optional[StopSpec] = None,
) -> KMeansResult:
    """vmap'd per-partition k-means — the paper's "device part".  On the CUDA
    original each subcluster ran on one thread block; here each is one lane of
    a vmap that shard_map spreads across the mesh.  ``stop`` is the canonical
    iteration contract (``iters`` remains as the deprecated fixed-trip
    alias); with ``stop.tol > 0`` each partition is one masked lane of a
    batched ``while_loop`` — converged partitions freeze and the stage exits
    once every lane is done.  The result's ``n_iter`` is the per-partition
    ``(P,)`` true iteration count."""
    n_parts = parts.shape[0]
    keys = jax.random.split(key, n_parts)
    be = get_backend(backend)  # resolve once; vmap batches the prepared data
    return jax.vmap(
        lambda p, w, kk: kmeans(
            p, k_local, weights=w, iters=iters, key=kk, init=init,
            backend=be, stop=stop)
    )(parts, part_w, keys)


def chunk_fold(xs: Array, lv: LevelSpec, key: Array, *,
               backend: BackendSpec = None
               ) -> tuple[Array, Array, Array, Array]:
    """Partition one (already feature-scaled) block of points and summarise
    it with the vmap'd local stage: ``(m, d)`` points ->
    ``(n_sub * k_local, d)`` weighted centers + ``(n_sub * k_local,)``
    member counts + ``()`` dropped-point count (Algorithm 2 overflow)
    + ``()`` Lloyd iterations actually executed, summed over the
    partitions (equals ``n_sub * max_iters`` under the default ``tol=0``
    policy; less when ``lv.stop`` converges partitions early).

    This is the unit of work every executor folds over its data: the batch
    pipeline calls it once on the whole (scaled) array, the chunked
    executor jits it per chunk and accumulates the pools, and the stream
    engine's ``summarize_chunk`` wraps it in per-chunk feature scaling.
    The stage parameters arrive as a :class:`LevelSpec` (the base
    partition/local sections expressed in the reduce-tree vocabulary —
    ``spec.level_schedule()[0]``); its stopping policy is
    ``lv.effective_stop``.
    """
    be = get_backend(backend)
    # the "fold" scope is named here, inside the jit of
    # _fold_scaled_chunk, so the executors that dispatch that jit on its
    # own (chunked, chunked_dist) name it in the device trace too
    with scope("fold"):
        part: Partition = get_partitioner(lv.scheme)(xs, lv.n_sub,
                                                     lv.capacity_factor)
        parts, part_w = gather_partitions(xs, part)
        cap = parts.shape[1]
        k_local = max(1, cap // lv.compression)
        local = local_stage(parts, part_w, k_local, key=key, init=lv.init,
                            backend=be, stop=lv.effective_stop)
        d = xs.shape[-1]
        return (local.centers.reshape(lv.n_sub * k_local, d),
                local.counts.reshape(lv.n_sub * k_local),
                part.n_dropped,
                jnp.sum(local.n_iter).astype(jnp.int32))


def merge_pool(pool: Array, pool_w: Array, merge: MergeSpec, key: Array, *,
               backend: BackendSpec = None) -> KMeansResult:
    """The merge ("host part") k-means over a weighted representative pool.

    ``merge.weighted`` weights each representative by its member count;
    otherwise every live (count > 0) representative votes equally, exactly
    as the paper merges.  Dead pool slots (count 0) carry no weight either
    way.  The iteration contract is ``merge.effective_stop`` — including
    the mini-batch option (``stop.minibatch`` sampled rows per step) for
    huge pools; the result's ``n_iter`` is the true count of the winning
    restart."""
    be = get_backend(backend)
    merge_w = (pool_w if merge.weighted
               else (pool_w > 0).astype(pool.dtype))
    return kmeans(pool, merge.k, weights=merge_w,
                  stop=merge.effective_stop,
                  key=key, init=merge.init, backend=be,
                  restarts=merge.restarts)


def reduce_pool(pool: Array, pool_w: Array, level: LevelSpec, key: Array,
                backend: BackendSpec = None) -> tuple[Array, Array, Array]:
    """One level of the hierarchical reduce tree: re-partition a weighted
    center pool and run the (weighted) local stage on it.

    ``(n, d)`` pool + ``(n,)`` mass -> ``(n', d)`` pool + ``(n',)`` mass
    + ``()`` dropped mass, with ``n' = level.n_sub * max(1, capacity //
    level.compression)``.  Dead entries (mass 0) carry no weight into
    their partition's k-means; a partition made entirely of dead entries
    yields zero-mass representatives that stay dead at the next level.

    Mass conservation: exact under the ``equal`` scheme (every entry gets
    a slot).  The ``unequal`` scheme's capacity bound can drop overflow
    *entries*, and each pool entry stands in for ``pool_w`` original
    points — the third return value is that dropped mass (0.0 for
    ``equal``), which :func:`fit_from_spec` folds into the result's
    ``n_dropped``.
    """
    be = get_backend(backend)
    part = get_partitioner(level.scheme)(pool, level.n_sub,
                                         level.capacity_factor)
    parts, part_w = gather_partitions(pool, part, weights=pool_w)
    w_dropped = jnp.sum(pool_w).astype(jnp.float32) - \
        jnp.sum(part_w).astype(jnp.float32)
    k_local = max(1, parts.shape[1] // level.compression)
    local = local_stage(parts, part_w, k_local, key=key,
                        init=level.init, backend=be,
                        stop=level.effective_stop)
    d = pool.shape[-1]
    return (local.centers.reshape(level.n_sub * k_local, d),
            local.counts.reshape(level.n_sub * k_local),
            jnp.maximum(w_dropped, 0.0))


def _log_stage_iters(log, stage: str, iters_run: int,
                     iters_budget: int) -> None:
    """Telemetry for the convergence contract: how many Lloyd iterations a
    stage actually executed vs its ``max_iters`` budget.  Host-side only —
    callers guard with ``log is not NULL`` so unlogged runs never sync on
    the device scalar."""
    log.event("stage_iters", stage=stage, iters_run=iters_run,
              iters_budget=iters_budget,
              iters_saved=max(0, iters_budget - iters_run))


def fit_from_spec(x: Array, spec: ClusterSpec,
                  key: Optional[Array] = None, *,
                  backend: BackendSpec = None,
                  logger=None) -> SampledClusteringResult:
    """Run the full pipeline as declared by ``spec`` on one device:
    partition -> local k-means -> (optional extra reduce levels over the
    weighted center pool, ``spec.levels``) -> merge.  ``backend`` overrides
    ``spec.execution.backend`` when the caller (e.g. the planner) has
    already resolved an instance; ``logger`` likewise overrides
    ``spec.execution.telemetry`` (a resolved :class:`RunLogger`).

    The stages ``fold``, ``reduce_level``, ``merge`` and ``sse`` are
    logger stages (:meth:`repro.telemetry.RunLogger.stage`): named scopes
    in the device trace always; with a logger, timers that wait for each
    stage's outputs.  A logged fit is bit-for-bit the unlogged fit.  When
    this function is itself traced under ``jax.jit`` (the ``donate``
    path, perf harnesses), host timers would fire once at trace time and
    mean nothing — the logger is disabled in that case (the scopes stay)
    and the *caller* times the compiled call as its ``fit`` stage."""
    if isinstance(x, jax.core.Tracer):
        log = NULL    # tracing: host-side timers would measure the trace
    else:
        log = get_run_logger(logger if logger is not None
                             else spec.execution.telemetry)
    if key is None:
        key = jax.random.PRNGKey(0)
    key_local, key_global = jax.random.split(key)
    be = get_backend(backend if backend is not None
                     else spec.execution.backend)

    t_start = _now()
    d = x.shape[-1]
    if spec.scale:
        lo = jnp.min(x, axis=0)
        span = jnp.maximum(jnp.max(x, axis=0) - lo, 1e-9)
        params = (lo, span)
    else:  # identity scaling: (x - 0) / 1 is bit-exact, one code path
        lo, span = jnp.zeros((d,), x.dtype), jnp.ones((d,), x.dtype)
        params = None

    # the SAME compiled stage the chunked executor folds per chunk — the
    # resident fit is literally the one-chunk schedule, so the out-of-core
    # parity pin holds by construction (for every dtype: sharing the trace
    # sidesteps jit-vs-eager bf16 rounding differences)
    base = spec.level_schedule()[0]
    with log.stage("fold", rows=int(x.shape[0])) as st:
        local_centers, local_counts, n_dropped, fold_iters = st.ready(
            _fold_scaled_chunk(x, lo, span, key_local, lv=base, backend=be))
    if log is not NULL:
        _log_stage_iters(log, "fold", int(fold_iters),
                         base.effective_stop.max_iters * base.n_sub)

    # hierarchical reduce tree: recursively re-partition the weighted center
    # pool until it is small enough for the merge stage (spec.levels is ()
    # for the paper's flat two-level pipeline — the loop is a no-op there)
    for i, lvl in enumerate(spec.levels):
        with log.stage("reduce_level", level=i,
                       pool_in=int(local_centers.shape[0])) as st:
            local_centers, local_counts, w_dropped = st.ready(reduce_pool(
                local_centers, local_counts, lvl,
                jax.random.fold_in(key_local, 1 + i), backend=be))
        # unequal-scheme levels can clamp overflow ENTRIES; each carries
        # the mass of the original points it represents — keep the loss
        # visible in the same n_dropped channel as the base partition
        n_dropped = n_dropped + jnp.round(w_dropped).astype(jnp.int32)

    with log.stage("merge", pool=int(local_centers.shape[0]),
                   k=spec.merge.k) as st:
        merged = st.ready(merge_pool(local_centers, local_counts,
                                     spec.merge, key_global, backend=be))
    if log is not NULL:
        _log_stage_iters(log, "merge", int(merged.n_iter),
                         spec.merge.effective_stop.max_iters)

    centers = merged.centers
    if spec.scale:
        centers = unscale(centers, params)
        local_centers = unscale(local_centers, params)
    with log.stage("sse") as st:
        total_sse = st.ready(sse_fn(x, centers))
    if log is not NULL:
        wall = _now() - t_start   # the stages waited: "result ready"
        log.event("fit_from_spec", n=int(x.shape[0]), d=d, k=spec.merge.k,
                  levels=spec.n_levels, backend=be.name, wall_s=wall,
                  points_per_sec=int(x.shape[0]) / max(wall, 1e-9))
    return SampledClusteringResult(centers, total_sse, local_centers,
                                   local_counts, n_dropped)


# ---------------------------------------------------------------------------
# The out-of-core chunked executor (mode="chunked")
# ---------------------------------------------------------------------------

def minmax_pass(source, chunk_points: int, *, prefetch: int = 2,
                device=None) -> tuple[Optional[Array], Optional[Array]]:
    """Running per-attribute ``(min, max)`` over a source's chunks —
    ``(None, None)`` when the source yields no chunks.  Min/max are exact
    and order-independent, so per-shard partials from the sharded executor
    combine (``jnp.minimum``/``jnp.maximum`` on the host) into exactly the
    whole-source answer.  ``device`` pins the pass's buffers (per-shard
    use)."""
    from repro.data.source import prefetch_to_device
    lo = hi = None
    for chunk in prefetch_to_device(source.chunks(chunk_points), prefetch,
                                    device=device):
        clo, chi = jnp.min(chunk, axis=0), jnp.max(chunk, axis=0)
        lo = clo if lo is None else jnp.minimum(lo, clo)
        hi = chi if hi is None else jnp.maximum(hi, chi)
    return lo, hi


def scale_pass(source, chunk_points: int, *, prefetch: int = 2,
               eps: float = 1e-9) -> tuple[Array, Array]:
    """Streaming feature-scale parameters: one pass of running per-attribute
    min/max over the source's chunks instead of a whole-array
    :func:`feature_scale`.  Returns the same ``(lo, span)`` pair (span
    clamped at ``eps``), bit-for-bit equal to the resident computation when
    the source fits in one chunk."""
    lo, hi = minmax_pass(source, chunk_points, prefetch=prefetch)
    if lo is None:
        raise ValueError("scale_pass: the source yielded no chunks")
    return lo, jnp.maximum(hi - lo, eps)


def sse_pass(source, centers: Array, chunk_points: int, *,
             prefetch: int = 2, device=None) -> Optional[Array]:
    """Chunked exact SSE: the final-accuracy pass of the out-of-core
    executor.  Memory stays O(chunk_points · k); a single-chunk traversal
    is the identical ``sse_fn(x, centers)`` call the batch pipeline makes.
    ``device`` pins the pass to one device (per-shard use, where an empty
    shard legitimately contributes ``None``)."""
    from repro.data.source import prefetch_to_device
    total = None
    for chunk in prefetch_to_device(source.chunks(chunk_points), prefetch,
                                    device=device):
        s = sse_fn(chunk, centers)
        total = s if total is None else total + s
    if total is None and device is None:
        raise ValueError("sse_pass: the source yielded no chunks")
    return total


class ChunkStats(NamedTuple):
    """Out-of-core accounting from one :func:`fit_chunked` run — what the
    acceptance tests use to prove the dataset never sat in one place."""
    n_points: int          # total rows folded through the pipeline
    n_chunks: int          # chunks the fold pass consumed
    max_chunk_points: int  # largest single resident chunk (rows)
    pool_size: int         # representative pool rows the merge stage saw
    prefetch: int          # chunks in flight at once (host→device buffer)
    passes: int            # data passes: fold (+ scale) (+ exact SSE)
    peak_pool_rows: int = 0  # most pool rows ever alive during the fold —
    #                          bounded O(level pool) by the flush
    #                          accumulator, NOT O(n_chunks · pool)


class _PoolAccumulator:
    """Bounded accumulator for the fold pass's per-chunk pools.

    Without reduce levels every chunk pool must survive to the final
    concatenate (the merge needs them all) — but when ``spec.levels`` is
    set, pending chunk pools can be folded early through ``levels[0]``
    (the same :func:`reduce_pool` the final chain applies) once
    :data:`repro.core.spec.CHUNK_FOLD_BUFFER` of them accumulate.  Host
    peak pool memory becomes O(level pool), not O(n_chunks · pool_chunk),
    which is what makes million-chunk runs possible.  ``finalize`` returns
    the concatenated remainder, to which the caller applies the *full*
    level chain — so runs that never flush (fewer than ``CHUNK_FOLD_BUFFER``
    chunks, or no levels) are bit-for-bit what the unbuffered executor
    produced.  Each flush draws from the dedicated
    ``_FLUSH_KEY_OFFSET + shard`` stream, disjoint from the per-chunk and
    per-level streams."""

    def __init__(self, levels, key_local: Array, *, shard: int = 0,
                 backend: BackendSpec = None, log=NULL):
        from repro.core.spec import CHUNK_FOLD_BUFFER
        self._level = levels[0] if levels else None
        self._buffer = CHUNK_FOLD_BUFFER
        self._key_flush = jax.random.fold_in(key_local,
                                             _FLUSH_KEY_OFFSET + shard)
        self._backend = backend
        self._log = log
        self._pools: list = []
        self._ws: list = []
        self._rows = 0
        self.peak_rows = 0
        self.n_flushes = 0
        self.w_dropped: Optional[Array] = None  # flush-time dropped mass

    def add(self, centers: Array, counts: Array) -> None:
        self._pools.append(centers)
        self._ws.append(counts)
        self._rows += int(centers.shape[0])
        self.peak_rows = max(self.peak_rows, self._rows)
        # len - n_flushes = pending chunk pools beyond the folded head
        if (self._level is not None
                and len(self._pools) - (1 if self.n_flushes else 0)
                >= self._buffer):
            self._flush()

    def _concat(self) -> tuple[Array, Array]:
        pool = (self._pools[0] if len(self._pools) == 1
                else jnp.concatenate(self._pools, axis=0))
        pool_w = (self._ws[0] if len(self._ws) == 1
                  else jnp.concatenate(self._ws, axis=0))
        return pool, pool_w

    def _flush(self) -> None:
        pool, pool_w = self._concat()
        rows_in = int(pool.shape[0])
        key = jax.random.fold_in(self._key_flush, self.n_flushes)
        with self._log.stage("pool_flush", flush=self.n_flushes,
                             rows_in=rows_in) as st:
            pool, pool_w, wd = st.ready(reduce_pool(
                pool, pool_w, self._level, key, backend=self._backend))
        self.w_dropped = wd if self.w_dropped is None else self.w_dropped + wd
        self._pools, self._ws = [pool], [pool_w]
        self._rows = int(pool.shape[0])
        self.peak_rows = max(self.peak_rows, self._rows)
        self.n_flushes += 1

    def finalize(self) -> tuple[Array, Array]:
        """Concatenated (pool, weights) of the folded head plus pending
        chunk pools — what the final level chain and merge stage consume."""
        if not self._pools:
            raise ValueError("fold accumulator: no chunk pools were added")
        return self._concat()


@functools.partial(jax.jit, static_argnames=("lv", "backend"))
def _fold_scaled_chunk(chunk: Array, lo: Array, span: Array, key: Array, *,
                       lv: LevelSpec, backend
                       ) -> tuple[Array, Array, Array, Array]:
    """jit wrapper over :func:`chunk_fold` that applies the *global* scale
    parameters to one chunk.  Compiled once per (chunk shape, level spec,
    backend) — with fixed-size chunks that is one trace plus at most one
    ragged tail."""
    return chunk_fold((chunk - lo) / span, lv, key, backend=backend)


def fit_chunked(source, spec: ClusterSpec, key: Optional[Array] = None, *,
                backend: BackendSpec = None, logger=None
                ) -> tuple[SampledClusteringResult, ChunkStats]:
    """Run the full spec-declared pipeline **out of core** over a
    :class:`repro.data.source.DataSource` (anything array-like auto-wraps):
    the dataset only ever exists ``chunk.chunk_points`` rows at a time.

    Passes over the data (all chunked + double-buffered to the device):

      1. ``scale_pass`` — running min/max -> the global feature-scale
         parameters (skipped when ``spec.scale`` is off);
      2. the fold — each chunk is scaled, partitioned and summarised by the
         jitted :func:`chunk_fold`; the weighted center pools accumulate
         (folded early through ``levels[0]`` every ``CHUNK_FOLD_BUFFER``
         pending pools when the spec has reduce levels, so host peak pool
         memory is O(level pool) — ``ChunkStats.peak_pool_rows`` — not
         O(n_chunks · pool)) and per-chunk Algorithm-2 drops accumulate
         into ``n_dropped``; a ragged tail chunk smaller than ``n_sub``
         clamps its partition count to the chunk size so no mandatory
         partition is ever empty;
      3. ``spec.levels`` reduce the accumulated pool and ``merge_pool``
         produces the k global centers — identical code to the resident
         pipeline;
      4. ``sse_pass`` — chunked exact SSE (``spec.chunk.sse="exact"``), or
         a free pool-weighted estimate (``"pool"``, no extra pass).

    Parity pin: a source that fits in ONE chunk reproduces
    :func:`fit_from_spec` bit-for-bit under the same key (chunk 0 reuses
    the base local key; the scale, fold, level, merge, and SSE stages are
    the same functions).  Returns ``(result, ChunkStats)``.

    With a logger (``logger=`` or ``spec.execution.telemetry``) the run
    emits per-stage timers that wait for their stage's outputs, a
    per-chunk ``fold_rate`` series
    (median-window points/sec — one slow tick, e.g. the compile on chunk
    0, does not read as the steady-state rate), and a final summary event
    carrying the :class:`ChunkStats` accounting plus peak RSS.  All of it
    host-side: the logged fit is bit-for-bit the unlogged fit.
    """
    from repro.data.source import as_source, prefetch_to_device
    log = get_run_logger(logger if logger is not None
                         else spec.execution.telemetry)
    source = as_source(source)
    if key is None:
        key = jax.random.PRNGKey(0)
    key_local, key_global = jax.random.split(key)
    be = get_backend(backend if backend is not None
                     else spec.execution.backend)
    cp = spec.chunk.chunk_points
    depth = spec.chunk.prefetch
    base = spec.level_schedule()[0]

    t_start = _now()
    passes = 1
    lo = span = None
    if spec.scale:
        with log.stage("scale_pass") as st:
            lo, span = st.ready(scale_pass(source, cp, prefetch=depth))
        passes += 1

    acc = _PoolAccumulator(spec.levels, key_local, shard=0, backend=be,
                           log=log)
    n_dropped = jnp.asarray(0, jnp.int32)
    fold_iters = jnp.asarray(0, jnp.int32)   # true Lloyd-iteration count
    fold_budget = 0                          # sum of max_iters budgets
    n_points = n_chunks = max_chunk = 0
    fold_rate = log.rate("fold_rate", units="points")
    with log.stage("fold") as st:
        for i, chunk in enumerate(prefetch_to_device(source.chunks(cp),
                                                     depth)):
            m, d = chunk.shape
            if m == 0:
                continue
            if lo is None:  # scale off: identity parameters, same code path
                lo = jnp.zeros((d,), chunk.dtype)
                span = jnp.ones((d,), chunk.dtype)
            lv = (base if m >= base.n_sub
                  else dataclasses.replace(base, n_sub=max(1, m)))
            ck = (key_local if i == 0
                  else jax.random.fold_in(key_local, _CHUNK_KEY_OFFSET + i))
            c, w, nd, ir = _fold_scaled_chunk(chunk, lo, span, ck, lv=lv,
                                              backend=be)
            acc.add(c, w)
            n_dropped = n_dropped + nd
            fold_iters = fold_iters + ir
            fold_budget += lv.effective_stop.max_iters * lv.n_sub
            n_points += m
            n_chunks += 1
            max_chunk = max(max_chunk, m)
            fold_rate.tick(m, chunk=i, rows=m)
        # every chunk's fold adds into fold_iters: ready once all are done
        st.ready(fold_iters)
    if n_chunks == 0:
        raise ValueError("fit_chunked: the source yielded no points")

    pool, pool_w = acc.finalize()
    if acc.w_dropped is not None:   # early flushes can clamp overflow mass
        n_dropped = n_dropped + jnp.round(acc.w_dropped).astype(jnp.int32)

    for j, lvl in enumerate(spec.levels):
        with log.stage("reduce_level", level=j,
                       pool_in=int(pool.shape[0])) as st:
            pool, pool_w, w_dropped = st.ready(reduce_pool(
                pool, pool_w, lvl, jax.random.fold_in(key_local, 1 + j),
                backend=be))
        n_dropped = n_dropped + jnp.round(w_dropped).astype(jnp.int32)

    with log.stage("merge", pool=int(pool.shape[0]),
                   k=spec.merge.k) as st:
        merged = st.ready(merge_pool(pool, pool_w, spec.merge, key_global,
                                     backend=be))
    if log is not NULL:
        _log_stage_iters(log, "fold", int(fold_iters), fold_budget)
        _log_stage_iters(log, "merge", int(merged.n_iter),
                         spec.merge.effective_stop.max_iters)

    centers, local_centers = merged.centers, pool
    if spec.scale:
        centers = unscale(centers, (lo, span))
        local_centers = unscale(local_centers, (lo, span))

    if spec.chunk.sse == "exact":
        with log.stage("sse_pass") as st:
            total_sse = st.ready(sse_pass(source, centers, cp,
                                          prefetch=depth))
        passes += 1
    else:  # "pool": weighted SSE of the representatives, no extra pass
        with log.stage("sse_pool") as st:
            total_sse = st.ready(sse_fn(local_centers, centers,
                                        weights=pool_w))

    result = SampledClusteringResult(centers, total_sse, local_centers,
                                     pool_w, n_dropped)
    stats = ChunkStats(n_points=n_points, n_chunks=n_chunks,
                       max_chunk_points=max_chunk,
                       pool_size=int(pool.shape[0]), prefetch=depth,
                       passes=passes, peak_pool_rows=acc.peak_rows)
    if log is not NULL:
        wall = _now() - t_start   # the stages waited: "result ready"
        log.event("fit_chunked", k=spec.merge.k, levels=spec.n_levels,
                  backend=be.name, wall_s=wall,
                  points_per_sec=n_points / max(wall, 1e-9),
                  peak_rss_mb=peak_rss_mb(), **stats._asdict())
    return result, stats


_SPEC_KWARGS = ("scheme", "n_sub", "compression", "local_iters",
                "global_iters", "init", "weighted_merge", "capacity_factor",
                "scale", "backend", "restarts")


def sampled_kmeans(
    x: Array,
    k: int,
    *,
    spec: Optional[ClusterSpec] = None,
    key: Optional[Array] = None,
    **kwargs,
) -> SampledClusteringResult:
    """Two-level sampled clustering (the paper's full method).

    Thin adapter over :func:`fit_from_spec`: pass ``spec=`` (preferred — see
    :class:`repro.core.spec.ClusterSpec`) or the historical flat kwargs
    (``scheme=``, ``n_sub=``, ``compression=``, ... — deprecated spellings
    that build the same spec internally).  ``compression`` is the paper's
    `c`: every partition of N points is summarised by ``N // c`` local
    centers.
    """
    if spec is not None:
        if kwargs:
            raise TypeError(
                f"sampled_kmeans: pass either spec= or flat kwargs, not "
                f"both (got {sorted(kwargs)})")
        if spec.merge.k != k:
            raise ValueError(
                f"sampled_kmeans(k={k}) disagrees with spec.merge.k="
                f"{spec.merge.k}")
    else:
        unknown = set(kwargs) - set(_SPEC_KWARGS)
        if unknown:
            raise TypeError(
                f"sampled_kmeans: unknown kwargs {sorted(unknown)}")
        if kwargs:
            warnings.warn(
                "sampled_kmeans(scheme=, n_sub=, compression=, ...) flat "
                "kwargs are deprecated: build a ClusterSpec (see "
                "repro.core.spec) and pass spec= — or use the "
                "repro.api.SampledKMeans facade",
                DeprecationWarning, stacklevel=2)
        spec = ClusterSpec.make(k, **kwargs)
    return fit_from_spec(x, spec, key)


def standard_kmeans(
    x: Array, k: int, *, iters: int = 25, key: Optional[Array] = None,
    init: str = "kmeans++", scale: bool = True,
    backend: BackendSpec = None, restarts: int = 4,
    spec: Optional[ClusterSpec] = None,
) -> SampledClusteringResult:
    """The baseline the paper compares against (plain Lloyd on all points),
    wrapped to return the same result type.  With ``spec=`` the merge and
    execution sections supply (stop, init, restarts, backend, scale) —
    the baseline is the merge stage run on the raw points."""
    stop = None
    if spec is not None:
        if spec.merge.k != k:
            raise ValueError(
                f"standard_kmeans(k={k}) disagrees with spec.merge.k="
                f"{spec.merge.k}")
        iters, stop = None, spec.merge.effective_stop
        init, restarts = spec.merge.init, spec.merge.restarts
        backend, scale = spec.execution.backend, spec.scale
    if key is None:
        key = jax.random.PRNGKey(0)
    xs, params = feature_scale(x) if scale else (x, None)
    res = kmeans(xs, k, iters=iters, key=key, init=init, backend=backend,
                 restarts=restarts, stop=stop)
    centers = unscale(res.centers, params) if scale else res.centers
    return SampledClusteringResult(
        centers, sse_fn(x, centers), centers, res.counts,
        jnp.asarray(0, jnp.int32))
