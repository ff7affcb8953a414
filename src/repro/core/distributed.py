"""Pod-scale version of the paper's parallel clustering.

Mapping of the paper's CUDA execution model onto a TPU mesh:

  CUDA host  -> the shard_map *program* (partitioning is done on-device,
                vectorized — see subcluster.py docstring)
  CUDA block -> one mesh device running a *batch* of subclusters via vmap
  block SMEM -> VMEM tiles inside the Pallas assignment kernel
  host merge -> either a replicated merge k-means after an all_gather of the
                local centers (paper-faithful, ``merge='replicated'``) or a
                fully distributed merge where only the k global centers are
                exchanged per Lloyd round (``merge='distributed'``,
                beyond-paper — collective bytes drop from O(M/c · d) to
                O(k · d · iters)).

Straggler mitigation falls out of the fixed-iteration Lloyd loop (every
subcluster costs the same — no data-dependent tail) plus equal-capacity
partitions; elastic scaling falls out of axis-name-based specs (the same code
runs on any mesh that has a ``data`` axis).

With ``spec.levels`` set, the hierarchical reduce tree runs *between* the
local stage and the merge: each extra level re-partitions the device's own
weighted center pool and shrinks it with another round of weighted local
k-means — entirely collective-free — so the merge's all_gather moves the
last (smallest) pool instead of all ``P_total * k_local`` representatives.
"""
from __future__ import annotations

import dataclasses
import functools
import time
import warnings
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import compat

from .backend import BackendSpec, LloydBackend, get_backend
from .kmeans import _centers_from_stats, _stop_update, kmeans, \
    pairwise_sqdist
from .pipeline import (SampledClusteringResult, _CHUNK_KEY_OFFSET,
                       _SHARD_KEY_OFFSET, _PoolAccumulator,
                       _fold_scaled_chunk, _log_stage_iters, merge_pool,
                       minmax_pass, reduce_pool, sse_pass)
from .metrics import sse as sse_fn
from .spec import ClusterSpec, StopSpec
from .subcluster import gather_partitions, get_partitioner, unscale
from repro.telemetry import NULL, get_run_logger, peak_rss_mb, scope

_now = time.perf_counter

Array = jax.Array


class DistributedClusteringResult(NamedTuple):
    centers: Array        # (k, d) — replicated, in the *input* space
    local_centers: Array  # (pool, d) — gathered representatives the merge
    #                       saw, input space (P_total * k_local flat; the
    #                       last reduce level's gathered pool with levels)
    local_weights: Array  # (pool,)
    sse: Array            # () global SSE, input space


def _global_feature_scale(xs: Array, axis: str, eps: float = 1e-9):
    lo = jax.lax.pmin(jnp.min(xs, axis=0), axis)
    hi = jax.lax.pmax(jnp.max(xs, axis=0), axis)
    span = jnp.maximum(hi - lo, eps)
    return (xs - lo) / span, (lo, span)


def _distributed_merge(
    local_centers: Array,    # per-device (n_local, d)
    local_w: Array,          # per-device (n_local,)
    k: int,
    stop: StopSpec,
    key: Array,
    axis: str,
    backend: LloydBackend,
) -> tuple[Array, Array]:
    """Merge-stage k-means with the *points* (= local centers) left sharded.

    Each Lloyd round: one ``backend.step`` over this device's centers (raw
    weighted sums/counts — with the fused backend that is a single pass and
    no HBM one-hot), one psum of (k*d + k + 1) floats, replicated update.
    ``stop`` is the iteration contract: ``tol=0`` keeps the static
    fixed-trip ``fori_loop`` (bit-for-bit the pre-StopSpec path);
    ``tol>0`` runs a ``while_loop`` whose convergence scalar is the
    *psum'd* global SSE — identical on every device, so all devices take
    the same trip count and the collective schedule stays in lockstep.
    (``stop.minibatch`` does not apply here; the replicated merge path
    supports it.)  Returns ``(centers, n_iter)``, both replicated.
    """
    # Replicated init: gather a candidate pool and run greedy farthest-point
    # (k-center) selection — identical on every device (the key is
    # replicated, so the jitter fallback below is too).  Stride across this
    # device's local centers so the pool spans every partition (partition
    # 0's centers all sit near the landmark L).
    n_local = local_centers.shape[0]
    n_cand = min(n_local, max(2 * k, 8))
    stride_ids = jnp.round(jnp.linspace(0, n_local - 1, n_cand)).astype(jnp.int32)
    cand = jax.lax.all_gather(local_centers[stride_ids], axis, tiled=True)
    cand_w = jax.lax.all_gather(local_w[stride_ids], axis, tiled=True)

    # Jitter scale for the exhausted-pool fallback: when the gathered pool
    # holds fewer than k live distinct candidates, greedy selection would
    # silently emit duplicate rows (= permanently dead clusters under the
    # keep-old-center fix-up).  Spread the surplus picks with noise scaled
    # to the candidates' per-dimension spread instead (the same remedy
    # kmeans(restarts>1, init=<Array>) applies to degenerate array inits).
    sigma = (0.05 * jnp.std(cand, axis=0) + 1e-6).astype(cand.dtype)

    def pick(i, carry):
        centers, min_d = carry
        score = jnp.where(cand_w > 0, min_d, -1.0)
        nxt = jnp.argmax(score)
        c = cand[nxt]
        exhausted = score[nxt] <= 0.0   # no live candidate adds spread
        noise = sigma * jax.random.normal(jax.random.fold_in(key, i),
                                          c.shape, c.dtype)
        c = jnp.where(exhausted, c + noise, c)
        centers = centers.at[i].set(c)
        min_d = jnp.minimum(min_d, jnp.sum((cand - c) ** 2, axis=-1))
        return centers, min_d

    with scope("kmeans_init"):
        first = jnp.argmax(cand_w)  # heaviest candidate
        centers0 = jnp.zeros((k, cand.shape[-1]),
                             cand.dtype).at[0].set(cand[first])
        min_d = jnp.sum((cand - cand[first]) ** 2, axis=-1)
        centers0, _ = jax.lax.fori_loop(1, k, pick, (centers0, min_d))

    prep = backend.prepare(local_centers, local_w)  # pad once, not per round

    if stop.tol <= 0:
        # static path: the pre-StopSpec trace, bit for bit
        def body(_, centers):
            sums, counts, _ = backend.step(prep, centers)
            sums = jax.lax.psum(sums, axis)
            counts = jax.lax.psum(counts, axis)
            new = (sums / jnp.maximum(counts, 1e-12)[:, None]).astype(
                centers.dtype)
            return jnp.where((counts <= 0)[:, None], centers, new)

        centers = jax.lax.fori_loop(0, stop.max_iters, body, centers0)
        return centers, jnp.asarray(stop.max_iters, jnp.int32)

    def cond(carry):
        i, _, _, _, done = carry
        return (i < stop.max_iters) & jnp.logical_not(done)

    def wl_body(carry):
        i, centers, prev_sse, streak, _ = carry
        sums, counts, sse = backend.step(prep, centers)
        sums = jax.lax.psum(sums, axis)
        counts = jax.lax.psum(counts, axis)
        sse = jax.lax.psum(sse.astype(jnp.float32), axis)  # global scalar
        new = _centers_from_stats(sums, counts, centers)
        streak, done = _stop_update(
            stop, sse=sse, prev_sse=prev_sse, new_centers=new,
            old_centers=centers, i=i, streak=streak)
        return i + 1, new, sse, streak, done

    carry0 = (jnp.asarray(0, jnp.int32), centers0,
              jnp.asarray(jnp.inf, jnp.float32),
              jnp.asarray(0, jnp.int32), jnp.asarray(False))
    n_iter, centers, _, _, _ = jax.lax.while_loop(cond, wl_body, carry0)
    return centers, n_iter


def make_distributed_sampled_kmeans(
    mesh: jax.sharding.Mesh,
    k: int = None,
    *,
    spec: ClusterSpec = None,
    axis: str = None,
    scheme: str = "equal",
    n_sub_per_device: int = 4,
    compression: int = 5,
    local_iters: int = 10,
    global_iters: int = 25,
    merge: str = None,
    weighted_merge: bool = False,
    capacity_factor: float = 2.0,
    backend: BackendSpec = None,
    init: str = "kmeans++",
    levels: tuple = None,
    logger=None,
):
    """Build a jit-able ``fn(x, key) -> DistributedClusteringResult`` where
    ``x`` is (M, d) sharded along ``axis``.  This is deliverable (a)'s main
    entry point for cluster-scale data.  Centers, representatives and SSE
    come back in the *input* space, matching
    :func:`~repro.core.pipeline.fit_from_spec`.

    With ``spec=`` every stage option comes from the
    :class:`~repro.core.spec.ClusterSpec` (``spec.partition.n_sub`` counts
    subclusters *per device*; ``spec.execution.mesh_axis`` is the data
    axis; ``spec.execution.merge_path`` picks the merge strategy;
    ``spec.levels`` adds hierarchical reduce levels); the flat kwargs
    remain as the legacy spelling, with ``merge=`` overriding the spec's
    merge path when given explicitly.

    ``levels`` (tuple of :class:`~repro.core.spec.LevelSpec`) runs the
    reduce tree *per device* on its own weighted center pool — no
    collectives — so only the final, ever-shrinking pool crosses devices:
    all_gather bytes drop from O(P_total · k_local · d) to
    O(P_total · pool_last/P_total · d) per fit.
    """
    if spec is not None:
        if k is not None and k != spec.merge.k:
            raise ValueError(f"k={k} disagrees with spec.merge.k="
                             f"{spec.merge.k}")
        k = spec.merge.k
        scheme = spec.partition.scheme
        n_sub_per_device = spec.partition.n_sub
        capacity_factor = spec.partition.capacity_factor
        compression = spec.local.compression
        local_stop = spec.local.effective_stop
        global_stop = spec.merge.effective_stop
        weighted_merge = spec.merge.weighted
        # an explicit backend= (e.g. the planner's resolved instance)
        # outranks the spec's name, mirroring fit_from_spec
        backend = backend if backend is not None else spec.execution.backend
        init = spec.local.init
        merge_init = spec.merge.init
        restarts = spec.merge.restarts
        axis = axis or spec.execution.mesh_axis
        merge = merge or spec.execution.merge_path
        # like merge=, an explicit kwarg (e.g. levels=() to disable the
        # tree for one run) outranks the spec
        levels = spec.levels if levels is None else tuple(levels)
    elif k is None:
        raise TypeError("make_distributed_sampled_kmeans: pass k or spec=")
    else:
        merge_init, restarts = "kmeans++", 4
        local_stop = StopSpec(max_iters=local_iters)
        global_stop = StopSpec(max_iters=global_iters)
    axis = axis or "data"
    merge = merge or "replicated"
    levels = () if levels is None else tuple(levels)
    if any(lvl.scheme == "unequal" for lvl in levels):
        # fit_from_spec folds reduce_pool's dropped mass into n_dropped;
        # DistributedClusteringResult has no such channel, so an
        # unequal-scheme level's capacity clamp would lose mass silently
        warnings.warn(
            "make_distributed_sampled_kmeans: unequal-scheme reduce levels "
            "can clamp overflow pool entries, and the distributed result "
            "has no n_dropped channel to report that mass — prefer "
            "equal-scheme levels (or raise capacity_factor)", stacklevel=2)
    be = get_backend(backend)
    partitioner = get_partitioner(scheme)

    def per_device(xs: Array, key: Array) -> DistributedClusteringResult:
        my = jax.lax.axis_index(axis)
        # Split the caller's key once per stage (like fit_from_spec): the
        # merge half stays replicated — the merge runs identically on every
        # device, so its key must NOT depend on the device index — while
        # the local half is folded per device.
        key_local, key_merge = jax.random.split(key)
        key_dev = jax.random.fold_in(key_local, my)
        xn, scale_params = _global_feature_scale(xs, axis)

        with scope("fold"):
            part = partitioner(xn, n_sub_per_device, capacity_factor)
            parts, part_w = gather_partitions(xn, part)
            cap = parts.shape[1]
            k_local = max(1, cap // compression)

            keys = jax.random.split(jax.random.fold_in(key_dev, 1),
                                    n_sub_per_device)
            local = jax.vmap(
                lambda p, w, kk: kmeans(p, k_local, weights=w,
                                        stop=local_stop, key=kk, init=init,
                                        backend=be)
            )(parts, part_w, keys)

            d = xs.shape[-1]
            lc = local.centers.reshape(n_sub_per_device * k_local, d)
            lw = local.counts.reshape(n_sub_per_device * k_local)

        # Hierarchical reduce tree, all_gather-free: every extra level
        # re-partitions THIS device's weighted pool and shrinks it in
        # place; no bytes cross the mesh until the final (smallest) pool.
        # (dropped mass has no channel here — build time warns on
        # unequal-scheme levels)
        for i, lvl in enumerate(levels):
            with scope("reduce_level"):
                lc, lw, _ = reduce_pool(lc, lw, lvl,
                                        jax.random.fold_in(key_dev, 2 + i),
                                        backend=be)

        merge_w = lw if weighted_merge else (lw > 0).astype(xs.dtype)

        with scope("merge"):
            if merge == "replicated":
                # Paper-faithful: gather every local center everywhere,
                # merge redundantly (the "host" stage, replicated instead
                # of serial), with the batch merge's multi-seed guard.
                all_c = jax.lax.all_gather(lc, axis, tiled=True)
                all_w = jax.lax.all_gather(merge_w, axis, tiled=True)
                merged = kmeans(all_c, k, weights=all_w, stop=global_stop,
                                key=key_merge, init=merge_init,
                                backend=be, restarts=restarts)
                centers = merged.centers
            elif merge == "distributed":
                centers, _ = _distributed_merge(lc, merge_w, k, global_stop,
                                                key_merge, axis, be)
                all_c = jax.lax.all_gather(lc, axis, tiled=True)
                all_w = jax.lax.all_gather(merge_w, axis, tiled=True)
            else:
                raise ValueError(f"unknown merge {merge!r}")

        # global SSE in the scaled space would under-report wide features;
        # map everything back through (lo, span) and score in input space
        centers = unscale(centers, scale_params)
        all_c = unscale(all_c, scale_params)
        with scope("sse"):
            local_sse = jnp.sum(jnp.min(pairwise_sqdist(xs, centers),
                                        axis=-1))
            total_sse = jax.lax.psum(local_sse, axis)
        return DistributedClusteringResult(centers, all_c, all_w, total_sse)

    mapped = compat.shard_map(
        per_device,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=DistributedClusteringResult(P(), P(axis), P(axis), P()),
        check_vma=False,
    )
    fitted = jax.jit(mapped)

    # telemetry (logger= or spec.execution.telemetry): the shard_map body
    # cannot log host-side, so the compiled fit is one "fit_shard_map"
    # stage from out here, with the mesh/merge accounting; the NULL path
    # returns the bare jitted fn (the body's scopes name its stages).
    log = get_run_logger(
        logger if logger is not None
        else (spec.execution.telemetry if spec is not None else None))
    if log is NULL:
        return fitted

    n_dev = int(mesh.shape[axis])

    def logged(x, key):
        with log.stage("fit_shard_map", n=int(x.shape[0]), k=k,
                       merge_path=merge, levels=len(levels),
                       devices=n_dev) as st:
            res = st.ready(fitted(x, key))
        log.event("dist_fit", n=int(x.shape[0]), k=k, merge_path=merge,
                  devices=n_dev,
                  pool=int(res.local_centers.shape[0]),
                  sse=float(res.sse))
        return res

    return logged


# ---------------------------------------------------------------------------
# The sharded out-of-core executor (mode="chunked_dist"):
# out-of-core × multi-device fused
# ---------------------------------------------------------------------------

class ChunkDistStats(NamedTuple):
    """Accounting from one :func:`fit_chunked_dist` run — the sharded
    counterpart of :class:`repro.core.pipeline.ChunkStats`, with per-device
    breakdowns so the acceptance tests can prove both that the dataset
    never sat in one place AND that every device pulled its own share."""
    n_points: int            # total rows folded across all shards
    n_chunks: int            # chunks consumed across all shards
    max_chunk_points: int    # largest single resident chunk (rows)
    pool_size: int           # concatenated pool rows the merge stage saw
    prefetch: int            # per-device chunks in flight (host→device)
    passes: int              # data passes: fold (+ scale) (+ exact SSE)
    n_devices: int           # mesh devices = source shards
    per_device_points: tuple  # rows folded by each device's shard
    per_device_chunks: tuple  # chunks consumed by each device's shard
    peak_pool_rows: int      # most pool rows alive on any ONE device


def merge_pool_distributed(pools, pool_ws, spec: ClusterSpec,
                           mesh: jax.sharding.Mesh, key: Array, *,
                           backend: BackendSpec = None) -> Array:
    """Merge per-device weighted center pools with the pool left sharded:
    each device keeps its own pool rows and only the ``k`` global centers
    cross the mesh per Lloyd round (:func:`_distributed_merge` — the
    ``merge_path="distributed"`` strategy of the resident shard_map
    executor, reused verbatim).

    ``pools``/``pool_ws`` are host-side per-device ``(p_i, d)`` /
    ``(p_i,)`` arrays in mesh-device order.  Ragged pools (a short tail
    shard compresses to fewer rows) are padded to the widest with
    zero-weight rows — dead slots carry no weight into the greedy
    candidate picks or the Lloyd rounds.  (When a device's pool exceeds
    the candidate budget ``max(2k, 8)``, the strided candidate subsample
    sees the padded layout, so the padded merge is deterministic given
    the pool shapes rather than literally identical to an unpadded one.)
    Returns ``(centers, n_iter)``: the replicated ``(k, d)`` centers (in
    whatever space the pools are in — the caller unscales) and the true
    Lloyd round count (``spec.merge.effective_stop.max_iters`` under the
    default ``tol=0`` policy; less when the psum'd convergence scalar
    exits early)."""
    be = get_backend(backend if backend is not None
                     else spec.execution.backend)
    axis = spec.execution.mesh_axis
    n_dev = int(np.prod(mesh.devices.shape))
    if len(pools) != n_dev:
        raise ValueError(
            f"merge_pool_distributed: {len(pools)} pools for a "
            f"{n_dev}-device mesh")
    d = int(pools[0].shape[-1])
    p_max = max(int(p.shape[0]) for p in pools)
    padded_c, padded_w = [], []
    for c, w in zip(pools, pool_ws):
        c, w = np.asarray(c), np.asarray(w)
        pad = p_max - c.shape[0]
        if pad:
            c = np.concatenate([c, np.zeros((pad, d), c.dtype)], axis=0)
            w = np.concatenate([w, np.zeros((pad,), w.dtype)], axis=0)
        padded_c.append(c)
        padded_w.append(w)
    all_c = np.concatenate(padded_c, axis=0)
    all_w = np.concatenate(padded_w, axis=0)
    merge_w = (all_w if spec.merge.weighted
               else (all_w > 0).astype(all_c.dtype))

    sharding = jax.sharding.NamedSharding(mesh, P(axis))
    dc = jax.device_put(all_c, sharding)
    dw = jax.device_put(merge_w, sharding)
    k, stop = spec.merge.k, spec.merge.effective_stop
    def merge_body(lc, lw, kk):
        with scope("merge"):
            return _distributed_merge(lc, lw, k, stop, kk, axis, be)

    body = compat.shard_map(
        merge_body,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P()),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return jax.jit(body)(dc, dw, key)


def fit_chunked_dist(source, spec: ClusterSpec, mesh: jax.sharding.Mesh,
                     key: Optional[Array] = None, *,
                     backend: BackendSpec = None, logger=None
                     ) -> tuple[SampledClusteringResult, ChunkDistStats]:
    """Run the spec-declared pipeline **out of core and multi-device**
    (``mode="chunked_dist"``): the source splits into one
    ``DataSource.shard(i, n)`` per mesh device, each device folds its own
    shard's chunks through the jitted per-chunk stage with
    ``prefetch_to_device`` pinning buffers to that device, reduces its pool
    through the collective-free ``spec.levels`` locally, and only the final
    per-device pools cross the mesh for the global merge — one collective
    round-trip per fit (``merge_path="distributed"``; the ``"replicated"``
    path gathers the pools on the host and merges eagerly, which is what
    keeps the 1-device run bit-for-bit :func:`fit_chunked`).

    Chunk dispatch round-robins across the devices, so while device ``i``'s
    jitted fold executes, the host is already handing device ``i+1`` its
    next chunk — the async-dispatch pipeline is what buys the fold-rate
    scaling.  All cross-device combination (min/max scale partials,
    dropped counts, SSE partials) happens on the host with exact or
    order-fixed arithmetic.

    PRNG streams: shard 0 draws exactly :func:`fit_chunked`'s streams
    (chunk 0 = ``key_local`` verbatim, chunk ``j`` =
    ``fold_in(key_local, _CHUNK_KEY_OFFSET + j)``, level ``j`` =
    ``fold_in(key_local, 1 + j)``), which makes the 1-device/1-shard
    parity pin hold by construction; shard ``i > 0`` folds per-chunk keys
    at ``(i + 1) * _CHUNK_KEY_OFFSET + j`` and derives level/flush streams
    from ``fold_in(key_local, _SHARD_KEY_OFFSET + i)`` — all streams
    disjoint for any shard with fewer than ``_CHUNK_KEY_OFFSET`` chunks.
    The merge key is the same ``key_global`` half :func:`fit_chunked`
    uses, so the distributed merge agrees with
    ``merge_pool_distributed`` on the same pools under the same key.

    Empty shards (fewer chunks than devices) are tolerated at runtime —
    they simply contribute nothing; ``plan()`` rejects the configurations
    where that is knowable in advance.  Returns
    ``(SampledClusteringResult, ChunkDistStats)``.
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
    axis = spec.execution.mesh_axis
    if tuple(mesh.axis_names) != (axis,):
        raise ValueError(
            f"fit_chunked_dist: needs a 1-D mesh over axis {axis!r} "
            f"(spec.execution.mesh_axis), got axes {mesh.axis_names}")
    devs = list(mesh.devices.flat)
    n_dev = len(devs)
    shards = [source.shard(i, n_dev) for i in range(n_dev)]
    shard_keys = [key_local if i == 0
                  else jax.random.fold_in(key_local, _SHARD_KEY_OFFSET + i)
                  for i in range(n_dev)]
    cp = spec.chunk.chunk_points
    depth = spec.chunk.prefetch
    base = spec.level_schedule()[0]

    t_start = _now()
    passes = 1
    lo_np = span_np = None
    if spec.scale:
        # per-shard running min/max, combined on the host: min/max are
        # exact and order-independent, so this is bitwise the single-pass
        # answer no matter how the rows were sharded
        # min/max come to the host (np.asarray): the stage ends when ready
        with log.stage("scale_pass", devices=n_dev):
            lo_parts, hi_parts = [], []
            for i, shard in enumerate(shards):
                slo, shi = minmax_pass(shard, cp, prefetch=depth,
                                       device=devs[i])
                if slo is not None:
                    lo_parts.append(np.asarray(slo))
                    hi_parts.append(np.asarray(shi))
            if not lo_parts:
                raise ValueError(
                    "fit_chunked_dist: the source yielded no points")
            lo_np = functools.reduce(np.minimum, lo_parts)
            hi_np = functools.reduce(np.maximum, hi_parts)
            span_np = np.maximum(hi_np - lo_np, np.asarray(1e-9, lo_np.dtype))
        passes += 1
        log.event("pass_rss", stage="scale", peak_rss_mb=peak_rss_mb())

    # per-device fold state: scale params pinned to each device once,
    # bounded pool accumulators, host-int counters (never cross-device adds)
    lo_d = [None] * n_dev
    span_d = [None] * n_dev
    if lo_np is not None:
        lo_d = [jax.device_put(lo_np, dv) for dv in devs]
        span_d = [jax.device_put(span_np, dv) for dv in devs]
    accs = [_PoolAccumulator(spec.levels, key_local, shard=i, backend=be,
                             log=log)
            for i in range(n_dev)]
    dropped = [None] * n_dev
    dev_points = [0] * n_dev
    dev_chunks = [0] * n_dev
    max_chunk = 0
    dev_iters = [None] * n_dev   # per-device true Lloyd-iteration counts
    fold_budget = 0              # sum of max_iters budgets
    fold_rate = log.rate("fold_rate", units="points")
    with log.stage("fold", devices=n_dev) as st:
        its = [iter(enumerate(prefetch_to_device(
                   shards[i].chunks(cp), depth, device=devs[i])))
               for i in range(n_dev)]
        live = set(range(n_dev))
        while live:
            # round-robin: dispatch one chunk per device per sweep; the
            # jitted fold call returns before the device finishes, so
            # device i computes while i+1's chunk is being dispatched
            for i in sorted(live):
                try:
                    j, chunk = next(its[i])
                except StopIteration:
                    live.discard(i)
                    continue
                m, d = chunk.shape
                if m == 0:
                    continue
                if lo_d[i] is None:  # scale off: identity params, same path
                    lo_d[i] = jnp.zeros((d,), chunk.dtype)
                    span_d[i] = jnp.ones((d,), chunk.dtype)
                lv = (base if m >= base.n_sub
                      else dataclasses.replace(base, n_sub=max(1, m)))
                ck = (key_local if (i == 0 and j == 0)
                      else jax.random.fold_in(
                          key_local, (i + 1) * _CHUNK_KEY_OFFSET + j))
                c, w, nd, ir = _fold_scaled_chunk(chunk, lo_d[i], span_d[i],
                                                  ck, lv=lv, backend=be)
                accs[i].add(c, w)
                dropped[i] = nd if dropped[i] is None else dropped[i] + nd
                dev_iters[i] = ir if dev_iters[i] is None \
                    else dev_iters[i] + ir
                fold_budget += lv.effective_stop.max_iters * lv.n_sub
                dev_points[i] += m
                dev_chunks[i] += 1
                max_chunk = max(max_chunk, m)
                fold_rate.tick(m, device=i, chunk=j, rows=m)
        # each device's chunk folds add into its dev_iters entry
        st.ready(dev_iters)
    n_points, n_chunks = sum(dev_points), sum(dev_chunks)
    if n_chunks == 0:
        raise ValueError("fit_chunked_dist: the source yielded no points")
    log.event("pass_rss", stage="fold", peak_rss_mb=peak_rss_mb())

    # per-device collective-free reduce levels (shard 0 on fit_chunked's
    # key stream), then only the final pools leave their devices
    pools, pool_ws = [], []
    n_dropped_total = 0
    for i in range(n_dev):
        if dev_chunks[i] == 0:
            continue  # empty shard: nothing to reduce, nothing to merge
        pool_i, w_i = accs[i].finalize()
        if accs[i].w_dropped is not None:
            dropped[i] = (dropped[i]
                          + jnp.round(accs[i].w_dropped).astype(jnp.int32))
        for jl, lvl in enumerate(spec.levels):
            with log.stage("reduce_level", device=i, level=jl,
                           pool_in=int(pool_i.shape[0])) as st:
                pool_i, w_i, wd = st.ready(reduce_pool(
                    pool_i, w_i, lvl,
                    jax.random.fold_in(shard_keys[i], 1 + jl), backend=be))
            dropped[i] = dropped[i] + jnp.round(wd).astype(jnp.int32)
        pools.append(np.asarray(pool_i))
        pool_ws.append(np.asarray(w_i))
        n_dropped_total += int(dropped[i])
    n_dropped = jnp.asarray(n_dropped_total, jnp.int32)
    peak_pool = max(a.peak_rows for a in accs)

    pool_np = (pools[0] if len(pools) == 1
               else np.concatenate(pools, axis=0))
    pool_w_np = (pool_ws[0] if len(pool_ws) == 1
                 else np.concatenate(pool_ws, axis=0))
    pool = jnp.asarray(pool_np)
    pool_w = jnp.asarray(pool_w_np)

    with log.stage("merge", pool=int(pool.shape[0]), k=spec.merge.k,
                   merge_path=spec.execution.merge_path) as st:
        if spec.execution.merge_path == "distributed":
            # pools stay device-resident; one collective per Lloyd round
            # moves only the k global centers (padded rows carry 0 weight);
            # empty shards rejoin the mesh as a single all-dead row
            merge_pools, merge_ws = list(pools), list(pool_ws)
            while len(merge_pools) < n_dev:
                merge_pools.append(np.zeros((1, pool_np.shape[-1]),
                                            pool_np.dtype))
                merge_ws.append(np.zeros((1,), pool_w_np.dtype))
            centers, merge_iters = merge_pool_distributed(
                merge_pools, merge_ws, spec, mesh, key_global, backend=be)
        else:
            # replicated: host-gathered pool, eager merge — the same
            # merge_pool call fit_chunked makes (the 1-device parity pin)
            merged = merge_pool(pool, pool_w, spec.merge, key_global,
                                backend=be)
            centers, merge_iters = merged.centers, merged.n_iter
        st.ready(centers)
    if log is not NULL:
        _log_stage_iters(log, "fold",
                         sum(int(it) for it in dev_iters if it is not None),
                         fold_budget)
        _log_stage_iters(log, "merge", int(merge_iters),
                         spec.merge.effective_stop.max_iters)

    local_centers = pool
    if spec.scale:
        params = (jnp.asarray(lo_np), jnp.asarray(span_np))
        centers = unscale(centers, params)
        local_centers = unscale(local_centers, params)

    if spec.chunk.sse == "exact":
        with log.stage("sse_pass", devices=n_dev) as st:
            totals = []
            for i, shard in enumerate(shards):
                c_i = jax.device_put(centers, devs[i])
                s = sse_pass(shard, c_i, cp, prefetch=depth, device=devs[i])
                if s is not None:
                    totals.append(s)
            # 1 device: the untouched device total — bitwise fit_chunked;
            # n devices: host-order sum of per-shard partials
            total_sse = st.ready(
                totals[0] if len(totals) == 1
                else jnp.asarray(sum(float(s) for s in totals), jnp.float32))
        passes += 1
        log.event("pass_rss", stage="sse", peak_rss_mb=peak_rss_mb())
    else:  # "pool": weighted SSE of the representatives, no extra pass
        with log.stage("sse_pool") as st:
            total_sse = st.ready(sse_fn(local_centers, centers,
                                        weights=pool_w))

    result = SampledClusteringResult(centers, total_sse, local_centers,
                                     pool_w, n_dropped)
    stats = ChunkDistStats(n_points=n_points, n_chunks=n_chunks,
                           max_chunk_points=max_chunk,
                           pool_size=int(pool.shape[0]), prefetch=depth,
                           passes=passes, n_devices=n_dev,
                           per_device_points=tuple(dev_points),
                           per_device_chunks=tuple(dev_chunks),
                           peak_pool_rows=peak_pool)
    if log is not NULL:
        wall = _now() - t_start   # the stages waited: "result ready"
        summary = stats._asdict()
        summary["per_device_points"] = list(stats.per_device_points)
        summary["per_device_chunks"] = list(stats.per_device_chunks)
        log.event("fit_chunked_dist", k=spec.merge.k, levels=spec.n_levels,
                  backend=be.name, merge_path=spec.execution.merge_path,
                  wall_s=wall, points_per_sec=n_points / max(wall, 1e-9),
                  peak_rss_mb=peak_rss_mb(), **summary)
    return result, stats
