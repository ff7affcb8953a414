"""Online sampled clustering: the paper's compression loop, run forever.

The batch pipeline (:func:`repro.core.pipeline.sampled_kmeans`) runs
partition -> local k-means -> merge exactly once.  A data stream wants the
same two levels but *incrementally*:

  1. each fixed-size chunk is partitioned and summarised by the shared
     ``chunk_fold`` stage (the paper's "device part", unchanged — the same
     substrate the batch and out-of-core executors fold over);
  2. the resulting weighted local centers are folded into a bounded,
     exponentially-decayed **coreset buffer** — the paper's "sampled
     representatives", now persistent.  Scalable K-Means++ (Bahmani et al.)
     justifies the move: oversampled weighted representatives preserve
     solution quality, so merging representatives-of-representatives does
     too;
  3. the k global centers are refreshed by a warm-started weighted k-means
     over the coreset (``init`` = previous centers), which is the paper's
     merge stage executed as a mini-batch update.

Drift handling: coreset weights decay by ``decay`` per update, so stale
regions fade; global centers whose coreset support hits zero are reseeded
from the heaviest still-uncovered coreset points (greedy farthest-point on
``weight * min_dist``, the same construction as the distributed merge init).

Everything is static-shape and pure: ``StreamState`` is a NamedTuple,
``update`` is jit-able, and the chunk summarisation + coreset fold split
lets :func:`make_sharded_update` run the local stage under shard_map along
the existing ``data`` axis (see :mod:`repro.core.distributed`).
"""
from __future__ import annotations

import dataclasses
import time as _time
import warnings
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.backend import BackendSpec, LloydBackend, get_backend
from repro.core.kmeans import kmeans, pairwise_sqdist
from repro.core.metrics import nearest, sse as sse_fn
from repro.core.pipeline import chunk_fold, reduce_pool
from repro.core.spec import ClusterSpec, LevelSpec, StopSpec
from repro.core.subcluster import feature_scale, unscale

Array = jax.Array


class StreamState(NamedTuple):
    """Pure-functional clusterer state (all fields static-shape)."""
    centers: Array     # (k, d) current global centers, input space
    coreset: Array     # (buffer_size, d) weighted representatives
    coreset_w: Array   # (buffer_size,) decayed weights; 0 = empty slot
    n_seen: Array      # () float32 — raw points ingested so far
    step: Array        # () int32 — update counter
    key: Array         # PRNG key threaded through updates


@dataclasses.dataclass(frozen=True)
class StreamConfig:
    """Hyper-parameters of the streaming engine (hashable -> jit-static)."""
    k: int
    n_sub: int = 8                 # partitions per chunk (paper's P)
    compression: int = 5           # paper's c: N-point partition -> N/c reps
    scheme: str = "equal"          # "equal" (Algo 1) | "unequal" (Algo 2)
    capacity_factor: float = 2.0   # Algo 2 capacity bound
    local_iters: int = 8           # Lloyd iters per partition
    merge_iters: int = 8           # warm-started Lloyd iters per update
    buffer_size: int = 1024        # coreset slots
    decay: float = 0.97            # per-update weight multiplier
    reseed_threshold: float = 1e-6 # coreset support below this = dead center
    init_mode: str = "kmeans++"    # local-stage init
    backend: str = "auto"          # LloydBackend name (repro.core.backend)
    telemetry: str = "off"         # RunLogger name (repro.telemetry) —
    #                                per-tick points/sec with median windows
    levels: tuple = ()             # tuple[LevelSpec, ...]: extra reduce
    #                                levels compressing the coreset pool
    #                                before each warm-started merge
    local_stop: Optional[StopSpec] = None   # overrides local_iters when set
    merge_stop: Optional[StopSpec] = None   # overrides merge_iters when set

    @classmethod
    def from_spec(cls, spec: ClusterSpec, **overrides) -> "StreamConfig":
        """Derive the streaming hyper-parameters from a
        :class:`~repro.core.spec.ClusterSpec`: the partition/local sections
        configure the chunk summarisation, the merge section the coreset
        merge.  Stream-only knobs (``buffer_size``, ``decay``,
        ``reseed_threshold``) keep their defaults unless overridden."""
        base = dict(
            k=spec.merge.k,
            n_sub=spec.partition.n_sub,
            compression=spec.local.compression,
            scheme=spec.partition.scheme,
            capacity_factor=spec.partition.capacity_factor,
            local_iters=spec.local.iters,
            merge_iters=spec.merge.iters,
            init_mode=spec.local.init,
            backend=spec.execution.backend,
            telemetry=spec.execution.telemetry,
            levels=spec.levels,
            local_stop=spec.local.stop,
            merge_stop=spec.merge.stop,
        )
        base.update(overrides)
        return cls(**base)


def summarize_chunk(chunk: Array, cfg: StreamConfig, key: Array,
                    backend: BackendSpec = None) -> tuple[Array, Array]:
    """Chunk -> (weighted local centers, weights): the paper's local stage.

    The chunk is feature-scaled on its own min/max (the partition landmarks
    are chunk-local, exactly as each batch invocation scales on its input),
    then folded through the shared :func:`repro.core.pipeline.chunk_fold`
    stage — the same substrate the batch and out-of-core executors use;
    centers come back in input space.
    """
    xs, params = feature_scale(chunk)
    lv = LevelSpec(n_sub=cfg.n_sub, compression=cfg.compression,
                   iters=cfg.local_iters, init=cfg.init_mode,
                   scheme=cfg.scheme, capacity_factor=cfg.capacity_factor,
                   stop=cfg.local_stop)
    centers, weights, _, _ = chunk_fold(
        xs, lv, key,
        backend=backend if backend is not None else cfg.backend)
    return unscale(centers, params), weights


def fold_coreset(coreset: Array, coreset_w: Array, new_pts: Array,
                 new_w: Array, decay: float) -> tuple[Array, Array]:
    """Decay the buffer, append the fresh representatives, evict back down
    to ``buffer_size`` by keeping the heaviest entries (static top_k)."""
    buffer = coreset.shape[0]
    all_pts = jnp.concatenate([coreset, new_pts], axis=0)
    all_w = jnp.concatenate([coreset_w * decay, new_w], axis=0)
    top_w, top_i = jax.lax.top_k(all_w, buffer)
    return all_pts[top_i], top_w


def reseed_dead_centers(centers: Array, coreset: Array, coreset_w: Array,
                        threshold: float) -> Array:
    """Replace centers with ~zero coreset support by greedy farthest-point
    picks over the coreset, scored by ``weight * min_dist`` (heavy, badly
    covered representatives first).  Alive centers are untouched; the greedy
    loop spreads the reseeds so k simultaneous deaths (e.g. the cold start
    from an all-zero init state) land on k distinct regions."""
    k = centers.shape[0]
    d2 = pairwise_sqdist(coreset, centers)  # one matrix serves both uses
    idx = nearest(d2, axis=1)
    support = (jax.nn.one_hot(idx, k, dtype=coreset.dtype)
               * coreset_w[:, None]).sum(axis=0)
    dead = support <= threshold

    big = jnp.asarray(jnp.finfo(coreset.dtype).max, coreset.dtype)
    min_d = jnp.min(jnp.where(dead[None, :], big, d2), axis=1)
    min_d = jnp.where(jnp.all(dead), 1.0, min_d)  # no live center at all

    def body(i, carry):
        cs, md = carry
        pick = coreset[jnp.argmax(coreset_w * md)]
        new_c = jnp.where(dead[i], pick, cs[i])
        cs = cs.at[i].set(new_c)
        md = jnp.minimum(md, jnp.sum((coreset - new_c) ** 2, axis=-1))
        return cs, md

    centers, _ = jax.lax.fori_loop(0, k, body, (centers, min_d))
    return centers


def fold_and_merge(state: StreamState, new_pts: Array, new_w: Array,
                   n_new_points: Array, cfg: StreamConfig,
                   key: Array, backend: BackendSpec = None
                   ) -> StreamState:
    """Global half of an update: coreset fold + reseed + warm-started merge.
    Runs replicated under shard_map (inputs already gathered).

    With ``cfg.levels`` the merge input is first compressed through the
    hierarchical reduce tree (:func:`repro.core.pipeline.reduce_pool`) —
    the persistent coreset buffer itself keeps its full resolution; only
    the per-update merge sees the shrunken pool.
    """
    coreset, coreset_w = fold_coreset(state.coreset, state.coreset_w,
                                      new_pts, new_w, cfg.decay)
    warm = reseed_dead_centers(state.centers, coreset, coreset_w,
                               cfg.reseed_threshold)
    pool, pool_w = coreset, coreset_w
    for i, lvl in enumerate(cfg.levels):
        pool, pool_w, _ = reduce_pool(pool, pool_w, lvl,
                                      jax.random.fold_in(key, 1 + i),
                                      backend=backend if backend is not None
                                      else cfg.backend)
    merge_stop = (cfg.merge_stop if cfg.merge_stop is not None
                  else StopSpec(max_iters=cfg.merge_iters))
    merged = kmeans(pool, cfg.k, weights=pool_w,
                    stop=merge_stop, key=key, init=warm,
                    backend=backend if backend is not None else cfg.backend)
    return StreamState(
        centers=merged.centers,
        coreset=coreset,
        coreset_w=coreset_w,
        n_seen=state.n_seen + n_new_points.astype(state.n_seen.dtype),
        step=state.step + 1,
        key=state.key,
    )


class StreamingClusterer:
    """Online sampled-k-means engine over fixed-size chunks.

    >>> sc = StreamingClusterer(StreamConfig(k=8))
    >>> state = sc.init(dim=2)
    >>> for chunk in chunks:                    # (chunk_size, 2) each
    ...     state = sc.update(state, chunk)     # jit-compiled
    >>> assignment, point_sse = sc.query(state, x)

    ``init`` starts from all-zero centers and an empty coreset; the first
    ``update`` detects the k unsupported centers and reseeds them from the
    fresh chunk's representatives, so no separate warm-up path exists.
    ``update`` recompiles per distinct chunk shape — feed fixed-size chunks.
    """

    def __init__(self, cfg: StreamConfig | ClusterSpec, *,
                 backend: BackendSpec = None, jit: bool = True,
                 logger=None):
        from repro.telemetry import NULL, get_run_logger
        if isinstance(cfg, ClusterSpec):
            cfg = StreamConfig.from_spec(cfg)
        self.cfg = cfg
        self.logger = get_run_logger(logger if logger is not None
                                     else cfg.telemetry)
        if any(lvl.scheme == "unequal" for lvl in cfg.levels):
            # the stream state has no n_dropped channel: an unequal-scheme
            # level's capacity clamp would shave merge-input mass silently
            # on every update
            warnings.warn(
                "StreamingClusterer: unequal-scheme reduce levels can clamp "
                "overflow pool entries out of each merge input unreported — "
                "prefer equal-scheme levels (or raise capacity_factor)",
                stacklevel=2)
        # resolve once (env/auto) so update/query/shard_map share one backend
        self.backend: LloydBackend = get_backend(
            backend if backend is not None else cfg.backend)
        wrap = jax.jit if jit else (lambda f: f)
        self.update = wrap(self._update)
        self.query = wrap(self._query)
        if self.logger is not NULL:
            # host-side tick meter around the (possibly jitted) update:
            # per-tick points/sec as a median window (one compile or
            # prefetch stall does not read as the steady-state rate).
            # Telemetry-only sync — values are untouched.
            raw_update = self.update
            meter = self.logger.rate("stream_tick", units="points")

            def logged_update(state, chunk):
                t0 = _time.perf_counter()
                new_state = raw_update(state, chunk)
                jax.block_until_ready(new_state.centers)
                meter.tick(int(chunk.shape[0]),
                           dur=_time.perf_counter() - t0,
                           step=int(new_state.step))
                return new_state

            self.update = logged_update

    # -- state ------------------------------------------------------------
    def init(self, dim: int, key: Optional[Array] = None,
             dtype=jnp.float32) -> StreamState:
        if key is None:
            key = jax.random.PRNGKey(0)
        cfg = self.cfg
        return StreamState(
            centers=jnp.zeros((cfg.k, dim), dtype),
            coreset=jnp.zeros((cfg.buffer_size, dim), dtype),
            coreset_w=jnp.zeros((cfg.buffer_size,), dtype),
            n_seen=jnp.zeros((), jnp.float32),
            step=jnp.zeros((), jnp.int32),
            key=key,
        )

    # -- pure update / query ----------------------------------------------
    def _update(self, state: StreamState, chunk: Array) -> StreamState:
        key_local, key_merge, key_next = jax.random.split(state.key, 3)
        lc, lw = summarize_chunk(chunk, self.cfg, key_local, self.backend)
        state = fold_and_merge(state, lc, lw,
                               jnp.asarray(chunk.shape[0], jnp.float32),
                               self.cfg, key_merge, self.backend)
        return state._replace(key=key_next)

    def _query(self, state: StreamState, x: Array) -> tuple[Array, Array]:
        """Assign points to the current centers; returns (assignment, sse)."""
        idx, _ = self.backend.assign_points(x, state.centers)
        return idx, sse_fn(x, state.centers)
