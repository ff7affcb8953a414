"""The plain reference for the sharded out-of-core fit
(``mode="chunked_dist"``, ``merge_path="distributed"``): straightforward
``jax.numpy`` code for what a configuration of that path states, built on
``reference.py``'s primitives (k-means++, Lloyd, the equal partition,
distances, first minima) and sharing no code with the program under test.

What it replays, for a host array ``x`` split over ``n_dev`` devices:

  scaling   global per-attribute min-max over all rows (span clamped at
            1e-9), applied to every chunk;
  shards    ``n_dev`` contiguous row ranges, ``[i·n/n_dev, (i+1)·n/n_dev)``;
  fold      per shard, chunks of ``chunk_points`` rows in order; per chunk
            the equal partition, k-means++ and Lloyd (``reference.fold``);
  level     per shard, its chunk pools concatenated in chunk order, then
            each ``levels`` entry: the equal partition of the weighted pool
            and weighted k-means++ + Lloyd per partition;
  merge     per shard ``min(rows, max(2k, 8))`` candidates at
            ``round(linspace(0, rows - 1, ·))``, gathered in shard order;
            the heaviest candidate first, then the greedy farthest-point
            pick over live candidates (a pick that adds no spread is
            jittered by ``0.05·std + 1e-6`` of the candidates, normal noise
            from ``fold_in(key, i)``); then ``iters`` weighted Lloyd rounds
            over all shards' pools concatenated.  One run: the path
            ignores ``merge.init`` and ``merge.restarts``;
  SSE       the pool-weighted SSE of the pool against the centers, in the
            input space (``chunk.sse = "pool"``).

Keys, as ``fit_chunked_dist``'s docstring states the contract: ``key`` splits
into a local and a global half; chunk ``j`` of shard ``i`` folds with the
local key itself for (0, 0), else ``fold_in(local, (i + 1)·CHUNK_KEY_OFFSET
+ j)``; level ``l`` of shard ``i`` with ``fold_in(shard key, 1 + l)``, the
shard key being the local key for shard 0 and ``fold_in(local,
SHARD_KEY_OFFSET + i)`` after it; the merge with the global half.

A shard with ``FOLD_BUFFER`` or more chunks would have its pending pools
folded early through the first level on a stream of its own; the
reference does not implement that and refuses such a split.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import reference
from reference import (HIGHEST, equal_partition, first_min, kmeanspp,
                       lloyd, sqdist)

CHUNK_KEY_OFFSET = 1_000_003
SHARD_KEY_OFFSET = 5_000_011
FOLD_BUFFER = 8

_LEVEL = {"n_sub": None, "compression": None, "iters": None,
          "init": ("kmeans++",), "scheme": ("equal",),
          "capacity_factor": None,     # read by other schemes only
          "stop": (None,)}

# Field by field, as in ``reference.SUPPORTED``: a tuple lists the values
# implemented, None takes any value; anything else is refused.
SUPPORTED = {
    "partition": {"scheme": ("equal",), "n_sub": None,
                  "capacity_factor": None},
    "local": {"compression": None, "iters": None, "init": ("kmeans++",),
              "stop": (None,)},
    # the distributed merge seeds by one greedy farthest-point pick:
    # ``init`` and ``restarts`` are read by no one on this path
    "merge": {"k": None, "iters": None, "weighted": (True, False),
              "restarts": None, "init": None, "stop": (None,)},
    "execution": {"backend": None, "mode": ("chunked_dist",),
                  "mesh_axis": None, "donate": (False,),
                  "merge_path": ("distributed",), "telemetry": None},
    "scale": (True,),
    "levels": None,                  # each entry checked against _LEVEL
    "chunk": {"chunk_points": None, "prefetch": None, "sse": ("pool",)},
}


def require_supported(spec: dict, n: int = None, n_dev: int = None) -> None:
    """Raise ``ValueError`` for any field of ``spec`` the reference does not
    implement as set, and, given the rows and devices, for a split whose
    shards hold ``FOLD_BUFFER`` chunks or more."""
    def check(path, value, rule):
        if isinstance(rule, dict):
            if not isinstance(value, dict):
                raise ValueError(f"reference_dist: {path} is not a section")
            for k, v in value.items():
                if k not in rule:
                    raise ValueError(f"reference_dist: {path}.{k} is not "
                                     f"implemented")
                check(f"{path}.{k}", v, rule[k])
        elif rule is not None and value not in rule:
            raise ValueError(f"reference_dist: {path}={value!r} is not "
                             f"implemented (only {list(rule)})")
    check("spec", spec, SUPPORTED)
    for i, lv in enumerate(spec.get("levels", [])):
        check(f"spec.levels[{i}]", lv, _LEVEL)
    if n is not None:
        cp = spec["chunk"]["chunk_points"]
        most = max(hi - lo for lo, hi in shard_rows(n, n_dev))
        if spec.get("levels") and -(-most // cp) >= FOLD_BUFFER:
            raise ValueError(
                f"reference_dist: {-(-most // cp)} chunks on a shard; early "
                f"pool folds (from {FOLD_BUFFER}) are not implemented")


def shard_rows(n: int, n_dev: int) -> list:
    """``[(lo, hi)]``: shard ``i`` holds rows ``[i·n/n_dev, (i+1)·n/n_dev)``."""
    return [((i * n) // n_dev, ((i + 1) * n) // n_dev) for i in range(n_dev)]


def chunk_rows(lo: int, hi: int, chunk_points: int) -> list:
    return [(s, min(s + chunk_points, hi)) for s in range(lo, hi, chunk_points)]


def keys(key, n_dev: int) -> tuple:
    """``(local, global, shard keys)`` of the key contract."""
    key_local, key_global = jax.random.split(key)
    shard = [key_local if i == 0 else
             jax.random.fold_in(key_local, SHARD_KEY_OFFSET + i)
             for i in range(n_dev)]
    return key_local, key_global, shard


def chunk_key(key_local, shard: int, j: int):
    if shard == 0 and j == 0:
        return key_local
    return jax.random.fold_in(key_local, (shard + 1) * CHUNK_KEY_OFFSET + j)


def scale_params(x: np.ndarray) -> tuple:
    """Global ``(lo, span)`` in float32, span clamped at 1e-9."""
    lo = x.min(axis=0)
    span = np.maximum(x.max(axis=0) - lo, np.asarray(1e-9, x.dtype))
    return lo, span


def _scaled(x, lo, span):
    return (jnp.asarray(x) - lo) / span


@functools.partial(jax.jit, static_argnames=("n_sub", "compression",
                                             "iters", "precision"))
def level(pool, pool_w, key, *, n_sub: int, compression: int, iters: int,
          precision=HIGHEST):
    """One reduce level of a weighted pool: equal partition (slots weighted
    by the rows' weights), weighted k-means++ and Lloyd per partition.
    Returns the new pool and its member weights."""
    ids, slot = equal_partition(pool, n_sub)
    parts, w = pool[ids], slot * pool_w[ids]
    k_local = max(1, ids.shape[1] // compression)
    ks = jax.random.split(key, n_sub)
    init = jax.vmap(lambda p, pw, kk: kmeanspp(p, pw, k_local, kk))(
        parts, w, ks)
    centers, counts, _ = jax.lax.map(
        lambda a: lloyd(a[0], a[1], a[2], iters, precision), (parts, w, init))
    d = pool.shape[1]
    return centers.reshape(n_sub * k_local, d), counts.reshape(-1)


@functools.partial(jax.jit, static_argnames=("n_sub", "compression"))
def level_counts(pool, pool_w, centers, *, n_sub: int, compression: int):
    """Weights of each of a level's output rows when every input row goes,
    with its weight, to the nearest output row of its own partition
    (partition-centered, at HIGHEST)."""
    ids, slot = equal_partition(pool, n_sub)
    parts, w = pool[ids], slot * pool_w[ids]
    k_local = max(1, ids.shape[1] // compression)
    cents = centers.reshape(n_sub, k_local, pool.shape[1])

    def one(a):
        p, pw, c = a
        mu = jnp.sum(p * pw[:, None], axis=0) / jnp.maximum(jnp.sum(pw),
                                                             1e-30)
        idx, _ = first_min(sqdist(p - mu, c - mu, HIGHEST))
        return jnp.zeros((k_local,), jnp.float32).at[idx].add(pw)

    return jax.lax.map(one, (parts, w, cents)).reshape(-1)


@functools.partial(jax.jit, static_argnames=("k", "n_dev"))
def pick(pool, pool_w, key, *, k: int, n_dev: int):
    """The merge's seeding over ``n_dev`` equal shards of ``pool``: strided
    candidates per shard in shard order, the heaviest first, then greedy
    farthest-point picks (first index of the largest score)."""
    rows = pool.shape[0] // n_dev
    n_cand = min(rows, max(2 * k, 8))
    stride = jnp.round(jnp.linspace(0, rows - 1, n_cand)).astype(jnp.int32)
    ids = (jnp.arange(n_dev, dtype=jnp.int32)[:, None] * rows
           + stride[None, :]).reshape(-1)
    cand, cand_w = pool[ids], pool_w[ids]
    sigma = 0.05 * jnp.std(cand, axis=0) + 1e-6

    def first_max(v):
        return jnp.argmax(v >= jnp.max(v))

    first = first_max(cand_w)
    centers = jnp.zeros((k, pool.shape[1]), pool.dtype).at[0].set(cand[first])
    min_d = jnp.sum((cand - cand[first]) ** 2, axis=-1)

    def body(i, carry):
        centers, min_d = carry
        score = jnp.where(cand_w > 0, min_d, -1.0)
        nxt = first_max(score)
        c = cand[nxt]
        noise = sigma * jax.random.normal(jax.random.fold_in(key, i),
                                          c.shape, c.dtype)
        c = jnp.where(score[nxt] <= 0.0, c + noise, c)
        return (centers.at[i].set(c),
                jnp.minimum(min_d, jnp.sum((cand - c) ** 2, axis=-1)))

    centers, _ = jax.lax.fori_loop(1, k, body, (centers, min_d))
    return centers


@functools.partial(jax.jit, static_argnames=("k", "iters", "n_dev",
                                             "precision"))
def merge(pool, pool_w, key, *, k: int, iters: int, n_dev: int,
          precision=HIGHEST):
    """The distributed merge's answer: the pick, then ``iters`` weighted
    Lloyd rounds over the whole pool."""
    c0 = pick(pool, pool_w, key, k=k, n_dev=n_dev)
    centers, _, _ = lloyd(pool, pool_w, c0, iters, precision)
    return centers


@functools.partial(jax.jit, static_argnames=("precision", "center",
                                             "block"))
def pool_sse(pool, pool_w, centers, *, precision=HIGHEST, center=True,
             block: int = 8192):
    """Weighted SSE of the pool rows against their nearest centers, in row
    blocks; ``center`` subtracts the pool's weighted mean from both sides
    first (the expansion then does not cancel)."""
    if center:
        mu = jnp.sum(pool * pool_w[:, None], axis=0) / jnp.sum(pool_w)
        pool, centers = pool - mu, centers - mu
    m, d = pool.shape
    nb = -(-m // block)
    xp = jnp.pad(pool, ((0, nb * block - m), (0, 0)))
    wp = jnp.pad(pool_w, (0, nb * block - m))

    def one(a):
        xb, wb = a
        _, mind = first_min(sqdist(xb, centers, precision))
        return jnp.sum(mind * wb)

    return jnp.sum(jax.lax.map(one, (xp.reshape(nb, block, d),
                                     wp.reshape(nb, block))))


def fold_chunks(x: np.ndarray, spec: dict, n_dev: int, key,
                precision=HIGHEST, device=None) -> list:
    """Per shard, per chunk: ``(scaled pool, weights)`` of the fold."""
    lo, span = scale_params(x)
    key_local, _, _ = keys(key, n_dev)
    out = []
    for i, (a, b) in enumerate(shard_rows(x.shape[0], n_dev)):
        pools = []
        for j, (s, e) in enumerate(chunk_rows(a, b,
                                              spec["chunk"]["chunk_points"])):
            xs = jax.device_put(_scaled(x[s:e], lo, span), device)
            c, w, _, _ = reference.fold(
                xs, chunk_key(key_local, i, j),
                n_sub=min(spec["partition"]["n_sub"], e - s),
                compression=spec["local"]["compression"],
                iters=spec["local"]["iters"], precision=precision)
            pools.append((c, w))
        out.append(pools)
    return out


def levels(folds: list, spec: dict, n_dev: int, key,
           precision=HIGHEST) -> list:
    """Per shard: its chunk pools concatenated, through every level."""
    _, _, shard_keys = keys(key, n_dev)
    out = []
    for i, pools in enumerate(folds):
        pool = jnp.concatenate([c for c, _ in pools])
        w = jnp.concatenate([w for _, w in pools])
        for jl, lv in enumerate(spec.get("levels", [])):
            pool, w = level(pool, w,
                            jax.random.fold_in(shard_keys[i], 1 + jl),
                            n_sub=lv["n_sub"], compression=lv["compression"],
                            iters=lv["iters"], precision=precision)
        out.append((pool, w))
    return out


def merge_shards(shards: list, spec: dict, n_dev: int, key,
                 precision=HIGHEST):
    """Centers (scaled space) of the merge over per-shard ``(pool, w)``,
    each shard padded to the widest with zero-weight rows."""
    _, key_global, _ = keys(key, n_dev)
    rows = max(int(p.shape[0]) for p, _ in shards)
    pool = jnp.concatenate([jnp.pad(p, ((0, rows - p.shape[0]), (0, 0)))
                            for p, _ in shards])
    w = jnp.concatenate([jnp.pad(w, (0, rows - w.shape[0]))
                         for _, w in shards])
    mg = spec["merge"]
    mw = w if mg.get("weighted", False) else (w > 0).astype(pool.dtype)
    return merge(pool, mw, key_global, k=mg["k"], iters=mg["iters"],
                 n_dev=n_dev, precision=precision)


def sampled_kmeans_dist(x: np.ndarray, spec: dict, n_dev: int, key,
                        precision=HIGHEST, device=None) -> dict:
    """The whole configured fit of host array ``x`` over ``n_dev`` devices,
    computed on ``device``.  Returns, in the input space, ``centers``,
    ``pool``, ``pool_w`` (shards in order) and ``sse`` (pool-weighted,
    uncentered, at ``precision``, as the program reports it), and the
    scaled per-chunk ``folds``."""
    require_supported(spec, x.shape[0], n_dev)
    lo, span = scale_params(x)
    folds = fold_chunks(x, spec, n_dev, key, precision, device)
    shards = levels(folds, spec, n_dev, key, precision)
    centers = merge_shards(shards, spec, n_dev, key, precision)
    lo, span = jnp.asarray(lo), jnp.asarray(span)
    pool = jnp.concatenate([p for p, _ in shards]) * span + lo
    pool_w = jnp.concatenate([w for _, w in shards])
    centers = centers * span + lo
    return {"centers": centers, "pool": pool, "pool_w": pool_w,
            "sse": pool_sse(pool, pool_w, centers, precision=precision,
                            center=False),
            "folds": folds}
