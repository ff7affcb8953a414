"""The plain reference: straightforward ``jax.numpy`` code for what the
cells' timed paths compute, written from the published method and sharing
no code with the program under test.

Sampled k-means (the paper, arXiv:1412.1947, as configured by a cell's
``spec``): min-max feature scaling; the equal-size partition (sort by
squared distance to the per-attribute minimum, cut into ``n_sub``
consecutive runs); per partition a k-means++ seeding and ``iters`` Lloyd
steps to ``cap // compression`` local centers, weighted by slot occupancy;
then the merge, a k-means over the pool of local centers (each live one
weighted 1, or by its member count when ``weighted``) with ``restarts``
seeded runs, the lowest-SSE one kept; centers mapped back to the input
space.  The PRNG keys are derived as the method defines them for this
program's key contract (split into local and global keys, one key per
partition, one per restart), so a run of the reference and a run of the
program from the same key take the same seeding draws.

Exact nearest neighbours: squared distances by brute force over all rows.

Every matmul takes ``precision``: ``HIGHEST`` (full float32) for the
reference; the control passes ``THREE_PASS``, float32 products computed
from truncated bfloat16 halves in three passes (hi*hi + hi*lo + lo*hi,
accumulated in float32), the scheme of ``Precision.HIGH`` on a TPU,
written out so that it means the same on every platform.  Nearest centers are picked as the first
index attaining the row minimum, so no reduction is narrowed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
THREE_PASS = "three_pass"


def _bf16_trunc(a):
    """``a`` cut to its leading bfloat16 bits (the low 16 bits dropped)."""
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def _halves(a):
    hi = _bf16_trunc(a)
    return hi, _bf16_trunc(a - hi)


def matmul(a, b, precision=HIGHEST):
    if precision == THREE_PASS:
        (ah, al), (bh, bl) = _halves(a), _halves(b)
        mm = functools.partial(jnp.matmul, precision=HIGHEST)
        return mm(ah, bh) + (mm(ah, bl) + mm(al, bh))
    return jnp.matmul(a, b, precision=precision)


def sqdist(x, c, precision=HIGHEST):
    """(m, d), (k, d) -> (m, k) squared distances, clamped at 0."""
    x2 = jnp.sum(x * x, axis=-1, keepdims=True)
    c2 = jnp.sum(c * c, axis=-1)
    xc = matmul(x, c.T, precision)
    return jnp.maximum(x2 + c2[None, :] - 2.0 * xc, 0.0)


def first_min(d):
    """Index of the first minimum along the last axis, and the minimum."""
    m = jnp.min(d, axis=-1)
    return jnp.argmax(d <= m[..., None], axis=-1).astype(jnp.int32), m


# ---------------------------------------------------------------------------
# sampled k-means
# ---------------------------------------------------------------------------

def kmeanspp(x, w, k: int, key):
    """k-means++ seeding: first center uniform over live rows, then each by
    D^2 weighting (``fold_in(loop key, i)`` for center ``i``)."""
    key0, key_loop = jax.random.split(key)
    first = jax.random.categorical(key0, jnp.where(w > 0, 0.0, -jnp.inf))
    centers = jnp.zeros((k, x.shape[1]), x.dtype).at[0].set(x[first])
    min_d = jnp.sum((x - x[first]) ** 2, axis=-1)

    def body(i, carry):
        centers, min_d = carry
        p = min_d * w
        logits = jnp.where(p > 0, jnp.log(jnp.maximum(p, 1e-30)), -jnp.inf)
        logits = jnp.where(jnp.all(~jnp.isfinite(logits)),
                           jnp.where(w > 0, 0.0, -jnp.inf), logits)
        nxt = jax.random.categorical(jax.random.fold_in(key_loop, i), logits)
        c = x[nxt]
        return (centers.at[i].set(c),
                jnp.minimum(min_d, jnp.sum((x - c) ** 2, axis=-1)))

    centers, _ = jax.lax.fori_loop(1, k, body, (centers, min_d))
    return centers


def lloyd(x, w, centers, iters: int, precision=HIGHEST):
    """``iters`` weighted Lloyd steps (an empty cluster keeps its center),
    then the final assignment: ``(centers, counts, sse)``."""
    k = centers.shape[0]

    def step(_, c):
        idx, _ = first_min(sqdist(x, c, precision))
        onehot = jax.nn.one_hot(idx, k, dtype=jnp.float32) * w[:, None]
        sums = matmul(onehot.T, x, precision)
        counts = jnp.sum(onehot, axis=0)
        new = sums / jnp.maximum(counts, 1e-12)[:, None]
        return jnp.where((counts > 0)[:, None], new, c)

    centers = jax.lax.fori_loop(0, iters, step, centers)
    idx, mind = first_min(sqdist(x, centers, precision))
    counts = jnp.zeros((k,), jnp.float32).at[idx].add(w)
    return centers, counts, jnp.sum(mind * w)


def equal_partition(xs, n_sub: int):
    """Row ids ``(n_sub, cap)`` and 0/1 slot weights of the equal-size
    partition (trailing slots of the last partition are empty)."""
    m = xs.shape[0]
    cap = -(-m // n_sub)
    d = jnp.sum((xs - jnp.min(xs, axis=0)) ** 2, axis=-1)
    order = jnp.argsort(d, stable=True).astype(jnp.int32)
    order = jnp.concatenate(
        [order, jnp.full((n_sub * cap - m,), -1, jnp.int32)])
    idx = order.reshape(n_sub, cap)
    return jnp.where(idx >= 0, idx, 0), (idx >= 0).astype(xs.dtype)


@functools.partial(jax.jit, static_argnames=("n_sub", "compression",
                                             "iters", "precision"))
def fold(xs, key, *, n_sub: int, compression: int, iters: int,
         precision=HIGHEST):
    """Partition + per-partition k-means of scaled points: the pool
    ``(n_sub * k_local, d)``, its member counts, and the row ids of each
    partition's slots."""
    ids, w = equal_partition(xs, n_sub)
    parts = xs[ids]
    k_local = max(1, ids.shape[1] // compression)
    keys = jax.random.split(key, n_sub)
    init = jax.vmap(lambda p, pw, kk: kmeanspp(p, pw, k_local, kk))(
        parts, w, keys)
    centers, counts, _ = jax.lax.map(
        lambda a: lloyd(a[0], a[1], a[2], iters, precision),
        (parts, w, init))
    d = xs.shape[1]
    return (centers.reshape(n_sub * k_local, d),
            counts.reshape(n_sub * k_local), ids, w)


@functools.partial(jax.jit, static_argnames=("k", "iters", "restarts",
                                             "precision"))
def merge(pool, pool_w, key, *, k: int, iters: int, restarts: int,
          precision=HIGHEST):
    """Seeded restarts of weighted k-means over the pool; the lowest-SSE
    run's centers."""
    keys = jax.random.split(key, restarts)

    def run(kk):
        c0 = kmeanspp(pool, pool_w, k, kk)
        c, _, s = lloyd(pool, pool_w, c0, iters, precision)
        return c, s

    cs, ss = jax.lax.map(run, keys)
    return cs[jnp.argmin(ss)]


# What the reference implements, field by field of a spec's plain-data
# form: a tuple lists the values it implements, None takes any value (an
# execution detail that does not change the result, or a number the
# reference reads).  A field missing here, or a value outside its tuple,
# is refused: the reference would compute another algorithm.
SUPPORTED = {
    "partition": {"scheme": ("equal",), "n_sub": None,
                  "capacity_factor": None},   # read by other schemes only
    "local": {"compression": None, "iters": None, "init": ("kmeans++",),
              "stop": (None,)},
    "merge": {"k": None, "iters": None, "weighted": None, "restarts": None,
              "init": ("kmeans++",), "stop": (None,)},
    "execution": {"backend": None, "mode": ("auto", "single"),
                  "mesh_axis": None, "donate": None,
                  "merge_path": None, "telemetry": None},
    "scale": None,
    "levels": ([],),
    "chunk": None,                 # read by the chunked modes only
}


def require_supported(spec: dict) -> None:
    """Raise ``ValueError`` for any field of ``spec`` that the reference
    does not implement as set."""
    def check(path, value, rule):
        if isinstance(rule, dict):
            if not isinstance(value, dict):
                raise ValueError(f"reference: {path} is not a section")
            for k, v in value.items():
                if k not in rule:
                    raise ValueError(f"reference: {path}.{k} is not "
                                     f"implemented")
                check(f"{path}.{k}", v, rule[k])
        elif rule is not None and value not in rule:
            raise ValueError(f"reference: {path}={value!r} is not "
                             f"implemented (only {list(rule)})")
    check("spec", spec, SUPPORTED)


def sampled_kmeans(x, spec: dict, key, precision=HIGHEST) -> dict:
    """The configured sampled k-means on resident points ``x``.  ``spec``
    is the configuration's plain-data spec (``partition``, ``local``,
    ``merge``, ``scale``); a field the reference does not implement is
    refused (``require_supported``).  Returns centers, pool, pool weights
    and the fit's SSE (computed like the program reports it: uncentered,
    at ``precision``)."""
    require_supported(spec)
    key_local, key_global = jax.random.split(key)
    if spec.get("scale", True):
        lo = jnp.min(x, axis=0)
        span = jnp.maximum(jnp.max(x, axis=0) - lo, 1e-9)
    else:
        lo, span = jnp.zeros(x.shape[1]), jnp.ones(x.shape[1])
    xs = (x - lo) / span
    pool, pool_w, _, _ = fold(
        xs, key_local, n_sub=spec["partition"]["n_sub"],
        compression=spec["local"]["compression"],
        iters=spec["local"]["iters"], precision=precision)
    mg = spec["merge"]
    merge_w = pool_w if mg.get("weighted", False) else (
        pool_w > 0).astype(pool.dtype)
    centers = merge(pool, merge_w, key_global, k=mg["k"], iters=mg["iters"],
                    restarts=mg.get("restarts", 4), precision=precision)
    centers = centers * span + lo
    return {"centers": centers, "pool": pool * span + lo, "pool_w": pool_w,
            "sse": min_sqdist_sum(x, centers, precision=precision,
                                  center=False)}


# ---------------------------------------------------------------------------
# evaluation: SSE, fold assignment counts, exact k-NN
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("precision", "center",
                                             "block"))
def min_sqdist_sum(x, centers, *, precision=HIGHEST, center=True,
                   block: int = 8192):
    """Sum over rows of the squared distance to the nearest center, in row
    blocks.  ``center`` subtracts the data mean from both sides first,
    which keeps the distance expansion from cancelling."""
    if center:
        mu = jnp.mean(x, axis=0)
        x, centers = x - mu, centers - mu
    m, d = x.shape
    nb = -(-m // block)
    xp = jnp.pad(x, ((0, nb * block - m), (0, 0)))
    valid = (jnp.arange(nb * block) < m).reshape(nb, block)

    def one(args):
        xb, vb = args
        _, mind = first_min(sqdist(xb, centers, precision))
        return jnp.sum(jnp.where(vb, mind, 0.0))

    return jnp.sum(jax.lax.map(one, (xp.reshape(nb, block, d), valid)))


@functools.partial(jax.jit, static_argnames=("n_sub", "k_local"))
def fold_counts(xs, pool, *, n_sub: int, k_local: int):
    """Member counts of each pool row when every point of a partition goes
    to the nearest of its own partition's ``k_local`` pool rows (scaled
    space, partition-centered, at HIGHEST)."""
    ids, w = equal_partition(xs, n_sub)
    parts = xs[ids]
    cents = pool.reshape(n_sub, k_local, xs.shape[1])

    def one(args):
        p, pw, c = args
        mu = jnp.sum(p * pw[:, None], axis=0) / jnp.sum(pw)
        idx, _ = first_min(sqdist(p - mu, c - mu, HIGHEST))
        return jnp.zeros((k_local,), jnp.float32).at[idx].add(pw)

    return jax.lax.map(one, (parts, w, cents)).reshape(n_sub * k_local)


@jax.jit
def row_sqdist(x, queries, ids):
    """Squared distance from each query to the rows ``ids`` (nq, j) of
    ``x``, by differences."""
    return jnp.sum((x[ids] - queries[:, None, :]) ** 2, axis=-1)


@functools.partial(jax.jit, static_argnames=("k", "block"))
def exact_knn(x, queries, *, k: int, block: int = 65536):
    """Ids of the ``k`` nearest rows of ``x`` for each query (data-mean
    centered, at HIGHEST), nearest first."""
    mu = jnp.mean(x, axis=0)
    q = queries - mu
    m, d = x.shape
    nb = -(-m // block)
    xp = jnp.pad(x - mu, ((0, nb * block - m), (0, 0)))
    nq = q.shape[0]

    def body(carry, b):
        best_d, best_i = carry
        xb = jax.lax.dynamic_slice_in_dim(xp, b * block, block)
        dist = sqdist(q, xb, HIGHEST)
        ids = b * block + jnp.arange(block, dtype=jnp.int32)
        dist = jnp.where(ids[None, :] < m, dist, jnp.inf)
        cat_d = jnp.concatenate([best_d, dist], axis=1)
        cat_i = jnp.concatenate(
            [best_i, jnp.broadcast_to(ids, (nq, block))], axis=1)
        neg, pos = jax.lax.top_k(-cat_d, k)
        return (-neg, jnp.take_along_axis(cat_i, pos, axis=1)), None

    init = (jnp.full((nq, k), jnp.inf, jnp.float32),
            jnp.full((nq, k), -1, jnp.int32))
    (_, best_i), _ = jax.lax.scan(body, init, jnp.arange(nb))
    return best_i
