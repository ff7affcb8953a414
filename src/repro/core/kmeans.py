"""Lloyd's k-means in pure JAX.

This is the work-horse the paper runs (a) inside every subcluster and (b) on
the gathered local centers.  Everything is static-shape / jit / vmap friendly:

  * points may carry *weights* (0 = padded/masked point) so capacity-padded
    partitions from :mod:`repro.core.subcluster` cluster correctly;
  * the Lloyd machinery is pluggable through the :class:`LloydBackend`
    registry (:mod:`repro.core.backend`): ``"jnp"`` reference, unfused
    ``"pallas"`` kernels, the fused single-pass ``"pallas_fused"`` kernel, or
    ``"auto"`` (env-overridable via ``REPRO_KMEANS_BACKEND``).  Padding is
    done once per call, outside the iteration loop;
  * empty clusters keep their previous center (standard Lloyd fix-up).
"""
from __future__ import annotations

import warnings
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from .backend import BackendSpec, LloydBackend, AssignFnBackend, get_backend
from .metrics import HIGHEST, nearest
from .spec import StopSpec
from repro.telemetry import scope

Array = jax.Array

# salt for deriving the mini-batch sampling stream from a run key, so the
# init draw sees the exact same key it always did
_MINIBATCH_SALT = 0x6D62


class KMeansResult(NamedTuple):
    centers: Array      # (k, d) final centroids
    assignment: Array   # (m,) int32 cluster id per point
    sse: Array          # () weighted sum of squared distances
    counts: Array       # (k,) weighted member count per cluster
    n_iter: Array       # () number of Lloyd iterations executed


def pairwise_sqdist(x: Array, c: Array) -> Array:
    """(m, d) x (k, d) -> (m, k) squared euclidean distances.

    Uses the expansion ||x - c||^2 = ||x||^2 + ||c||^2 - 2 x.c so the inner
    product hits the MXU; clamped at zero against fp cancellation.
    """
    x2 = jnp.sum(x * x, axis=-1, keepdims=True)
    c2 = jnp.sum(c * c, axis=-1)
    xc = jnp.matmul(x, c.T, precision=HIGHEST)
    return jnp.maximum(x2 + c2[None, :] - 2.0 * xc, 0.0)


def assign_jnp(x: Array, c: Array) -> tuple[Array, Array]:
    """Reference assignment step: nearest center id + its squared distance."""
    d = pairwise_sqdist(x, c)
    idx = nearest(d).astype(jnp.int32)
    mind = jnp.take_along_axis(d, idx[:, None].astype(jnp.int32), axis=-1)[:, 0]
    return idx, mind


AssignFn = Callable[[Array, Array], tuple[Array, Array]]


def update_centers(
    x: Array, weights: Array, idx: Array, k: int, old_centers: Array
) -> tuple[Array, Array]:
    """Weighted centroid update via one-hot matmul (TPU-friendly scatter)."""
    onehot = jax.nn.one_hot(idx, k, dtype=x.dtype) * weights[:, None]
    counts = onehot.sum(axis=0)
    sums = jnp.matmul(onehot.T, x, precision=HIGHEST)
    new = sums / jnp.maximum(counts, 1e-12)[:, None]
    keep_old = (counts <= 0.0)[:, None]
    return jnp.where(keep_old, old_centers, new), counts


def _centers_from_stats(sums: Array, counts: Array, old_centers: Array
                        ) -> Array:
    """Divide raw backend statistics, keeping old centers for empty
    clusters (standard Lloyd fix-up) and the carry dtype stable."""
    new = (sums / jnp.maximum(counts, 1e-12)[:, None]).astype(old_centers.dtype)
    return jnp.where((counts <= 0.0)[:, None], old_centers, new)


# ---------------------------------------------------------------------------
# Initialisation schemes
# ---------------------------------------------------------------------------

def random_init(x: Array, weights: Array, k: int, key: Array) -> Array:
    """Sample k distinct points with probability proportional to weight.

    Gumbel top-k gives weighted sampling *without replacement*, so k centers
    cannot collide on small partitions (collided centers = permanently dead
    clusters under the keep-old-center fix-up).  If fewer than k points have
    positive weight the remainder falls back to with-replacement draws among
    the valid points (duplicates are then unavoidable).
    """
    logits = jnp.where(weights > 0, jnp.log(jnp.maximum(weights, 1e-30)),
                       -jnp.inf)
    key_g, key_fb = jax.random.split(key)
    scores = logits + jax.random.gumbel(key_g, logits.shape)
    top_scores, ids = jax.lax.top_k(scores, k)
    fallback = jax.random.categorical(key_fb, logits, shape=(k,))
    ids = jnp.where(jnp.isfinite(top_scores), ids, fallback)
    return x[ids]


def landmark_init(x: Array, weights: Array, k: int, key: Array | None = None) -> Array:
    """The paper's Algorithm-2 landmark construction used as a k-means init:
    k evenly spaced points on the segment [per-attribute min, per-attribute max].

    Masked points are pushed out of the min/max with +/-inf sentinels.
    """
    del key
    big = jnp.asarray(jnp.finfo(x.dtype).max, x.dtype)
    valid = (weights > 0)[:, None]
    lo = jnp.min(jnp.where(valid, x, big), axis=0)
    hi = jnp.max(jnp.where(valid, x, -big), axis=0)
    t = jnp.linspace(0.0, 1.0, k, dtype=x.dtype)[:, None]
    return lo[None, :] + t * (hi - lo)[None, :]


def kmeans_pp_init(x: Array, weights: Array, k: int, key: Array) -> Array:
    """k-means++ (D^2 weighting), incremental min-distance bookkeeping."""
    m = x.shape[0]
    key0, key_loop = jax.random.split(key)
    first = jax.random.categorical(key0, jnp.where(weights > 0, 0.0, -jnp.inf))
    centers0 = jnp.zeros((k,) + x.shape[1:], x.dtype).at[0].set(x[first])
    d0 = jnp.sum((x - x[first]) ** 2, axis=-1)

    def body(i, carry):
        centers, min_d = carry
        kk = jax.random.fold_in(key_loop, i)
        p = min_d * weights
        logits = jnp.where(p > 0, jnp.log(jnp.maximum(p, 1e-30)), -jnp.inf)
        # All-zero guard (all points coincide with chosen centers): uniform.
        logits = jnp.where(jnp.all(~jnp.isfinite(logits)),
                           jnp.where(weights > 0, 0.0, -jnp.inf), logits)
        nxt = jax.random.categorical(kk, logits)
        c = x[nxt]
        centers = centers.at[i].set(c)
        min_d = jnp.minimum(min_d, jnp.sum((x - c) ** 2, axis=-1))
        return centers, min_d

    centers, _ = jax.lax.fori_loop(1, k, body, (centers0, d0))
    return centers


def kmeans_parallel_init(x: Array, weights: Array, k: int, key: Array,
                         *, rounds: int = 3,
                         oversample: int | None = None) -> Array:
    """k-means|| (Bahmani et al., Scalable K-Means++): oversample-then-reduce.

    Instead of k strictly sequential D²-draws, each of ``rounds`` rounds
    draws ``oversample`` (default 2k) candidates *jointly* with probability
    proportional to ``weight * min_dist²`` (Gumbel top-k = weighted sampling
    without replacement — the static-shape stand-in for the paper's
    independent coin flips).  The ~``rounds * 2k`` candidates are then
    weighted by the point mass they attract and reduced to k centers by
    weighted k-means++.  Depth drops from O(k) dependent steps to
    O(rounds) — the right init for large k and for the merge stage, where
    the points are already weighted representatives.
    """
    m = x.shape[0]
    # top_k cannot draw more than m candidates per round; the merge stage
    # routinely runs with m only a few multiples of k, so clamp
    l = min(oversample or 2 * k, m)
    key0, key_rounds, key_reduce = jax.random.split(key, 3)

    first = jax.random.categorical(key0, jnp.where(weights > 0, 0.0, -jnp.inf))
    min_d = jnp.sum((x - x[first]) ** 2, axis=-1)
    n_cand = 1 + rounds * l
    cand = jnp.zeros((n_cand,) + x.shape[1:], x.dtype).at[0].set(x[first])
    cand_valid = jnp.zeros((n_cand,), bool).at[0].set(True)

    def round_body(r, carry):
        cand, cand_valid, min_d = carry
        kk = jax.random.fold_in(key_rounds, r)
        p = min_d * weights
        logits = jnp.where(p > 0, jnp.log(jnp.maximum(p, 1e-30)), -jnp.inf)
        scores = logits + jax.random.gumbel(kk, logits.shape)
        top_scores, ids = jax.lax.top_k(scores, l)
        ok = jnp.isfinite(top_scores)          # fewer than l useful points?
        picked = x[ids]
        slot = 1 + r * l + jnp.arange(l)
        cand = cand.at[slot].set(jnp.where(ok[:, None], picked, 0.0))
        cand_valid = cand_valid.at[slot].set(ok)
        # one distance update per ROUND (not per candidate): new min over
        # the l fresh candidates, masked to the ones actually drawn
        d_new = pairwise_sqdist(x, picked)
        d_new = jnp.where(ok[None, :], d_new, jnp.inf)
        return cand, cand_valid, jnp.minimum(min_d, jnp.min(d_new, axis=-1))

    cand, cand_valid, _ = jax.lax.fori_loop(
        0, rounds, round_body, (cand, cand_valid, min_d))

    # weight candidates by the point mass they attract, then reduce with
    # the sequential k-means++ on the (small) candidate set only
    d2 = pairwise_sqdist(x, cand)
    d2 = jnp.where(cand_valid[None, :], d2, jnp.inf)
    cand_w = (jax.nn.one_hot(nearest(d2), n_cand, dtype=jnp.float32)
              * weights[:, None].astype(jnp.float32)).sum(axis=0)
    cand_w = jnp.where(cand_valid, jnp.maximum(cand_w, 1e-12), 0.0)
    return kmeans_pp_init(cand, cand_w.astype(x.dtype), k, key_reduce)


# ---------------------------------------------------------------------------
# Init registry — what ``LocalSpec.init`` / ``MergeSpec.init`` resolve
# against.  An init maps ``(x, weights, k, key) -> (k, d) centers``.
# ---------------------------------------------------------------------------

InitFn = Callable[[Array, Array, int, Array], Array]

_INITS: dict[str, InitFn] = {
    "random": random_init,
    "landmark": landmark_init,
    "kmeans++": kmeans_pp_init,
    "kmeans||": kmeans_parallel_init,
}


def register_init(name: str, fn: InitFn) -> None:
    """Register ``fn(x, weights, k, key) -> centers`` as an init scheme."""
    _INITS[name] = fn


def get_init(name: str) -> InitFn:
    try:
        return _INITS[name]
    except KeyError:
        raise ValueError(
            f"unknown init scheme {name!r}; known: {sorted(_INITS)}"
        ) from None


def available_inits() -> tuple[str, ...]:
    return tuple(sorted(_INITS))


def _jittered_array_init(init: Array, x: Array, key: Array,
                         r: Array | int) -> Array:
    """Restart r of an explicit array init: r=0 keeps the given centers
    verbatim; r>0 perturbs them with noise scaled to the per-dimension
    spread of the *data* (not the init — a degenerate init with coincident
    centers has zero spread, and that is exactly when jitter matters)."""
    sigma = 0.05 * jnp.std(x, axis=0, keepdims=True).astype(init.dtype) + 1e-6
    noise = sigma * jax.random.normal(key, init.shape, init.dtype)
    keep = jnp.asarray(r, jnp.int32) == 0
    return jnp.where(keep, init, init + noise)


# ---------------------------------------------------------------------------
# Lloyd's algorithm
# ---------------------------------------------------------------------------

def _stop_update(stop: StopSpec, *, sse: Array, prev_sse: Array,
                 new_centers: Array, old_centers: Array, i: Array,
                 streak: Array) -> tuple[Array, Array]:
    """Convergence bookkeeping for one Lloyd iteration under a ``tol>0``
    policy: returns the updated consecutive-hit ``streak`` and the ``done``
    flag.  ``sse`` is the backend step's convergence scalar (SSE measured
    at ``old_centers``); ``prev_sse`` is the same scalar one iteration ago
    (+inf on the first iteration, which therefore never converges)."""
    if stop.metric == "rel_sse":
        impr = (prev_sse - sse) / jnp.maximum(prev_sse, 1e-30)
        hit = jnp.isfinite(prev_sse) & (impr <= stop.tol)
    else:                                            # "center_shift"
        shift2 = jnp.max(jnp.sum(
            (new_centers.astype(jnp.float32)
             - old_centers.astype(jnp.float32)) ** 2, axis=-1))
        hit = jnp.sqrt(shift2) <= stop.tol
    streak = jnp.where(hit, streak + 1, jnp.zeros_like(streak))
    done = (streak >= stop.patience) & (i + 1 >= stop.min_iters)
    return streak, done


def _lloyd_converged(be: LloydBackend, prep, centers0: Array,
                     stop: StopSpec) -> tuple[Array, Array]:
    """Full-batch Lloyd under a ``tol>0`` policy: ``lax.while_loop`` with a
    data-dependent exit.  Under vmap, JAX's while batching rule masks the
    carry per lane (converged lanes freeze via ``select``) and the loop
    runs until every lane is done — static shapes throughout.  Returns
    ``(centers, n_iter)`` where ``n_iter`` is the per-lane true count."""
    def cond(carry):
        i, _, _, _, done = carry
        return (i < stop.max_iters) & jnp.logical_not(done)

    def body(carry):
        i, centers, prev_sse, streak, _ = carry
        sums, counts, sse = be.step(prep, centers)
        sse = sse.astype(jnp.float32)
        new = _centers_from_stats(sums, counts, centers)
        streak, done = _stop_update(
            stop, sse=sse, prev_sse=prev_sse, new_centers=new,
            old_centers=centers, i=i, streak=streak)
        return i + 1, new, sse, streak, done

    carry0 = (jnp.asarray(0, jnp.int32), centers0,
              jnp.asarray(jnp.inf, jnp.float32),
              jnp.asarray(0, jnp.int32), jnp.asarray(False))
    n_iter, centers, _, _, _ = jax.lax.while_loop(cond, body, carry0)
    return centers, n_iter


def _lloyd_minibatch(be: LloydBackend, x: Array, weights: Array,
                     centers0: Array, stop: StopSpec,
                     key: Array) -> tuple[Array, Array]:
    """Mini-batch Lloyd (Sculley-style) for huge pools: each iteration
    samples ``stop.minibatch`` rows weight-proportionally (with
    replacement, unit sample weight — mass enters through the sampling
    probabilities), runs one backend step on the block, and moves each
    center toward its batch mean with the running cumulative-count
    learning rate ``counts / cum_counts``.  ``tol>0`` early exit applies
    to the (noisy) per-batch convergence scalar — raise ``patience`` to
    taste; ``tol=0`` runs all ``max_iters`` batches."""
    b = min(int(stop.minibatch), int(x.shape[0]))
    logits = jnp.where(
        weights > 0,
        jnp.log(jnp.maximum(weights.astype(jnp.float32), 1e-30)), -jnp.inf)
    ones = jnp.ones((b,), x.dtype)
    k = centers0.shape[0]

    def cond(carry):
        i, _, _, _, _, done = carry
        return (i < stop.max_iters) & jnp.logical_not(done)

    def body(carry):
        i, centers, cum_counts, prev_sse, streak, done = carry
        kk = jax.random.fold_in(key, i)
        ids = jax.random.categorical(kk, logits, shape=(b,))
        sums, counts, sse = be.step(be.prepare(x[ids], ones), centers)
        sse = sse.astype(jnp.float32)
        cum_counts = cum_counts + counts
        batch_mean = sums / jnp.maximum(counts, 1e-12)[:, None]
        lr = (counts / jnp.maximum(cum_counts, 1e-12))[:, None]
        stepped = ((1.0 - lr) * centers.astype(jnp.float32)
                   + lr * batch_mean).astype(centers.dtype)
        new = jnp.where((counts <= 0.0)[:, None], centers, stepped)
        if stop.tol > 0:
            streak, done = _stop_update(
                stop, sse=sse, prev_sse=prev_sse, new_centers=new,
                old_centers=centers, i=i, streak=streak)
        return i + 1, new, cum_counts, sse, streak, done

    carry0 = (jnp.asarray(0, jnp.int32), centers0,
              jnp.zeros((k,), jnp.float32),
              jnp.asarray(jnp.inf, jnp.float32),
              jnp.asarray(0, jnp.int32), jnp.asarray(False))
    n_iter, centers, _, _, _, _ = jax.lax.while_loop(cond, body, carry0)
    return centers, n_iter


def kmeans(
    x: Array,
    k: int,
    *,
    weights: Optional[Array] = None,
    iters: Optional[int] = None,
    key: Optional[Array] = None,
    init: str | Array = "kmeans++",
    backend: BackendSpec = None,
    assign_fn: Optional[AssignFn] = None,
    restarts: int = 1,
    stop: Optional[StopSpec] = None,
) -> KMeansResult:
    """Weighted Lloyd's k-means under a :class:`~repro.core.spec.StopSpec`
    iteration contract.

    ``stop`` is the canonical way to bound the loop; ``iters`` survives as
    a deprecated alias for ``StopSpec(max_iters=iters)`` (passing both
    raises).  The default policy (``tol=0``) runs a *static*
    trip-count ``fori_loop`` — vmap-able across subclusters, shard_map
    friendly, and — at pod scale — a straggler-mitigation device in itself
    (every subcluster costs the same, no data-dependent tail) — bit-for-bit
    the historical fixed-``iters`` behavior.  ``stop.tol > 0`` switches to
    a ``lax.while_loop`` that exits once the convergence metric
    (relative SSE improvement or max center shift) stays at or below
    ``tol`` for ``patience`` consecutive iterations; ``stop.minibatch > 0``
    switches to sampled mini-batch center updates (meant for the merge
    stage over huge pools).  ``KMeansResult.n_iter`` reports the number of
    Lloyd iterations actually executed (of the best restart).

    ``backend`` selects the Lloyd machinery (see :mod:`repro.core.backend`);
    its ``step`` already returns the SSE convergence scalar alongside the
    raw stats, so the early-exit test costs no extra pass.  ``assign_fn``
    is the legacy hook, adapted onto the registry when given.  With
    ``restarts > 1`` the lowest-SSE of several independent runs wins; an
    explicit array ``init`` participates too (restart 0 uses it verbatim,
    later restarts jitter it — see :func:`_jittered_array_init`).
    """
    if stop is None:
        stop = StopSpec(max_iters=25 if iters is None else iters)
    elif iters is not None:
        raise TypeError(
            "kmeans: pass either stop= or the deprecated iters= alias, "
            "not both")
    m = x.shape[0]
    if weights is None:
        weights = jnp.ones((m,), x.dtype)
    weights = weights.astype(x.dtype)
    if key is None:
        key = jax.random.PRNGKey(0)

    if assign_fn is not None:
        warnings.warn(
            "kmeans(assign_fn=...) is deprecated: pass backend= (a name or "
            "LloydBackend instance, see repro.core.backend) instead; the "
            "assign_fn adapter pays the one-hot update and per-iteration "
            "padding the backends hoist",
            DeprecationWarning, stacklevel=2)
        be = AssignFnBackend(assign_fn)
    else:
        be = get_backend(backend)
    prep = be.prepare(x, weights)   # pad ONCE, outside the Lloyd loop
    w32 = weights.astype(jnp.float32)

    def lloyd(centers0, run_key):
        if stop.minibatch > 0:
            centers, n_iter = _lloyd_minibatch(
                be, x, weights, centers0, stop,
                jax.random.fold_in(run_key, _MINIBATCH_SALT))
        elif stop.tol > 0:
            centers, n_iter = _lloyd_converged(be, prep, centers0, stop)
        else:
            # static-trip path: the pre-StopSpec trace, bit for bit
            def body(_, centers):
                sums, counts, _ = be.step(prep, centers)
                return _centers_from_stats(sums, counts, centers)

            centers = jax.lax.fori_loop(0, stop.max_iters, body, centers0)
            n_iter = jnp.asarray(stop.max_iters, jnp.int32)
        idx, mind = be.assign(prep, centers)
        sse = jnp.sum(mind * w32)
        return centers, idx, sse, n_iter

    def one_run(kk, r):
        # every seeding (k-means++, k-means||, random, an array's jitter)
        # under one name, in the fold's lanes and the merge's restarts
        with scope("kmeans_init"):
            if isinstance(init, str):
                centers0 = get_init(init)(x, weights, k, kk)
            else:
                centers0 = _jittered_array_init(init, x, kk, r)
        return lloyd(centers0, kk)

    if restarts <= 1:
        centers, idx, sse, n_iter = one_run(key, 0)
    else:
        # multi-seed restart: rerun Lloyd from independent inits, keep the
        # lowest-SSE solution (vmap'd so the restarts batch on device);
        # an array init restarts from jittered copies of itself (r=0 exact)
        keys = jax.random.split(key, restarts)
        centers_r, idx_r, sse_r, n_iter_r = jax.vmap(one_run)(
            keys, jnp.arange(restarts))
        best = jnp.argmin(sse_r)
        centers = jnp.take(centers_r, best, axis=0)
        idx = jnp.take(idx_r, best, axis=0)
        sse = jnp.take(sse_r, best, axis=0)
        n_iter = jnp.take(n_iter_r, best, axis=0)

    counts = jnp.zeros((k,), weights.dtype).at[idx].add(weights)
    return KMeansResult(centers, idx, sse, counts, n_iter)


def kmeans_lloyd_step(
    x: Array, centers: Array, weights: Array,
    backend: BackendSpec = None,
) -> tuple[Array, Array]:
    """One exposed Lloyd iteration (used by the roofline cost parts and
    tests)."""
    be = get_backend(backend)
    prep = be.prepare(x, weights)
    sums, counts, _ = be.step(prep, centers)
    return _centers_from_stats(sums, counts, centers), counts
