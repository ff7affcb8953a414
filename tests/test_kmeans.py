"""Unit + property tests for the core k-means."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import (kmeans, kmeans_lloyd_step, landmark_init,
                        pairwise_sqdist, sse, update_centers)


def test_pairwise_sqdist_matches_numpy(rng):
    x = rng.normal(size=(50, 7)).astype(np.float32)
    c = rng.normal(size=(11, 7)).astype(np.float32)
    d = np.asarray(pairwise_sqdist(jnp.asarray(x), jnp.asarray(c)))
    ref = ((x[:, None] - c[None]) ** 2).sum(-1)
    np.testing.assert_allclose(d, ref, rtol=1e-4, atol=1e-4)


def test_kmeans_recovers_separated_blobs(blob_data):
    pts, labels, centers = blob_data
    res = kmeans(jnp.asarray(pts), 4, iters=30, key=jax.random.PRNGKey(0))
    # every true center has a found center within a small distance
    found = np.asarray(res.centers)
    for c in centers:
        assert np.min(np.linalg.norm(found - c, axis=1)) < 0.5


def test_weighted_kmeans_ignores_masked_points(rng):
    x = rng.normal(size=(100, 2)).astype(np.float32)
    x[50:] += 100.0  # junk points, masked away
    w = np.concatenate([np.ones(50), np.zeros(50)]).astype(np.float32)
    res = kmeans(jnp.asarray(x), 3, weights=jnp.asarray(w), iters=20,
                 key=jax.random.PRNGKey(1))
    assert np.abs(np.asarray(res.centers)).max() < 10.0


def test_empty_cluster_keeps_old_center():
    x = jnp.zeros((10, 2))
    centers = jnp.asarray([[0.0, 0.0], [5.0, 5.0]])
    idx, _ = (jnp.zeros(10, jnp.int32), None)
    new, counts = update_centers(x, jnp.ones(10), idx, 2, centers)
    np.testing.assert_allclose(np.asarray(new[1]), [5.0, 5.0])
    assert float(counts[1]) == 0.0


@settings(max_examples=25, deadline=None)
@given(m=st.integers(8, 60), d=st.integers(1, 6), k=st.integers(1, 5),
       seed=st.integers(0, 2 ** 30))
def test_property_sse_monotone_under_lloyd(m, d, k, seed):
    """Each Lloyd iteration may not increase the (weighted) SSE."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(m, d)).astype(np.float32))
    w = jnp.ones((m,), jnp.float32)
    centers = landmark_init(x, w, k)
    prev = float(sse(x, centers))
    for _ in range(4):
        centers, _ = kmeans_lloyd_step(x, centers, w)
        cur = float(sse(x, centers))
        assert cur <= prev + 1e-3 + 1e-5 * abs(prev)
        prev = cur


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2 ** 30), k=st.integers(1, 6))
def test_property_centers_in_convex_hull_box(seed, k):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.uniform(-3, 7, size=(40, 3)).astype(np.float32))
    res = kmeans(x, k, iters=10, key=jax.random.PRNGKey(seed % 1000))
    c = np.asarray(res.centers)
    assert (c >= np.asarray(x).min(0) - 1e-4).all()
    assert (c <= np.asarray(x).max(0) + 1e-4).all()


def test_permutation_invariance(rng):
    x = rng.normal(size=(64, 4)).astype(np.float32)
    perm = rng.permutation(64)
    r1 = kmeans(jnp.asarray(x), 4, iters=20, init="landmark")
    r2 = kmeans(jnp.asarray(x[perm]), 4, iters=20, init="landmark")
    # landmark init is permutation-invariant -> same centers (sorted)
    c1 = np.asarray(r1.centers)
    c2 = np.asarray(r2.centers)
    c1 = c1[np.lexsort(c1.T)]
    c2 = c2[np.lexsort(c2.T)]
    np.testing.assert_allclose(c1, c2, rtol=1e-3, atol=1e-3)


# ------------------------------------------------ k-means++ seeding ----

def _kmeans_pp_row_major(x, w, k, key):
    """k-means++ written out over row-major (m, d) points: the draws the
    coordinate-major narrow path has to reproduce bit for bit."""
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
        c = x[jax.random.categorical(jax.random.fold_in(key_loop, i), logits)]
        return (centers.at[i].set(c),
                jnp.minimum(min_d, jnp.sum((x - c) ** 2, axis=-1)))

    return jax.lax.fori_loop(1, k, body, (centers, min_d))[0]


_MASKED = 1e3      # coordinates of every masked row: a draw of one shows


def _seeding_case(shape, d, rng):
    """Points, weights, keys and the vmapped call for one seeding shape:
    ``parts`` are partitions vmapped with their own points (the fold),
    ``pool`` is one shared pool under 4 vmapped restarts (the merge), made
    of 30 distinct rows so the all-zero guard engages before k=50."""
    if shape == "parts":
        x = rng.uniform(size=(8, 300, d)).astype(np.float32)
        w = (rng.uniform(size=(8, 300)) > 0.25).astype(np.float32)
        w[-1, -40:] = 0.0                   # a capacity-padded tail
        x[w == 0] = _MASKED
        keys = jax.random.split(jax.random.PRNGKey(d), 8)
        return x, w, keys, 40, lambda f: jax.vmap(
            lambda a, b, kk: f(a, b, 40, kk))
    x = rng.uniform(size=(30, d)).astype(np.float32)[rng.integers(0, 30, 1200)]
    w = rng.integers(0, 4, 1200).astype(np.float32)
    x[w == 0] = _MASKED
    keys = jax.random.split(jax.random.PRNGKey(100 + d), 4)
    return x, w, keys, 50, lambda f: (
        lambda a, b, ks: jax.vmap(lambda kk: f(a, b, 50, kk))(ks))


@pytest.mark.parametrize("d", [2, 3])
@pytest.mark.parametrize("shape", ["parts", "pool"])
def test_kmeans_pp_narrow_path_draws_the_row_major_centers(shape, d, rng):
    """Below the lane width the D^2 update reads coordinate-major points;
    the centers are the row-major formula's, array_equal, and never a
    masked row."""
    from repro.core import kmeans_pp_init
    x, w, keys, k, batched = _seeding_case(shape, d, rng)
    got = np.asarray(jax.jit(batched(kmeans_pp_init))(x, w, keys))
    want = np.asarray(jax.jit(batched(_kmeans_pp_row_major))(x, w, keys))
    np.testing.assert_array_equal(got, want)
    assert got.shape[-2:] == (k, d)
    assert (got < _MASKED).all()
    live = {tuple(r) for r in x[w > 0].reshape(-1, d)}
    assert all(tuple(c) in live for c in got.reshape(-1, d))


@pytest.mark.parametrize("d, narrow", [(2, True), (128, False)])
def test_kmeans_pp_takes_the_lane_path_only_below_the_lane_width(d, narrow):
    from repro.core import kmeans_pp_init
    x = jnp.ones((64, d), jnp.float32)
    text = jax.jit(kmeans_pp_init, static_argnums=2).lower(
        x, jnp.ones((64,)), 4, jax.random.PRNGKey(0)).as_text(
            debug_info=True)
    assert ("kmeans_pp_lanes" in text) == narrow
