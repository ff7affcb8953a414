"""The out-of-core chunked executor (``mode="chunked"``): DataSource
adapters, single-chunk bit-for-bit parity with the resident pipeline,
chunk-size/bf16/weighted/levels sweeps, ragged-shape edge cases, the
blocked predict-side metrics, and the >=4x-larger-than-resident
acceptance pin."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import SampledKMeans, execute, plan
from repro.core import (ChunkSpec, ClusterSpec, ExecutionSpec, LevelSpec,
                        LocalSpec, MergeSpec, PartitionSpec, fit_chunked,
                        fit_from_spec, min_sqdist, relative_error,
                        scale_pass, sse)
from repro.core.metrics import BLOCKED_RTOL
from repro.core.subcluster import feature_scale
from repro.data import (ArraySource, IterSource, SyntheticSource, as_source,
                        prefetch_to_device)
from repro.data.synthetic import blobs


@pytest.fixture(scope="module")
def dataset():
    pts, labels, _ = blobs(2000, n_clusters=5, dim=3, seed=7)
    return jnp.asarray(pts), labels


SPEC = ClusterSpec(
    partition=PartitionSpec(scheme="equal", n_sub=8),
    local=LocalSpec(compression=5, iters=8),
    merge=MergeSpec(k=5, iters=15),
)


def _chunked(spec, **chunk_kwargs):
    return spec.replace(chunk=ChunkSpec(**chunk_kwargs),
                        execution=ExecutionSpec(mode="chunked"))


# ---------------------------------------------------------------------------
# Parity: single chunk bit-for-bit, multi-chunk within tolerance
# ---------------------------------------------------------------------------

def test_single_chunk_bit_for_bit(dataset):
    """A source that fits in one chunk IS fit_from_spec, bit for bit."""
    x, _ = dataset
    key = jax.random.PRNGKey(3)
    ref = fit_from_spec(x, SPEC, key)
    res, stats = fit_chunked(ArraySource(x), _chunked(SPEC, chunk_points=4096),
                             key)
    assert stats.n_chunks == 1
    np.testing.assert_array_equal(np.asarray(ref.centers),
                                  np.asarray(res.centers))
    np.testing.assert_array_equal(np.asarray(ref.local_centers),
                                  np.asarray(res.local_centers))
    np.testing.assert_array_equal(np.asarray(ref.local_weights),
                                  np.asarray(res.local_weights))
    assert float(ref.sse) == float(res.sse)
    assert int(ref.n_dropped) == int(res.n_dropped)


def test_single_chunk_bit_for_bit_via_facade(dataset):
    x, _ = dataset
    key = jax.random.PRNGKey(11)
    ref = fit_from_spec(x, SPEC, key)
    est = SampledKMeans(_chunked(SPEC, chunk_points=4096)).fit(
        ArraySource(x), key=key)
    np.testing.assert_array_equal(np.asarray(ref.centers),
                                  np.asarray(est.centers_))
    assert float(ref.sse) == float(est.sse_)
    assert est.chunk_stats_.n_chunks == 1


@pytest.mark.parametrize("n_chunks", [4, 16])
def test_multi_chunk_sse_tolerance(dataset, n_chunks):
    """Chunked folds see only a slice of the data per partition pass; the
    merged solution must stay close to the flat batch fit."""
    x, _ = dataset
    key = jax.random.PRNGKey(0)
    ref = float(fit_from_spec(x, SPEC, key).sse)
    spec = _chunked(SPEC, chunk_points=x.shape[0] // n_chunks)
    res, stats = fit_chunked(ArraySource(x), spec, key)
    assert stats.n_chunks == n_chunks
    assert abs(relative_error(float(res.sse), ref)) < 0.15, (
        n_chunks, float(res.sse), ref)


def test_chunked_bf16(dataset):
    x, _ = dataset
    xb = x.astype(jnp.bfloat16)
    key = jax.random.PRNGKey(2)
    ref = fit_from_spec(xb, SPEC, key)
    res1, _ = fit_chunked(ArraySource(xb), _chunked(SPEC, chunk_points=4096),
                          key)
    np.testing.assert_array_equal(
        np.asarray(ref.centers, np.float32), np.asarray(res1.centers,
                                                        np.float32))
    res4, _ = fit_chunked(ArraySource(xb), _chunked(SPEC, chunk_points=500),
                          key)
    assert bool(jnp.all(jnp.isfinite(res4.centers)))
    ref32 = float(fit_from_spec(x, SPEC, key).sse)
    assert abs(relative_error(float(res4.sse), ref32)) < 0.25


@pytest.mark.parametrize("weighted", [False, True])
def test_chunked_weighted_merge(dataset, weighted):
    x, _ = dataset
    spec = _chunked(SPEC.replace(merge=MergeSpec(k=5, iters=15,
                                                 weighted=weighted)),
                    chunk_points=500)
    res, _ = fit_chunked(ArraySource(x), spec, jax.random.PRNGKey(0))
    ref = float(fit_from_spec(x, SPEC, jax.random.PRNGKey(0)).sse)
    assert abs(relative_error(float(res.sse), ref)) < 0.15


def test_chunked_with_levels(dataset):
    """spec.levels reduce the ACCUMULATED multi-chunk pool before the merge,
    exactly as they reduce the resident pipeline's pool."""
    x, _ = dataset
    lv = (LevelSpec(n_sub=4, compression=2, iters=6),)
    spec = _chunked(SPEC, chunk_points=500).replace(levels=lv)
    res, stats = fit_chunked(ArraySource(x), spec, jax.random.PRNGKey(0))
    # accounting: 4 chunks x (8 * (ceil(500/8) // 5)) = 4 x 96 = 384 pool
    # entries, then one level: cap = ceil(384/4) = 96, k_local = 48 -> 192
    assert stats.pool_size == spec.chunked_pool_schedule(2000)[-1] == 192
    ref = float(fit_from_spec(x, SPEC.replace(levels=lv),
                              jax.random.PRNGKey(0)).sse)
    assert abs(relative_error(float(res.sse), ref)) < 0.15
    # mass is conserved through chunks + equal-scheme levels
    np.testing.assert_allclose(float(res.local_weights.sum()), 2000.0,
                               rtol=1e-5)


def test_partial_fit_after_fit_resets(dataset):
    """fit() is a fresh estimator state in every mode: a later partial_fit
    must start a NEW stream, not extend one left over from fit."""
    x, _ = dataset
    key = jax.random.PRNGKey(4)
    est = SampledKMeans(SPEC).fit(x, key=key)          # single-mode fit
    est.partial_fit(x[:500], key=key)
    fresh = SampledKMeans(SPEC)
    fresh.partial_fit(x[:500], key=key)
    assert int(est.stream_state.step) == 1
    np.testing.assert_array_equal(np.asarray(est.centers_),
                                  np.asarray(fresh.centers_))


# ---------------------------------------------------------------------------
# IterSource: ragged and odd shapes, end to end
# ---------------------------------------------------------------------------

def test_iter_source_rebatches_ragged_pieces(dataset):
    """Arbitrary incoming piece sizes are re-batched to fixed chunks with
    one ragged tail; no points are lost or duplicated."""
    x, _ = dataset
    pieces = np.split(np.asarray(x), [300, 1100, 1150, 1900])  # ragged
    src = IterSource(lambda: iter(pieces), dim=3, n_points=2000)
    sizes = [c.shape[0] for c in src.chunks(600)]
    assert sizes == [600, 600, 600, 200]
    res, stats = fit_chunked(src, _chunked(SPEC, chunk_points=600),
                             jax.random.PRNGKey(0))
    assert stats.n_chunks == 4 and stats.n_points == 2000
    assert stats.max_chunk_points == 600
    # every point lands in exactly one partition of one chunk
    np.testing.assert_allclose(
        float(res.local_weights.sum()) + int(res.n_dropped), 2000.0,
        rtol=1e-5)
    assert bool(jnp.all(jnp.isfinite(res.centers)))


def test_tail_chunk_smaller_than_n_sub(dataset):
    """A tail chunk with fewer points than partition count clamps its
    partition count to the chunk size — no empty mandatory partitions, no
    NaNs, mass conserved."""
    x, _ = dataset
    src = IterSource(lambda: [np.asarray(x[:1005])], dim=3, n_points=1005)
    # 1000-point chunk + 5-point tail, n_sub=8 > 5
    res, stats = fit_chunked(src, _chunked(SPEC, chunk_points=1000),
                             jax.random.PRNGKey(1))
    assert stats.n_chunks == 2
    assert bool(jnp.all(jnp.isfinite(res.centers)))
    assert bool(jnp.all(jnp.isfinite(res.local_centers)))
    np.testing.assert_allclose(float(res.local_weights.sum()), 1005.0,
                               rtol=1e-5)


def test_partition_smaller_than_k_local():
    """compression=1 makes k_local = capacity; the padded last partition
    then has fewer valid points than k_local — the weighted init fallback
    must keep everything finite and the mass exact."""
    pts, _, _ = blobs(10, n_clusters=2, dim=2, seed=0)
    spec = ClusterSpec(partition=PartitionSpec(n_sub=4),
                       local=LocalSpec(compression=1, iters=4),
                       merge=MergeSpec(k=2, iters=5),
                       chunk=ChunkSpec(chunk_points=10),
                       execution=ExecutionSpec(mode="chunked"))
    res, stats = fit_chunked(IterSource(lambda: [pts], dim=2), spec,
                             jax.random.PRNGKey(0))
    assert stats.n_chunks == 1
    assert bool(jnp.all(jnp.isfinite(res.centers)))
    np.testing.assert_allclose(float(res.local_weights.sum()), 10.0,
                               rtol=1e-5)


def test_iter_source_rejects_bare_generator(dataset):
    x, _ = dataset

    def gen():
        yield np.asarray(x[:100])

    with pytest.raises(ValueError, match="factory"):
        IterSource(gen())            # single-use generator object
    IterSource(gen)                  # the factory spelling is fine


def test_empty_source_raises():
    src = IterSource(lambda: iter(()), dim=2)
    with pytest.raises(ValueError, match="no chunks"):
        fit_chunked(src, _chunked(SPEC, chunk_points=100),
                    jax.random.PRNGKey(0))


def test_iter_source_dim_mismatch_raises():
    pieces = [np.zeros((4, 3), np.float32), np.zeros((4, 2), np.float32)]
    src = IterSource(lambda: iter(pieces))
    with pytest.raises(ValueError, match="dim"):
        list(src.chunks(8))


# ---------------------------------------------------------------------------
# Sources + prefetcher
# ---------------------------------------------------------------------------

def test_synthetic_source_deterministic_across_passes():
    src = SyntheticSource(5000, dim=4, n_clusters=6, seed=3)
    a = np.concatenate(list(src.chunks(1024)))
    b = np.concatenate(list(src.chunks(1024)))
    assert a.shape == (5000, 4)
    np.testing.assert_array_equal(a, b)
    # different chunking = same points (chunk i is seeded by index, so only
    # equal chunk_points traversals line up; the full set is what matters
    # for the scale/sse passes, which reuse one chunk_points)
    sizes = [c.shape[0] for c in src.chunks(2048)]
    assert sizes == [2048, 2048, 904]


def test_prefetch_preserves_order_and_handles_short_streams():
    chunks = [np.full((2, 2), i, np.float32) for i in range(5)]
    out = list(prefetch_to_device(chunks, depth=3))
    assert [int(c[0, 0]) for c in out] == [0, 1, 2, 3, 4]
    assert list(prefetch_to_device([], depth=2)) == []
    with pytest.raises(ValueError, match="depth"):
        list(prefetch_to_device(chunks, depth=0))


def test_scale_pass_matches_feature_scale(dataset):
    x, _ = dataset
    lo_ref, span_ref = feature_scale(x)[1]
    lo, span = scale_pass(ArraySource(x), 300)
    np.testing.assert_array_equal(np.asarray(lo), np.asarray(lo_ref))
    np.testing.assert_array_equal(np.asarray(span), np.asarray(span_ref))


def test_as_source_wraps_arrays(dataset):
    x, _ = dataset
    src = as_source(x)
    assert isinstance(src, ArraySource) and src.shape == (2000, 3)
    assert as_source(src) is src
    with pytest.raises(TypeError, match="IterSource"):
        as_source(iter([x]))


# ---------------------------------------------------------------------------
# Planner / facade dispatch
# ---------------------------------------------------------------------------

def test_auto_mode_resolution_with_sources(dataset):
    x, _ = dataset
    it = IterSource(lambda: [np.asarray(x)], dim=3, n_points=2000)
    assert plan(SPEC, it.shape, source=it).mode == "chunked"
    assert plan(SPEC, source=ArraySource(x)).mode == "single"
    assert plan(_chunked(SPEC, chunk_points=500), (2000, 3)).mode == "chunked"


def test_plan_rejects_starved_chunk_schedule():
    spec = _chunked(SPEC, chunk_points=500).replace(
        levels=(LevelSpec(n_sub=1, compression=100000),))
    with pytest.raises(ValueError, match="chunked schedule"):
        plan(spec, (2000, 3))


def test_execute_rejects_nonresident_source_in_single_mode(dataset):
    x, _ = dataset
    src = IterSource(lambda: [np.asarray(x)], dim=3, n_points=2000)
    pl = plan(SPEC.replace(mode="single"), (2000, 3))
    with pytest.raises(ValueError, match="resident array"):
        execute(pl, src)


def test_execute_chunked_accepts_plain_array(dataset):
    """execute auto-wraps arrays, and the single-chunk run stays pinned to
    the resident pipeline."""
    x, _ = dataset
    key = jax.random.PRNGKey(6)
    res = execute(plan(_chunked(SPEC, chunk_points=4096), (2000, 3)), x, key)
    ref = fit_from_spec(x, SPEC, key)
    np.testing.assert_array_equal(np.asarray(ref.centers),
                                  np.asarray(res.centers))


def test_fit_predict_over_source(dataset):
    """fit_predict(DataSource) assigns chunk-by-chunk: only the (n,) label
    vector materializes, and labels agree with the resident predict."""
    x, _ = dataset
    src = IterSource(lambda: [np.asarray(x)], dim=3, n_points=2000)
    est = SampledKMeans(_chunked(SPEC, chunk_points=500))
    labels = est.fit_predict(src, key=jax.random.PRNGKey(0))
    assert labels.shape == (2000,)
    np.testing.assert_array_equal(np.asarray(labels),
                                  np.asarray(est.predict(x)))


def test_stream_mode_fit_over_source_reports_sse(dataset):
    """mode="stream" + DataSource: fit folds the source chunk-wise through
    partial_fit AND still reports quality (one chunked SSE pass) — unlike a
    bare partial_fit, which leaves sse_ stale on purpose."""
    x, _ = dataset
    src = IterSource(lambda: [np.asarray(x)], dim=3, n_points=2000)
    spec = _chunked(SPEC, chunk_points=500).replace(mode="stream")
    est = SampledKMeans(spec).fit(src, key=jax.random.PRNGKey(0))
    assert int(est.stream_state.step) == 4
    assert est.sse_ is not None and bool(jnp.isfinite(est.sse_))


def test_pool_sse_policy_skips_exact_pass(dataset):
    x, _ = dataset
    res, stats = fit_chunked(
        ArraySource(x), _chunked(SPEC, chunk_points=500, sse="pool"),
        jax.random.PRNGKey(0))
    assert stats.passes == 2          # scale + fold, no exact-SSE pass
    assert float(res.sse) > 0 and bool(jnp.isfinite(res.sse))


# ---------------------------------------------------------------------------
# ChunkSpec validation + serialization
# ---------------------------------------------------------------------------

def test_chunk_spec_validation():
    with pytest.raises(ValueError, match="sse policy"):
        ChunkSpec(sse="estimate")
    with pytest.raises(ValueError, match="chunk_points"):
        ChunkSpec(chunk_points=0)
    with pytest.raises(ValueError, match="prefetch"):
        ChunkSpec(prefetch=0)
    with pytest.raises(ValueError, match="unknown execution mode"):
        ExecutionSpec(mode="out_of_core")


def test_spec_roundtrip_with_chunk_section():
    spec = _chunked(SPEC, chunk_points=1234, prefetch=3, sse="pool")
    restored = ClusterSpec.from_dict(json.loads(json.dumps(spec.to_dict())))
    assert restored == spec
    assert restored.chunk.chunk_points == 1234
    # replace() reaches the chunk sub-spec by field name
    assert SPEC.replace(chunk_points=777).chunk.chunk_points == 777
    with pytest.raises(ValueError, match="unknown chunk keys"):
        ClusterSpec.from_dict({"merge": {"k": 3},
                               "chunk": {"chunk_rows": 10}})


# ---------------------------------------------------------------------------
# Blocked predict-side metrics (satellite: no (N, K) materialization)
# ---------------------------------------------------------------------------

def _expansion_scale(x, centers):
    """Per-row |x|² + max|c|² — what BLOCKED_RTOL is relative to."""
    x = np.asarray(x, np.float64)
    c = np.asarray(centers, np.float64)
    return np.sum(x * x, -1) + np.max(np.sum(c * c, -1))


def test_sse_blocked_identical_to_dense(dataset):
    """Blocked and dense evaluation agree within the one stated tolerance
    (core/metrics.py BLOCKED_RTOL), whatever the block size."""
    x, _ = dataset
    centers = x[:7]
    scale = _expansion_scale(x, centers)
    dense = float(sse(x, centers))
    for block in (256, 999, 2000, 4096):
        got = float(sse(x, centers, block=block))
        assert abs(got - dense) <= BLOCKED_RTOL * scale.sum()
    w = jnp.asarray(np.random.default_rng(0).uniform(0, 2, 2000),
                    jnp.float32)
    got = float(sse(x, centers, weights=w, block=300))
    assert (abs(got - float(sse(x, centers, weights=w)))
            <= BLOCKED_RTOL * float(np.sum(np.asarray(w) * scale)))
    diff = np.abs(np.asarray(min_sqdist(x, centers), np.float64)
                  - np.asarray(min_sqdist(x, centers, block=300)))
    assert np.all(diff <= BLOCKED_RTOL * scale)


def test_transform_score_blocked_identical(dataset):
    x, _ = dataset
    est = SampledKMeans(SPEC).fit(x, key=jax.random.PRNGKey(0))
    scale = _expansion_scale(x, est.centers_)
    diff = np.abs(np.asarray(est.transform(x, block=10 ** 9), np.float64)
                  - np.asarray(est.transform(x, block=300)))
    assert np.all(diff <= BLOCKED_RTOL * scale[:, None])
    assert (abs(float(est.score(x, block=10 ** 9))
                - float(est.score(x, block=300)))
            <= BLOCKED_RTOL * scale.sum())


# ---------------------------------------------------------------------------
# Acceptance: the dataset never sits in one place
# ---------------------------------------------------------------------------

def test_fit_iter_source_4x_larger_than_resident():
    """SampledKMeans.fit(IterSource(...)) clusters a dataset >= 4x larger
    than any single resident array (chunk accounting), with quality on par
    with the flat batch fit."""
    n, chunk = 24_000, 3_000
    pts, _, _ = blobs(n, n_clusters=8, dim=3, seed=9)

    def pieces():
        for start in range(0, n, 1_700):      # ragged producer
            yield pts[start:start + 1_700]

    src = IterSource(pieces, dim=3, n_points=n)
    spec = ClusterSpec(partition=PartitionSpec(n_sub=8),
                       local=LocalSpec(compression=5, iters=8),
                       merge=MergeSpec(k=8, iters=15),
                       chunk=ChunkSpec(chunk_points=chunk, prefetch=2),
                       execution=ExecutionSpec(mode="chunked"))
    est = SampledKMeans(spec).fit(src, key=jax.random.PRNGKey(0))
    st = est.chunk_stats_
    assert st.n_points == n and st.n_chunks == 8
    # no resident array ever held more than one chunk; even counting the
    # prefetch buffer the live window is 4x smaller than the dataset
    assert st.n_points >= 4 * st.max_chunk_points
    assert st.n_points >= 4 * st.max_chunk_points * st.prefetch
    ref = float(fit_from_spec(jnp.asarray(pts),
                              spec.replace(mode="single"),
                              jax.random.PRNGKey(0)).sse)
    assert abs(relative_error(float(est.sse_), ref)) < 0.15
