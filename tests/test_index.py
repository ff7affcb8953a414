"""The IVF/PQ index subsystem (``repro.index``): ADC kernel parity, spec
round-trips + fail-fast planning, PQ codebook/encode properties, build
identity between in-memory and out-of-core (and sharded) paths, search
recall against the brute-force baseline, empty-cell edge cases, and the
query-path telemetry."""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.backend import get_backend
from repro.core.spec import ChunkSpec, ClusterSpec
from repro.data.source import ArraySource, IterSource
from repro.index import (IndexSpec, IVFIndex, PQSpec, build_index, decode,
                         exact_search, plan_index, recall_at_k, search,
                         train_codebooks)
from repro.index.pq import encode_residuals, split_subspaces
from repro.kernels.ref import adc_scan_ref
from repro.kernels.scan import (adc_scan, adc_scan_jnp, adc_scan_pallas,
                                resolve_scan_backend)
from repro.telemetry import RecordingLogger


# ---------------------------------------------------------------------------
# ADC scan kernel: parity vs the jnp reference across ragged shapes / bf16
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,m,c,l", [
    (1, 1, 16, 7),          # minimal + ragged L
    (3, 8, 256, 100),       # 8-bit codebooks, ragged L
    (4, 32, 16, 513),       # 4-bit codebooks, L just past a block
    (2, 4, 256, 256),       # block-aligned L
    (1, 16, 16, 1),         # single candidate
])
def test_adc_scan_parity(rng, b, m, c, l):
    luts = jnp.asarray(rng.standard_normal((b, m, c)).astype(np.float32))
    codes = jnp.asarray(rng.integers(0, c, (b, l, m)).astype(np.uint8))
    ref = adc_scan_ref(luts, codes)
    np.testing.assert_allclose(adc_scan_jnp(luts, codes), ref, atol=1e-4)
    np.testing.assert_allclose(
        adc_scan_pallas(luts, codes, interpret=True), ref, atol=1e-4)


def test_adc_scan_bf16_luts(rng):
    """bf16 LUTs accumulate in fp32 — kernel and jnp backend agree
    exactly."""
    luts = jnp.asarray(rng.standard_normal((2, 8, 256)).astype(np.float32)
                       ).astype(jnp.bfloat16)
    codes = jnp.asarray(rng.integers(0, 256, (2, 333, 8)).astype(np.uint8))
    np.testing.assert_array_equal(
        np.asarray(adc_scan_pallas(luts, codes, interpret=True)),
        np.asarray(adc_scan_jnp(luts, codes)))


def test_adc_scan_shape_mismatch_raises(rng):
    luts = jnp.zeros((2, 8, 16))
    codes = jnp.zeros((2, 10, 4), jnp.uint8)
    with pytest.raises(ValueError, match="do not match"):
        adc_scan_pallas(luts, codes)


def test_resolve_scan_backend(monkeypatch):
    assert resolve_scan_backend("jnp") == "jnp"
    assert resolve_scan_backend("pallas") == "pallas"
    monkeypatch.setenv("REPRO_SCAN_BACKEND", "pallas")
    assert resolve_scan_backend(None) == "pallas"
    monkeypatch.delenv("REPRO_SCAN_BACKEND")
    with pytest.raises(ValueError, match="unknown scan backend"):
        resolve_scan_backend("cuda")


def test_adc_scan_dispatcher_agrees(rng):
    luts = jnp.asarray(rng.standard_normal((2, 4, 16)).astype(np.float32))
    codes = jnp.asarray(rng.integers(0, 16, (2, 50, 4)).astype(np.uint8))
    np.testing.assert_allclose(adc_scan(luts, codes, backend="pallas"),
                               adc_scan(luts, codes, backend="jnp"),
                               atol=1e-5)


# ---------------------------------------------------------------------------
# Spec: construction, serialization, fail-fast planning
# ---------------------------------------------------------------------------

def test_index_spec_roundtrip():
    spec = IndexSpec.make(nlist=64, n_subspaces=8, bits=4, nprobe=4,
                          train_points=2048, n_sub=4)
    back = IndexSpec.from_dict(spec.to_dict())
    assert back == spec
    assert back.stable_hash() == spec.stable_hash()
    assert spec.nlist == 64 and spec.pq.n_codes == 16


def test_index_spec_default_merge_init_is_kmeans_parallel():
    """The coarse quantizer's documented default seeding is kmeans||."""
    spec = IndexSpec.make(nlist=32)
    assert spec.coarse.merge.init == "kmeans||"
    # the local stage keeps the plain init; explicit override wins
    assert spec.coarse.local.init == "kmeans++"
    assert IndexSpec.make(nlist=32, merge_init="random"
                          ).coarse.merge.init == "random"


def test_index_spec_hash_ignores_execution_keeps_nprobe():
    spec = IndexSpec.make(nlist=32)
    moved = spec.replace(mode="chunked")
    assert moved.coarse.execution.mode == "chunked"
    assert moved.stable_hash() == spec.stable_hash()
    assert spec.replace(nprobe=17).stable_hash() != spec.stable_hash()


def test_index_spec_replace_reaches_down():
    spec = IndexSpec.make(nlist=32)
    assert spec.replace(bits=4).pq.bits == 4
    assert spec.replace(chunk_points=1234
                        ).coarse.chunk.chunk_points == 1234


def test_index_spec_rejects_unknown_keys():
    spec = IndexSpec.make(nlist=8)
    d = spec.to_dict()
    d["typo"] = 1
    with pytest.raises(ValueError, match="typo"):
        IndexSpec.from_dict(d)


def test_pq_spec_bits_validated():
    with pytest.raises(ValueError, match="bits"):
        PQSpec(bits=5)
    with pytest.raises(ValueError, match="bits"):
        IndexSpec.make(nlist=8, bits=16)


def test_plan_index_fail_fast():
    spec = IndexSpec.make(nlist=32, n_subspaces=8, train_points=2048)
    # n_subspaces must divide d
    with pytest.raises(ValueError, match="divide"):
        plan_index(spec, (10_000, 12))
    # nprobe <= nlist
    with pytest.raises(ValueError, match="nprobe"):
        plan_index(spec.replace(nprobe=33), (10_000, 16))
    # train_points must cover the codebooks and the coarse k
    with pytest.raises(ValueError, match="codebooks"):
        plan_index(IndexSpec.make(nlist=8, bits=8, train_points=100))
    with pytest.raises(ValueError, match="nlist"):
        plan_index(IndexSpec.make(nlist=512, bits=4, train_points=256))
    # a valid plan resolves the coarse quantizer's own plan
    ip = plan_index(spec, (10_000, 16))
    assert ip.nlist == 32 and ip.coarse.mode == "single"
    assert ip.dim == 16 and ip.n_points == 10_000


def test_plan_index_reads_source_dim():
    spec = IndexSpec.make(nlist=8, n_subspaces=8, bits=4, train_points=256)
    src = ArraySource(np.zeros((500, 12), np.float32))
    with pytest.raises(ValueError, match="divide"):
        plan_index(spec, source=src)


# ---------------------------------------------------------------------------
# PQ: codebooks, encode/decode
# ---------------------------------------------------------------------------

def test_split_subspaces_shape_and_content(rng):
    x = jnp.asarray(rng.standard_normal((10, 8)).astype(np.float32))
    sub = split_subspaces(x, 4)
    assert sub.shape == (4, 10, 2)
    np.testing.assert_array_equal(np.asarray(sub[1, 3]),
                                  np.asarray(x[3, 2:4]))
    with pytest.raises(ValueError, match="divide"):
        split_subspaces(x, 3)


def test_pq_roundtrip_error_small(rng):
    """Residual PQ with 1-dim subspaces and 8-bit codebooks reconstructs
    clustered data to far below the point spread."""
    centers = rng.uniform(0, 10, (4, 8)).astype(np.float32)
    x = jnp.asarray((centers[rng.integers(0, 4, 2000)]
                     + rng.normal(0, 0.3, (2000, 8))).astype(np.float32))
    coarse = jnp.asarray(centers)
    cells, _ = get_backend("jnp").assign_points(x, coarse)
    resid = x - coarse[cells]
    pq = PQSpec(n_subspaces=8, bits=8, iters=8)
    cb = train_codebooks(resid, pq, jax.random.PRNGKey(0))
    assert cb.shape == (8, 256, 1)
    codes = encode_residuals(resid, cb, block=500)
    assert codes.shape == (2000, 8) and codes.dtype == jnp.uint8
    recon = decode(cells, codes, coarse, cb)
    err = float(jnp.mean(jnp.sum((recon - x) ** 2, -1)))
    spread = float(jnp.mean(jnp.sum(resid ** 2, -1)))
    assert err < 0.05 * spread, (err, spread)


def test_encode_residuals_blocked_matches_dense(rng):
    resid = jnp.asarray(rng.standard_normal((1003, 8)).astype(np.float32))
    cb = jnp.asarray(rng.standard_normal((4, 16, 2)).astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(encode_residuals(resid, cb, block=None)),
        np.asarray(encode_residuals(resid, cb, block=100)))


# ---------------------------------------------------------------------------
# Build: in-memory vs out-of-core vs sharded — identical indexes
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(3)
    centers = rng.uniform(0, 10, (16, 8)).astype(np.float32)
    ids = rng.integers(0, 16, 6000)
    x = (centers[ids] + rng.normal(0, 0.35, (6000, 8))).astype(np.float32)
    q = (centers[rng.integers(0, 16, 48)]
         + rng.normal(0, 0.35, (48, 8))).astype(np.float32)
    return x, q


INDEX_SPEC = IndexSpec.make(nlist=16, n_subspaces=8, bits=8, nprobe=4,
                            train_points=1500, n_sub=4, chunk_points=1024)


@pytest.fixture(scope="module")
def built(corpus):
    x, _ = corpus
    return build_index(x, INDEX_SPEC, jax.random.PRNGKey(5))


def _same_index(a: IVFIndex, b: IVFIndex):
    np.testing.assert_array_equal(np.asarray(a.coarse_centers),
                                  np.asarray(b.coarse_centers))
    np.testing.assert_array_equal(np.asarray(a.codebooks),
                                  np.asarray(b.codebooks))
    np.testing.assert_array_equal(np.asarray(a.counts), np.asarray(b.counts))
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))
    np.testing.assert_array_equal(np.asarray(a.codes), np.asarray(b.codes))


def test_build_out_of_core_identical(corpus, built):
    """An IterSource streamed in chunks far below the data size builds the
    exact index the in-memory build produces — and the stats prove the
    data never sat resident."""
    x, _ = corpus
    index, stats_mem = built
    src = IterSource(lambda: (x[i:i + 997] for i in range(0, len(x), 997)),
                     dim=8, n_points=len(x))
    ooc, stats = build_index(src, INDEX_SPEC, jax.random.PRNGKey(5))
    _same_index(index, ooc)
    assert stats.n_points == len(x)
    assert stats.n_chunks > 1
    assert stats.max_chunk_points <= 1024
    assert stats.train_rows == 1500
    # the resident ceiling: training sample + prefetch window, well below n
    assert stats.max_resident_rows < len(x) / 2
    assert stats.passes == 2 and stats.n_shards == 1
    # the in-memory build is a degenerate 6-chunk stream of the same rows
    assert stats_mem.n_points == len(x)


def test_build_sharded_identical(corpus, built):
    """A 2-shard mesh build (contiguous ArraySource shards, shard-major
    ids) reproduces the unsharded index exactly."""
    x, _ = corpus
    index, _ = built
    devs = np.array(jax.devices() * 2)      # fake 2-entry 1-D mesh
    mesh = jax.sharding.Mesh(devs, ("data",))
    sharded, stats = build_index(ArraySource(x), INDEX_SPEC,
                                 jax.random.PRNGKey(5), mesh=mesh)
    _same_index(index, sharded)
    assert stats.n_shards == 2
    assert stats.n_points == len(x)


def test_build_empty_source_raises():
    src = IterSource(lambda: iter([]), dim=8)
    with pytest.raises(ValueError, match="no rows"):
        build_index(src, INDEX_SPEC)


# ---------------------------------------------------------------------------
# Search: recall, edge cases, telemetry
# ---------------------------------------------------------------------------

def test_search_beats_recall_floor(corpus, built):
    x, q = corpus
    index, _ = built
    _, true_ids = exact_search(x, q, k=10)
    _, ids = index.search(q, k=10, nprobe=4)
    assert recall_at_k(ids, true_ids) >= 0.9


def test_search_distances_sorted_and_consistent(corpus, built):
    x, q = corpus
    index, _ = built
    d, ids = index.search(q, k=10)
    d = np.asarray(d)
    assert (np.diff(d, axis=1) >= -1e-6).all()
    assert np.isfinite(d).all() and (np.asarray(ids) >= 0).all()
    # exhaustive probe (nprobe=nlist) can only improve the top-1 distance
    d_full, _ = index.search(q, k=10, nprobe=index.nlist)
    assert (np.asarray(d_full)[:, 0] <= d[:, 0] + 1e-6).all()


def test_search_query_blocks_identical(corpus, built):
    x, q = corpus
    index, _ = built
    d1, i1 = index.search(q, k=5, q_block=48)
    d2, i2 = index.search(q, k=5, q_block=7)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), atol=1e-5)


def test_search_validates_inputs(corpus, built):
    x, q = corpus
    index, _ = built
    with pytest.raises(ValueError, match="nprobe"):
        index.search(q, k=5, nprobe=index.nlist + 1)
    with pytest.raises(ValueError, match="queries"):
        index.search(q[:, :4], k=5)


def test_search_empty_cells_pad_with_minus_one():
    """An index holding fewer points than k: every real point surfaces,
    the rest of the top-k is inf/-1 padding — probing more cells than have
    members must not fabricate candidates."""
    rng = np.random.default_rng(0)
    x = rng.uniform(0, 10, (20, 8)).astype(np.float32)
    spec = IndexSpec.make(nlist=4, n_subspaces=4, bits=4, nprobe=4,
                          train_points=32, n_sub=2, compression=1,
                          restarts=1)
    index, _ = build_index(x, spec)
    assert index.n_points == 20
    d, ids = search(index, x[:3], k=25, nprobe=4)
    d, ids = np.asarray(d), np.asarray(ids)
    for row_d, row_i in zip(d, ids):
        real = row_i >= 0
        assert real.sum() == 20                     # every point found once
        assert sorted(row_i[real]) == list(range(20))
        assert np.isinf(row_d[~real]).all()
    # nprobe covers every cell, including any empty ones
    assert index.n_nonempty <= 4


def test_search_telemetry_events(corpus, built):
    x, q = corpus
    index, _ = built
    log = RecordingLogger()
    index.search(q[:8], k=5, logger=log)
    names = [e["name"] for e in log.events]
    assert "index_probe" in names and "index_scan" in names
    assert "index_search" in names
    rates = log.named("index_query_rate")
    assert rates and rates[-1]["step_units"] == 8
    assert rates[-1]["units"] == "queries"


def test_build_telemetry_events(corpus):
    x, _ = corpus
    log = RecordingLogger()
    build_index(x, INDEX_SPEC, logger=log)
    names = {e["name"] for e in log.events}
    assert {"index_build", "index_train_coarse", "index_train_pq",
            "index_encode", "index_built"} <= names
    built_ev = log.named("index_built")[-1]
    assert built_ev["n_points"] == len(x)


def test_exact_search_streams(corpus):
    """The brute-force baseline is chunking-invariant."""
    x, q = corpus
    d1, i1 = exact_search(x, q[:8], k=5)
    src = IterSource(lambda: (x[i:i + 611] for i in range(0, len(x), 611)),
                     dim=8)
    d2, i2 = exact_search(src, q[:8], k=5, chunk_points=577)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), rtol=1e-5)


def test_recall_at_k_counts_partial_overlap():
    true = np.array([[0, 1, 2, 3]])
    assert recall_at_k(np.array([[0, 1, 9, 8]]), true) == 0.5
    assert recall_at_k(np.array([[3, 2, 1, 0]]), true) == 1.0
    # padding in the truth is excluded from the denominator
    padded = np.array([[0, 1, -1, -1]])
    assert recall_at_k(np.array([[1, 0, 7, 7]]), padded) == 1.0


# ---------------------------------------------------------------------------
# 8 host devices (subprocess, slow): sharded encode at mesh scale
# ---------------------------------------------------------------------------

_SHARD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import numpy as np
from repro import compat
from repro.data.source import ArraySource
from repro.index import IndexSpec, build_index
assert len(jax.devices()) == 8
rng = np.random.default_rng(11)
centers = rng.uniform(0, 10, (16, 8)).astype(np.float32)
x = (centers[rng.integers(0, 16, 16000)]
     + rng.normal(0, 0.3, (16000, 8))).astype(np.float32)
spec = IndexSpec.make(nlist=16, n_subspaces=8, bits=8, nprobe=4,
                      train_points=2048, n_sub=4, chunk_points=1000)
mesh = compat.make_mesh((8,), ("data",))
ref, _ = build_index(x, spec)
sharded, st = build_index(ArraySource(x), spec, mesh=mesh)
assert st.n_shards == 8 and st.n_points == 16000
np.testing.assert_array_equal(np.asarray(ref.counts),
                              np.asarray(sharded.counts))
np.testing.assert_array_equal(np.asarray(ref.ids), np.asarray(sharded.ids))
np.testing.assert_array_equal(np.asarray(ref.codes),
                              np.asarray(sharded.codes))
print("INDEX_SHARD_OK", st.n_chunks)
"""


@pytest.mark.slow
def test_build_sharded_8dev():
    """8 host devices each encode their own contiguous shard; the
    assembled index is identical to the single-device build."""
    r = subprocess.run([sys.executable, "-c", _SHARD_SCRIPT],
                       capture_output=True, text=True, timeout=600,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"})
    assert "INDEX_SHARD_OK" in r.stdout, r.stdout + r.stderr
