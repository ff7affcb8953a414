"""The sharded out-of-core fit (``mode="chunked_dist"``) compiles its stages
once.

* A reduce level (:func:`reduce_pool`) is one jitted program, bit for bit
  its body run op by op; a second call of the same shapes compiles
  nothing.
* The distributed merge's program is built once per mesh, merge spec and
  backend.
* A warm fit with a reduce level and ``merge_path="distributed"`` compiles
  nothing: on a 1-device mesh in this process, and on 4 host devices in a
  subprocess (the XLA flag must not leak into this process).
* Each chunk's transfer is the host span ``feed``, and the summary event
  reports the bytes fed to each device.
* The fit agrees with the benchmark's plain reference of this path
  (``benchmarks/chip/reference_dist.py``) on 1 and 4 host devices.
"""
import contextlib
import functools
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.api import SampledKMeans
from repro.core import ClusterSpec, LevelSpec
from repro.core.backend import get_backend
from repro.core.distributed import _merge_program
from repro.core.pipeline import reduce_pool
from repro.data import ArraySource
from repro.telemetry import RecordingLogger

REPO = pathlib.Path(__file__).resolve().parents[1]
BENCH = REPO / "benchmarks" / "chip"

# Agreement with the reference.  The pool's mass is exact (every point is
# counted once).  Pool weights: share of the points counted to other pool
# rows, |w - w_ref|_1 / 2n; the reference resolves nearest centers as the
# first index of the minimum at HIGHEST, and a backend whose distance
# arithmetic rounds otherwise may send a near-tie the other way, so a few
# points in a thousand may move (on the CPU's jnp backend none do).  SSE:
# the same pool and centers summed in another order and expansion.
POOL_GAP_TOL = 1e-3
SSE_RTOL = 1e-4

# a BIGANN-like schedule at a test size: per device 4 chunks of 1,024 rows,
# 4 partitions at c=8 (32 local centers), one level (4 partitions, c=2),
# the merge at k=16 with the pool left sharded
SPEC = {"merge": {"k": 16, "iters": 5, "weighted": True, "restarts": 1,
                  "init": "kmeans++"},
        "partition": {"scheme": "equal", "n_sub": 4, "capacity_factor": 2.0},
        "local": {"compression": 8, "iters": 3, "init": "kmeans++"},
        "execution": {"backend": "auto", "mode": "chunked_dist",
                      "mesh_axis": "data", "donate": False,
                      "merge_path": "distributed", "telemetry": "off"},
        "scale": True,
        "levels": [{"n_sub": 4, "compression": 2, "iters": 3,
                    "init": "kmeans++", "scheme": "equal",
                    "capacity_factor": 2.0}],
        "chunk": {"chunk_points": 1024, "prefetch": 2, "sse": "pool"}}

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@contextlib.contextmanager
def _compiles():
    """The backend compiles that run inside the block, as a list."""
    seen = []

    def on_duration(event, secs, **_):
        if event == COMPILE_EVENT:
            seen.append(secs)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)


def _points(n, d=16, seed=0):
    """Rows of a 64-component mixture mapped onto [0, 255] and rounded
    (uint8-like values, as BIGANN's descriptors are)."""
    rng = np.random.default_rng(seed)
    means = rng.uniform(0, 1, (64, d))
    x = means[rng.integers(0, 64, n)] + 0.2 * rng.normal(size=(n, d))
    return np.round((x - x.min()) / (x.max() - x.min()) * 255).astype(
        np.float32)


@pytest.mark.parametrize("level", [
    LevelSpec(n_sub=8, compression=2, iters=3),
    LevelSpec(n_sub=4, compression=5, iters=4, scheme="unequal")])
def test_reduce_level_compiles_once(level):
    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.normal(size=(640, 16)).astype(np.float32))
    w = jnp.asarray(rng.integers(0, 5, 640).astype(np.float32))
    be = get_backend(None)
    jax.block_until_ready(reduce_pool(pool, w, level, jax.random.PRNGKey(3),
                                      backend=be))
    pool2, key2 = jax.block_until_ready((2.0 * pool, jax.random.PRNGKey(4)))
    with _compiles() as seen:
        out = reduce_pool(pool2, w, level, key2, backend=be)
        jax.block_until_ready(out)
    assert seen == []
    # mass conservation: the equal scheme keeps every entry, the unequal
    # one reports what its capacity dropped
    np.testing.assert_allclose(float(jnp.sum(out[1]) + out[2]),
                               float(jnp.sum(w)), rtol=1e-6)


@pytest.mark.parametrize("level", [
    LevelSpec(n_sub=8, compression=2, iters=3),
    LevelSpec(n_sub=4, compression=5, iters=4, scheme="unequal")])
def test_jitted_level_is_eager_bit_for_bit(level):
    """The compiled level gives what its body gives run op by op."""
    rng = np.random.default_rng(1)
    pool = jnp.asarray(rng.normal(size=(640, 16)).astype(np.float32))
    w = jnp.asarray(rng.integers(0, 5, 640).astype(np.float32))
    be = get_backend(None)
    key = jax.random.PRNGKey(7)
    jitted = reduce_pool(pool, w, level, key, backend=be)
    eager = reduce_pool.__wrapped__(pool, w, level, key, backend=be)
    for a, b in zip(jitted, eager):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_merge_program_built_once_per_mesh_and_spec():
    spec = ClusterSpec.from_dict(SPEC)
    mesh = compat.make_mesh((1,), ("data",))
    be = get_backend(None)
    build = functools.partial(_merge_program, mesh, "data", spec.merge.k,
                              spec.merge.effective_stop, be)
    assert build() is build()
    assert _merge_program(mesh, "data", spec.merge.k + 1,
                          spec.merge.effective_stop, be) is not build()


def test_warm_fit_compiles_nothing_one_device():
    x = _points(4096)
    spec = ClusterSpec.from_dict(SPEC)
    mesh = compat.make_mesh((1,), ("data",))
    SampledKMeans(spec, mesh=mesh).fit(ArraySource(x),
                                       key=jax.random.PRNGKey(0))
    with _compiles() as seen:
        est = SampledKMeans(spec, mesh=mesh).fit(ArraySource(x),
                                                 key=jax.random.PRNGKey(1))
        jax.block_until_ready(est.result_)
    assert seen == []
    assert float(np.sum(np.asarray(est.result_.local_weights))) == len(x)


def test_feed_spans_and_bytes(monkeypatch):
    """Every chunk transfer is a ``feed`` span; the summary event counts
    the bytes each device was fed over the fit's two data passes."""
    x = _points(4096)
    rec = RecordingLogger()
    spans = []
    real = jax.profiler.TraceAnnotation

    class Recorded(real):
        def __init__(self, name, **kw):
            spans.append(name)
            super().__init__(name, **kw)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorded)
    SampledKMeans(ClusterSpec.from_dict(SPEC),
                  mesh=compat.make_mesh((1,), ("data",)),
                  logger=rec).fit(ArraySource(x), key=jax.random.PRNGKey(0))
    # scale pass + fold pass, 4 chunks each
    assert spans.count("feed") == 8
    summary = [e for e in rec.events if e["name"] == "fit_chunked_dist"]
    assert summary[0]["per_device_bytes"] == [2 * x.nbytes]


def reference_gaps(x, result, n_dev, key):
    """``(mass gap, pool gap, relative SSE gap)`` of a fit's result against
    the reference replay of the same fit."""
    if str(BENCH) not in sys.path:
        sys.path.insert(0, str(BENCH))
    import reference_dist
    ref = reference_dist.sampled_kmeans_dist(x, SPEC, n_dev, key)
    w = np.asarray(result.local_weights, np.float64)
    return (abs(w.sum() - len(x)),
            np.abs(w - np.asarray(ref["pool_w"])).sum() / (2 * len(x)),
            abs(float(result.sse) / float(ref["sse"]) - 1.0))


def test_matches_reference_one_device():
    x = _points(4096)
    key = jax.random.PRNGKey(5)
    est = SampledKMeans(ClusterSpec.from_dict(SPEC),
                        mesh=compat.make_mesh((1,), ("data",))).fit(
        ArraySource(x), key=key)
    mass, pool_gap, sse_gap = reference_gaps(x, est.result_, 1, key)
    assert mass == 0
    assert pool_gap <= POOL_GAP_TOL
    assert sse_gap <= SSE_RTOL


_FOUR_DEVICES = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
sys.path.insert(0, "tests")
import test_chunked_dist_compile as t
from repro import compat
from repro.api import SampledKMeans
from repro.core import ClusterSpec
from repro.data import ArraySource
assert len(jax.devices()) == 4
x = t._points(4 * 4096)
spec = ClusterSpec.from_dict(t.SPEC)
mesh = compat.make_mesh((4,), ("data",))
SampledKMeans(spec, mesh=mesh).fit(ArraySource(x), key=jax.random.PRNGKey(0))
seen = []
jax.monitoring.register_event_duration_secs_listener(
    lambda event, secs, **_: seen.append(event) if event == t.COMPILE_EVENT
    else None)
key = jax.random.PRNGKey(1)
est = SampledKMeans(spec, mesh=mesh).fit(ArraySource(x), key=key)
jax.block_until_ready(est.result_)
assert seen == [], len(seen)
assert est.chunk_stats_.per_device_chunks == (4, 4, 4, 4)
assert float(est.result_.local_weights.sum()) == len(x)
mass, pool_gap, sse_gap = t.reference_gaps(x, est.result_, 4, key)
assert mass == 0 and pool_gap <= t.POOL_GAP_TOL, (mass, pool_gap)
assert sse_gap <= t.SSE_RTOL, sse_gap
print("FOUR_DEVICES_OK")
"""


def test_four_devices_compile_nothing():
    r = subprocess.run([sys.executable, "-c", _FOUR_DEVICES],
                       capture_output=True, text=True, timeout=300, cwd=REPO,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "JAX_PLATFORMS": "cpu"})
    assert "FOUR_DEVICES_OK" in r.stdout, r.stdout + r.stderr
