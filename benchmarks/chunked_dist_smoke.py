"""Sharded out-of-core smoke for CI: ``mode="chunked_dist"`` under an
8-host-device mesh (``XLA_FLAGS=--xla_force_host_platform_device_count=8``,
set below before jax imports — same idiom as the dist_smoke subprocess
tests).

Runs the spec-file workload twice: once through the plain 1-device
``fit_chunked`` (the reference — optionally on a ``ref_fraction`` of the
points so the nightly 50M spec doesn't pay two full passes) and once
through ``fit_chunked_dist`` on a mesh over every host device.  Records
fold throughput, the fold-scaling ratio between the two, per-device
chunk/row accounting, and the bounded-accumulator peak pool rows.

``fold_scaling`` is *recorded, not asserted*: CI runners are often
single-core, where 8 host devices time-slice one CPU and the ratio
hovers near 1.  The trajectory store tracks it so real multi-core runs
show the scaling; the gate only checks the machine-normalized
throughput/SSE/RSS metrics it checks for every other bench.

  PYTHONPATH=src python -m benchmarks.chunked_dist_smoke
  PYTHONPATH=src python -m benchmarks.chunked_dist_smoke \\
      --spec benchmarks/specs/chunked_dist_50m.json        # nightly
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import json
import pathlib
import time

import jax
import jax.numpy as jnp

from repro import compat
from repro.core import ClusterSpec, fit_chunked, fit_chunked_dist
from repro.data import SyntheticSource

SPECS = pathlib.Path(__file__).resolve().parent / "specs"
ARTIFACTS = pathlib.Path(__file__).resolve().parent / "artifacts"


def _timed_fit(fit, warm):
    """Wall-clock one fit call (after an optional warm call that eats
    compile time); returns (result, stats, seconds)."""
    if warm:
        res, _ = fit()
        jax.block_until_ready(res.sse)
    t0 = time.perf_counter()
    res, stats = fit()
    jax.block_until_ready(res.sse)
    return res, stats, time.perf_counter() - t0


def main(argv=None) -> None:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--spec", default=str(SPECS / "chunked_dist_smoke.json"),
                    help="spec-file JSON (cluster_spec + workload)")
    args = ap.parse_args(argv)

    payload = json.loads(pathlib.Path(args.spec).read_text())
    spec = ClusterSpec.from_dict(payload["cluster_spec"])
    wl = payload["workload"]
    n, dim, seed = int(wl["n"]), int(wl["dim"]), int(wl.get("seed", 0))
    n_clusters = int(wl.get("n_clusters", 0)) or None
    frac = float(wl.get("ref_fraction", 1.0))
    key = jax.random.PRNGKey(seed)
    warm = n <= 1_000_000          # the 50M run amortizes compile instead

    mesh = compat.make_mesh((len(jax.devices()),),
                            (spec.execution.mesh_axis,))
    n_dev = len(jax.devices())

    n_ref = max(spec.chunk.chunk_points, int(n * frac))
    ref_src = SyntheticSource(n_ref, dim=dim, n_clusters=n_clusters,
                              seed=seed)
    ref_res, _, ref_wall = _timed_fit(
        lambda: fit_chunked(ref_src, spec, key), warm)
    pps_ref = n_ref / ref_wall

    src = SyntheticSource(n, dim=dim, n_clusters=n_clusters, seed=seed)
    res, stats, wall = _timed_fit(
        lambda: fit_chunked_dist(src, spec, mesh, key), warm)
    pps = n / wall

    assert stats.n_devices == n_dev, stats
    assert stats.n_points == n, stats
    balance = max(stats.per_device_chunks) - min(stats.per_device_chunks)
    assert balance <= 1, f"round-robin imbalance: {stats.per_device_chunks}"
    assert stats.pool_size >= spec.merge.k, stats
    rel = None
    if frac >= 1.0:                # same workload -> SSEs must agree
        rel = abs(float(res.sse) - float(ref_res.sse)) / float(ref_res.sse)
        assert rel < 0.25, f"chunked_dist vs fit_chunked SSE: {rel:.3f}"
        lo = jnp.asarray(src.centers.min(axis=0) - 1.0)
        hi = jnp.asarray(src.centers.max(axis=0) + 1.0)
        assert bool(jnp.all(res.centers >= lo - 1e-3)), "not unscaled"
        assert bool(jnp.all(res.centers <= hi + 1e-3)), "not unscaled"

    from repro.telemetry import calibrate, peak_rss_mb
    ARTIFACTS.mkdir(parents=True, exist_ok=True)
    name = payload.get("name", "chunked_dist_smoke")
    record = {
        "schema": 1,
        "bench": "spec_file",      # same trajectory shape as run.py specs
        "name": name,
        "spec_hash": spec.stable_hash(),
        "mode": "chunked_dist",
        "backend": spec.execution.backend,
        "calib_mflops": calibrate(),
        "workload": {"n": n, "dim": dim, "seed": seed,
                     "ref_fraction": frac},
        "n_devices": n_dev,
        "us_best": wall * 1e6,
        "points_per_sec": pps,
        "fold_scaling": pps / pps_ref,
        "ref_points_per_sec": pps_ref,
        "peak_rss_mb": peak_rss_mb(),
        "sse": float(res.sse),
        "per_device": {
            "points": [int(p) for p in stats.per_device_points],
            "chunks": [int(c) for c in stats.per_device_chunks],
            "peak_pool_rows": int(stats.peak_pool_rows),
        },
    }
    if rel is not None:
        record["rel_sse"] = rel
    (ARTIFACTS / f"BENCH_{name}.json").write_text(
        json.dumps(record, indent=1))
    print(f"CHUNKED_DIST_SMOKE_OK name={name} devices={n_dev} "
          f"chunks={stats.n_chunks} pool={stats.pool_size} "
          f"peak_pool_rows={stats.peak_pool_rows} "
          f"pps={pps:.0f} fold_scaling={pps / pps_ref:.2f}"
          + (f" rel_sse={rel:.4f}" if rel is not None else ""))


if __name__ == "__main__":
    main()
