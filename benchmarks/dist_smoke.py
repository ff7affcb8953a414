"""Interpret-mode distributed smoke for CI: 1-device mesh,
``merge_path='distributed'``, ``levels=2``.

Exercises the full shard_map path (global feature scale, per-device local
stage, the per-device hierarchical reduce level, the sharded-pool merge
with psum'd Lloyd statistics) and asserts the two parity properties the
distributed bugfixes pinned down: results come back in the *input* space,
and the SSE lands within tolerance of the single-device ``fit_from_spec``
on the same spec.

Writes ``benchmarks/artifacts/BENCH_dist_smoke.json`` so the shard_map path
shows up in the perf trajectory and CI gate alongside the spec-file benches.

  PYTHONPATH=src python -m benchmarks.dist_smoke
"""
import json
import pathlib
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compat
from repro.core import (ClusterSpec, ExecutionSpec, LevelSpec, LocalSpec,
                        MergeSpec, PartitionSpec, fit_from_spec,
                        make_distributed_sampled_kmeans)
from repro.data.synthetic import blobs


def main() -> None:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    spec = ClusterSpec(
        partition=PartitionSpec(scheme="equal", n_sub=8),
        local=LocalSpec(compression=5, iters=6),
        merge=MergeSpec(k=8, iters=10),
        execution=ExecutionSpec(merge_path="distributed"),
        levels=(LevelSpec(n_sub=4, compression=3, iters=5),),  # levels=2
    )
    pts, _, _ = blobs(8192, n_clusters=8, dim=4, seed=0)
    x = jnp.asarray(pts)
    key = jax.random.PRNGKey(0)

    mesh = compat.make_mesh((1,), ("data",))
    xd = jax.device_put(x, NamedSharding(mesh, P("data")))
    fit = make_distributed_sampled_kmeans(mesh, spec=spec)
    res = fit(xd, key)                         # compile + warm
    jax.block_until_ready(res.sse)
    t0 = time.perf_counter()
    res = fit(xd, key)
    jax.block_until_ready(res.sse)
    wall = time.perf_counter() - t0
    ref = fit_from_spec(x, spec, key)

    rel = abs(float(res.sse) - float(ref.sse)) / float(ref.sse)
    assert rel < 0.10, f"distributed vs single SSE diverged: {rel:.3f}"
    lo, hi = x.min(axis=0), x.max(axis=0)
    assert bool(jnp.all(res.centers >= lo - 1e-3)), "centers not unscaled"
    assert bool(jnp.all(res.centers <= hi + 1e-3)), "centers not unscaled"
    assert res.local_centers.shape[0] == spec.pool_schedule(x.shape[0])[-1]

    from repro.telemetry import calibrate, peak_rss_mb
    artifacts = pathlib.Path(__file__).resolve().parent / "artifacts"
    artifacts.mkdir(parents=True, exist_ok=True)
    record = {
        "schema": 1,
        "bench": "dist_smoke",
        "name": "dist_smoke",
        "spec_hash": spec.stable_hash(),
        "mode": "shard_map",
        "backend": spec.execution.backend,
        "calib_mflops": calibrate(),
        "workload": {"n": int(x.shape[0]), "dim": int(x.shape[1]),
                     "seed": 0},
        "us_best": wall * 1e6,
        "points_per_sec": x.shape[0] / wall,
        "peak_rss_mb": peak_rss_mb(),
        "sse": float(res.sse),
        "rel_sse": rel,
    }
    (artifacts / "BENCH_dist_smoke.json").write_text(
        json.dumps(record, indent=1))
    print(f"DIST_SMOKE_OK levels={spec.n_levels} "
          f"pool={spec.pool_schedule(x.shape[0])} rel_sse={rel:.4f} "
          f"pps={x.shape[0] / wall:.0f}")


if __name__ == "__main__":
    main()
