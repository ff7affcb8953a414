"""End-to-end driver at the paper's largest scale: cluster 500k synthetic
points (500 per cluster, like §VI), with the distributed shard_map pipeline
when multiple devices are available.

  PYTHONPATH=src python examples/cluster_500k.py [--n 500000] [--devices 8]

With more than one device the real shard_map pipeline runs too (one device
= one batch of subclusters — the paper's CUDA-block mapping); the merge
stage runs both replicated (paper-faithful) and distributed (beyond-paper,
O(k*d) exchange per round).  On a TPU host that is every chip; on the CPU,
--devices N splits the host into N devices (the flag only shapes the CPU
backend, so it changes nothing on a chip).
"""
import argparse
import os
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=500_000)
    ap.add_argument("--devices", type=int, default=0)
    ap.add_argument("--compression", type=int, default=5)
    args = ap.parse_args()

    if args.devices:
        # set before jax initialises; read by the CPU backend only
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}")

    import jax
    import jax.numpy as jnp
    from repro import compat
    from repro.api import SampledKMeans
    from repro.configs.paper_clustering import workload_spec
    from repro.core import (make_distributed_sampled_kmeans, relative_error,
                            standard_kmeans)
    from repro.data.synthetic import blobs

    n = args.n
    k = n // 500
    print(f"generating {n} points / {k} clusters ...")
    pts, _, _ = blobs(n, dim=2, seed=0)
    x = jnp.asarray(pts)

    t0 = time.perf_counter()
    full = standard_kmeans(x, k, iters=10, key=jax.random.PRNGKey(0))
    jax.block_until_ready(full.sse)
    t_full = time.perf_counter() - t0
    print(f"traditional k-means: {t_full:8.2f}s  sse={float(full.sse):.1f}")

    spec = workload_spec("synthetic_500k", compression=args.compression,
                         local_iters=10, global_iters=10)
    spec = spec.replace(k=k) if k != spec.merge.k else spec
    t0 = time.perf_counter()
    samp = SampledKMeans(spec).fit(x, key=jax.random.PRNGKey(0)).result_
    jax.block_until_ready(samp.sse)
    t_s = time.perf_counter() - t0
    print(f"sampled (serial):    {t_s:8.2f}s  sse={float(samp.sse):.1f}  "
          f"rel_err={relative_error(float(samp.sse), float(full.sse)):+.2%}")

    ndev = jax.device_count()
    if ndev > 1:
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = compat.make_mesh((ndev,), ("data",))
        xd = jax.device_put(x[: n - n % ndev], NamedSharding(mesh, P("data")))
        dist_spec = spec.replace(n_sub=max(1, 64 // ndev))
        for merge in ("replicated", "distributed"):
            fn = make_distributed_sampled_kmeans(
                mesh, spec=dist_spec, merge=merge)
            res = fn(xd, jax.random.PRNGKey(0))
            jax.block_until_ready(res.sse)
            t0 = time.perf_counter()
            res = fn(xd, jax.random.PRNGKey(0))
            jax.block_until_ready(res.sse)
            dt = time.perf_counter() - t0
            # distributed results are in input space now — directly
            # comparable to the serial rows above
            print(f"shard_map x{ndev} ({merge:11s}): {dt:8.2f}s  "
                  f"sse={float(res.sse):.1f}  "
                  f"rel_err={relative_error(float(res.sse), float(full.sse)):+.2%}")


if __name__ == "__main__":
    main()
