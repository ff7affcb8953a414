"""Paper Table 2: execution time, traditional k-means vs the parallel
sampled pipeline on 100k / 250k / 500k synthetic 2-D points (500/cluster).

Three numbers per size:
  * traditional  — full Lloyd on all points (paper's CPU column);
  * sampled-serial — the paper pipeline executed serially (shows the
    algorithmic overhead is bounded);
  * sampled-parallel(model P=64) — partition + local-stage/P + merge, the
    paper's GPU-block execution model (this container has 1 physical core,
    so P-way parallelism is *modeled* the way the paper's Tesla C2075 ran
    one block per subcluster; the shard_map path in
    repro.core.distributed is the real multi-device implementation).
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.paper_clustering import workload_spec
from repro.core import (relative_error, sampled_kmeans, standard_kmeans)
from repro.core.pipeline import local_stage
from repro.core.subcluster import equal_partition, feature_scale, gather_partitions
from repro.core.kmeans import kmeans
from repro.data.synthetic import blobs

SIZES = (100_000, 250_000, 500_000)
N_SUB = 64
COMPRESSION = 5
ITERS = 10


def _timed(fn, *a):
    t0 = time.perf_counter()
    out = fn(*a)
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


def run(csv):
    rows = []
    for n in SIZES:
        k = n // 500
        pts, _, _ = blobs(n, dim=2, seed=0)
        x = jnp.asarray(pts)

        full_fn = jax.jit(lambda xx: standard_kmeans(
            xx, k, iters=ITERS, key=jax.random.PRNGKey(0)).sse)
        full_fn(x)  # compile
        full_sse, t_full = _timed(full_fn, x)

        spec = workload_spec(f"synthetic_{n // 1000}k",
                             local_iters=ITERS, global_iters=ITERS)
        samp_fn = jax.jit(lambda xx, _s=spec: sampled_kmeans(
            xx, k, spec=_s, key=jax.random.PRNGKey(0)).sse)
        samp_fn(x)
        samp_sse, t_serial = _timed(samp_fn, x)

        # parallel model: partition once + ONE subcluster's local k-means
        # (= the per-block wall time on a P-block device) + the merge stage
        xs, _ = feature_scale(x)
        part_fn = jax.jit(lambda xx: equal_partition(xx, N_SUB).indices)
        part_fn(xs)
        _, t_part = _timed(part_fn, xs)
        part = equal_partition(xs, N_SUB)
        ptss, w = gather_partitions(xs, part)
        cap = ptss.shape[1]
        kl = max(1, cap // COMPRESSION)
        one_fn = jax.jit(lambda p, ww: kmeans(
            p, kl, weights=ww, iters=ITERS, key=jax.random.PRNGKey(0)).centers)
        one_fn(ptss[0], w[0])
        lc, t_one = _timed(one_fn, ptss[0], w[0])
        merge_fn = jax.jit(lambda c: kmeans(
            c, k, iters=ITERS, key=jax.random.PRNGKey(1)).sse)
        all_local = local_stage(ptss, w, kl, iters=1,
                                key=jax.random.PRNGKey(0)).centers
        flat = all_local.reshape(-1, 2)
        merge_fn(flat)
        _, t_merge = _timed(merge_fn, flat)
        t_parallel = t_part + t_one + t_merge
        rel = relative_error(float(samp_sse), float(full_sse))

        csv(f"table2/{n}/traditional", t_full * 1e6, f"k={k}")
        csv(f"table2/{n}/sampled_serial", t_serial * 1e6,
            f"rel_err={rel:+.3%}")
        csv(f"table2/{n}/sampled_parallel_P{N_SUB}", t_parallel * 1e6,
            f"speedup={t_full / t_parallel:.1f}x;paper=25x@250k,30x@500k")
        rows.append((n, t_full, t_serial, t_parallel, rel))
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run(lambda n, us, d: print(f"{n},{us:.1f},{d}"))
