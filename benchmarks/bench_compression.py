"""Paper Table 3: execution time (and SSE, which the paper omits) across
compression values c = 5, 10, 15, 20 on the 500k-point synthetic set —
including the c=20 cell the paper left blank (text claims ~55x speedup).
"""
import time

import jax
import jax.numpy as jnp

from repro.configs.paper_clustering import COMPRESSION_SWEEP, workload_spec
from repro.core import relative_error, sampled_kmeans, standard_kmeans
from repro.data.synthetic import blobs

N = 500_000
N_SUB = 64
ITERS = 10


def run(csv):
    pts, _, _ = blobs(N, dim=2, seed=0)
    x = jnp.asarray(pts)
    k = N // 500
    full_fn = jax.jit(lambda xx: standard_kmeans(
        xx, k, iters=ITERS, key=jax.random.PRNGKey(0)).sse)
    full_fn(x)
    t0 = time.perf_counter()
    full_sse = full_fn(x)
    jax.block_until_ready(full_sse)
    t_full = time.perf_counter() - t0

    rows = []
    for c in COMPRESSION_SWEEP:
        spec = workload_spec("synthetic_500k", compression=c,
                             local_iters=ITERS, global_iters=ITERS)
        fn = jax.jit(lambda xx, _s=spec: sampled_kmeans(
            xx, k, spec=_s, key=jax.random.PRNGKey(0)).sse)
        fn(x)
        t0 = time.perf_counter()
        sse = fn(x)
        jax.block_until_ready(sse)
        dt = time.perf_counter() - t0
        rel = relative_error(float(sse), float(full_sse))
        csv(f"table3/c{c}", dt * 1e6,
            f"serial_speedup={t_full / dt:.2f}x;rel_err={rel:+.3%}")
        rows.append((c, dt, rel))
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run(lambda n, us, d: print(f"{n},{us:.1f},{d}"))
