"""Streaming engine: ingest throughput (points/sec) and SSE vs the batch
oracle on a drifting synthetic stream.

  PYTHONPATH=src python -m benchmarks.bench_stream

Two numbers per configuration:
  * steady-state update throughput — points/sec through the jitted
    ``StreamingClusterer.update`` (compile excluded by a warm-up chunk);
  * quality — final-centers SSE over the full stream history, relative to a
    batch ``sampled_kmeans`` run on all points at once (the oracle a
    re-cluster-from-scratch design would pay for on every refresh).
"""
import time

import jax
import jax.numpy as jnp

from repro.core import ClusterSpec, relative_error, sampled_kmeans, sse
from repro.data.synthetic import drifting_blobs
from repro.stream import StreamConfig, StreamingClusterer

N_CHUNKS = 24
CHUNK = 4096
K = 16
DIM = 2


def run(csv):
    chunks, _, _ = drifting_blobs(N_CHUNKS, CHUNK, n_clusters=K, dim=DIM,
                                  seed=0, drift=0.02)
    rows = []
    # local_iters/global_iters mirror StreamConfig's 8/8 defaults so the
    # spec-built engine times the same work as before
    spec = ClusterSpec.make(K, n_sub=16, compression=5,
                            local_iters=8, global_iters=8)
    for decay, buffer_size in ((0.97, 2048), (0.90, 1024)):
        sc = StreamingClusterer(StreamConfig.from_spec(
            spec, decay=decay, buffer_size=buffer_size))
        state = sc.init(dim=DIM)
        state = sc.update(state, jnp.asarray(chunks[0]))  # warm-up/compile
        jax.block_until_ready(state.centers)

        t0 = time.perf_counter()
        for ch in chunks[1:]:
            state = sc.update(state, jnp.asarray(ch))
        jax.block_until_ready(state.centers)
        dt = time.perf_counter() - t0
        pts_per_sec = (N_CHUNKS - 1) * CHUNK / dt

        full = jnp.asarray(chunks.reshape(-1, DIM))
        oracle = sampled_kmeans(full, K,
                                spec=ClusterSpec.make(K, n_sub=16,
                                                      compression=5),
                                key=jax.random.PRNGKey(0))
        rel = relative_error(float(sse(full, state.centers)),
                             float(oracle.sse))
        csv(f"stream/decay{decay}_buf{buffer_size}",
            dt / (N_CHUNKS - 1) * 1e6,
            f"points_per_sec={pts_per_sec:,.0f};rel_err_vs_batch={rel:+.3%}")
        rows.append((decay, buffer_size, pts_per_sec, rel))
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run(lambda n, us, d: print(f"{n},{us:.1f},{d}"))
