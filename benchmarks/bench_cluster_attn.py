"""The paper's error-vs-compression trade applied to the framework's
flagship integration: clustered-KV decode attention.

For a structured KV cache, sweep compression c and report (a) relative
error of the attention output vs exact full-cache attention, (b) the cache
bytes read per decoded token (the memory-roofline win that makes long_500k
decode runnable for full-attention archs).
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ref import cluster_attn_decode_ref
from repro.models.attention import compress_kv_cache


def run(csv):
    rng = np.random.default_rng(0)
    B, kv, S, dh, h = 1, 8, 8192, 128, 32
    g = h // kv
    # keys with local (rope-like) drift: the regime the paper's equal-sized
    # contiguous chunks exploit
    drift = np.cumsum(rng.normal(0, 0.05, (B, kv, S, dh)), axis=2)
    k = (drift + 0.4 * rng.normal(size=(B, kv, S, dh))).astype(np.float32)
    v = rng.normal(size=(B, kv, S, dh)).astype(np.float32)
    q = jnp.asarray(rng.normal(size=(B, h, dh)), jnp.float32)
    kj, vj = jnp.asarray(k), jnp.asarray(v)
    scale = dh ** -0.5

    qg = q.reshape(B, kv, g, dh)
    logits = jnp.einsum("bkgd,bksd->bkgs", qg, kj) * scale
    p = jax.nn.softmax(logits, -1)
    exact = jnp.einsum("bkgs,bksd->bkgd", p, vj).reshape(B, h, dh)
    full_bytes = 2 * S * dh * kv * 2  # k+v bf16 per head-group read

    rows = []
    for c in (8, 16, 32, 64, 128):
        t0 = time.perf_counter()
        kc, vc, counts = compress_kv_cache(kj, vj, chunk=max(4 * c, 64),
                                           compression=c, iters=8)
        jax.block_until_ready(kc)
        t_comp = time.perf_counter() - t0
        approx = jax.vmap(lambda a, b_, c_, d: cluster_attn_decode_ref(
            a, b_, c_, d, scale))(q, kc, vc, counts)
        err = float(jnp.linalg.norm(approx - exact) / jnp.linalg.norm(exact))
        comp_bytes = 2 * (S // c) * dh * kv * 2 + 4 * (S // c) * kv
        csv(f"cluster_attn/c{c}", t_comp * 1e6,
            f"rel_err={err:.4f};cache_read_reduction="
            f"{full_bytes / comp_bytes:.1f}x")
        rows.append((c, err, full_bytes / comp_bytes))
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run(lambda n, us, d: print(f"{n},{us:.1f},{d}"))
