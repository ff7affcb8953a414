"""Pure-jnp oracles for every Pallas kernel (the correctness contract the
shape/dtype sweep tests assert against)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

# full-f32 MXU passes: a TPU's default f32 matmul is one bf16 pass
HIGHEST = jax.lax.Precision.HIGHEST


def assign_argmin_ref(x: jax.Array, c: jax.Array) -> tuple[jax.Array, jax.Array]:
    x = x.astype(jnp.float32)
    c = c.astype(jnp.float32)
    d2 = (jnp.sum(x * x, -1, keepdims=True)
          + jnp.sum(c * c, -1)[None, :]
          - 2.0 * jnp.matmul(x, c.T, precision=HIGHEST))
    d2 = jnp.maximum(d2, 0.0)
    # min as a value, then its first index: a bare argmin is narrowed to
    # bf16 by XLA:TPU (see repro.core.metrics.nearest)
    idx = jnp.argmax(d2 == jnp.min(d2, -1, keepdims=True),
                     axis=-1).astype(jnp.int32)
    mind = jnp.take_along_axis(d2, idx[:, None], axis=-1)[:, 0]
    return idx, mind


def centroid_update_ref(
    x: jax.Array, idx: jax.Array, w: jax.Array, k: int
) -> tuple[jax.Array, jax.Array]:
    x = x.astype(jnp.float32)
    onehot = jax.nn.one_hot(idx, k, dtype=jnp.float32) * w.astype(jnp.float32)[:, None]
    return jnp.matmul(onehot.T, x, precision=HIGHEST), onehot.sum(axis=0)


def lloyd_step_ref(
    x: jax.Array, w: jax.Array, c: jax.Array
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Oracle for the fused Lloyd step: raw weighted per-cluster sums /
    counts, weighted SSE, and the assignment itself (all fp32)."""
    idx, mind = assign_argmin_ref(x, c)
    k = c.shape[0]
    sums, counts = centroid_update_ref(x, idx, w, k)
    sse = jnp.sum(mind * w.astype(jnp.float32))
    return sums, counts, sse, idx, mind


def adc_scan_ref(luts: jax.Array, codes: jax.Array) -> jax.Array:
    """Oracle for the ADC scan: (B, m, C) LUTs + (B, L, m) codes ->
    (B, L) f32 candidate distances, via explicit per-subspace one-hot
    contractions (deliberately a different formulation than both the
    production gather path and the Pallas kernel)."""
    luts = luts.astype(jnp.float32)
    b, m, c = luts.shape
    onehot = jax.nn.one_hot(codes.astype(jnp.int32), c,
                            dtype=jnp.float32)            # (B, L, m, C)
    return jnp.einsum("blmc,bmc->bl", onehot, luts, precision=HIGHEST)


def cluster_attn_decode_ref(
    q: jax.Array,        # (h, dh)
    kc: jax.Array,       # (hkv, n, dh) centroid keys
    vc: jax.Array,       # (hkv, n, dh) centroid values
    counts: jax.Array,   # (hkv, n) member counts (0 = dead centroid)
    scale: float,
) -> jax.Array:
    """Decode attention over clustered KV: logit bias log(count) approximates
    sum_{i in cluster j} exp(q.k_i) ~= count_j * exp(q.kbar_j)."""
    h = q.shape[0]
    hkv = kc.shape[0]
    g = h // hkv
    qg = q.reshape(hkv, g, -1).astype(jnp.float32)
    logits = jnp.einsum("hgd,hnd->hgn", qg, kc.astype(jnp.float32)) * scale
    bias = jnp.where(counts > 0, jnp.log(jnp.maximum(counts, 1e-9)), -jnp.inf)
    logits = logits + bias[:, None, :]
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("hgn,hnd->hgd", p, vc.astype(jnp.float32))
    return out.reshape(h, -1)
