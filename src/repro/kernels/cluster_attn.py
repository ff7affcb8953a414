"""Pallas TPU kernel: decode attention over a *clustered* KV cache.

This is the paper's sampled-clustering output used as an attention operand:
keys/values are the per-subcluster k-means centroids (kc, vc) with member
counts; a query attends to centroid j with logit  q.kc_j * scale + log n_j,
which is the first-order approximation of attending to every member of the
cluster (sum_i exp(q.k_i) ~ n_j exp(q.kbar_j)).  Compression c shrinks the
cache read per decoded token by c - this is what makes long_500k decode
runnable for full-attention architectures.

Flash-style online softmax over centroid tiles; the running (max, denom,
accumulator) carry lives in the revisited output VMEM blocks (sequential
grid walk over centroid tiles), so no scratch is needed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NEG = -1.0e30


def _cluster_attn_kernel(q_ref, kc_ref, vc_ref, cnt_ref,
                         acc_ref, m_ref, l_ref, *, scale: float):
    j = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32)        # (g, dh)
    kc = kc_ref[0, 0].astype(jnp.float32)      # (bn, dh)
    vc = vc_ref[0, 0].astype(jnp.float32)      # (bn, dh)
    cnt = cnt_ref[0, 0].astype(jnp.float32)    # (bn,)

    logits = jax.lax.dot_general(
        q, kc, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale          # (g, bn)
    bias = jnp.where(cnt > 0, jnp.log(jnp.maximum(cnt, 1e-9)), _NEG)
    logits = logits + bias[None, :]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    m_old = m_ref[0, 0]                                       # (g,)
    m_new = jnp.maximum(m_old, jnp.max(logits, axis=-1))
    alpha = jnp.exp(m_old - m_new)                            # (g,)
    p = jnp.exp(logits - m_new[:, None])                      # (g, bn)
    l_ref[0, 0] = l_ref[0, 0] * alpha + jnp.sum(p, axis=-1)
    acc_ref[0, 0] = (acc_ref[0, 0] * alpha[:, None]
                     + jax.lax.dot_general(
                         p, vc, (((1,), (0,)), ((), ())),
                         preferred_element_type=jnp.float32))
    m_ref[0, 0] = m_new


def cluster_attn_decode_pallas(
    q: jax.Array,       # (B, H, dh)
    kc: jax.Array,      # (B, Hkv, Nc, dh)
    vc: jax.Array,      # (B, Hkv, Nc, dh)
    counts: jax.Array,  # (B, Hkv, Nc)
    scale: float,
    *,
    block_n: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    from . import default_interpret
    if interpret is None:
        interpret = default_interpret()
    b, h, dh = q.shape
    hkv, nc = kc.shape[1], kc.shape[2]
    g = h // hkv
    qg = q.reshape(b, hkv, g, dh)

    bn = min(block_n, nc)
    ncp = -(-nc // bn) * bn
    if ncp != nc:
        pad = ((0, 0), (0, 0), (0, ncp - nc), (0, 0))
        kc = jnp.pad(kc, pad)
        vc = jnp.pad(vc, pad)
        counts = jnp.pad(counts, ((0, 0), (0, 0), (0, ncp - nc)))
    grid = (b, hkv, ncp // bn)

    acc, m, l = pl.pallas_call(
        functools.partial(_cluster_attn_kernel, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, g, dh), lambda b_, h_, j: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, bn, dh), lambda b_, h_, j: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, bn, dh), lambda b_, h_, j: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, bn), lambda b_, h_, j: (b_, h_, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, g, dh), lambda b_, h_, j: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, g), lambda b_, h_, j: (b_, h_, 0)),
            pl.BlockSpec((1, 1, g), lambda b_, h_, j: (b_, h_, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, g, dh), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, g), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, g), jnp.float32),
        ],
        interpret=interpret,
        name="cluster_attn",
    )(qg, kc, vc, counts)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, h, dh)
