"""Gradient compression by 1-D k-means quantization (beyond-paper use of the
paper's own machinery).

Before the cross-pod gradient exchange, each leaf is quantized to ``levels``
centroids fit by the paper's sampled clustering on the gradient values (1-D,
equal-sized subclusters = sorted value chunks).  With error feedback the
quantization residual is carried into the next step, so convergence is
preserved while the DCN all-reduce payload drops from 32 bits to
log2(levels) bits + the tiny codebook (16 levels -> 8x compression).

On this CPU container the collective itself is simulated (quantize ->
dequantize -> psum); the byte accounting in benchmarks/bench_compress.py
reports the payload reduction a real fabric would see.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.backend import BackendSpec, get_backend
from repro.core.kmeans import kmeans
from repro.core.metrics import nearest
from repro.core.spec import ClusterSpec, StopSpec


def quantize_leaf(g: jax.Array, levels: int, key,
                  backend: BackendSpec = None, *, iters: int | None = None,
                  stop: StopSpec | None = None,
                  init: str = "landmark") -> tuple[jax.Array, dict]:
    """-> (dequantized g, {codebook, indices-free stats}).  1-D k-means on a
    value sample (equal-sized subclustering over the sorted sample = the
    paper's Algorithm 1 in one dimension).  ``stop`` carries the stopping
    policy (``iters`` is the deprecated fixed-budget alias; default 8)."""
    if stop is None:
        stop = StopSpec(max_iters=8 if iters is None else iters)
    elif iters is not None:
        raise TypeError("quantize_leaf: pass either stop= or the deprecated "
                        "iters= alias, not both")
    flat = g.reshape(-1, 1).astype(jnp.float32)
    n = flat.shape[0]
    samp = flat[:: max(1, n // 4096)][:4096]
    res = kmeans(samp, levels, stop=stop, key=key, init=init,
                 backend=backend)
    code = res.centers[:, 0]                       # (levels,)
    idx = nearest(jnp.abs(flat - code[None, :]))
    deq = code[idx].reshape(g.shape)
    return deq.astype(g.dtype), {"codebook": code}


def make_grad_compressor(levels: int = 16, error_feedback: bool = True,
                         seed: int = 0, backend: BackendSpec = None,
                         spec: ClusterSpec | None = None):
    """Returns (compress_fn(grads, residual) -> (grads', residual'), init_residual).

    With ``spec=`` the codebook fit is declared as a ClusterSpec: ``merge.k``
    is the level count, ``merge.effective_stop``/``merge.init`` configure the
    1-D k-means, ``execution.backend`` the Lloyd machinery.
    """
    if spec is not None:
        levels = spec.merge.k
        stop, init = spec.merge.effective_stop, spec.merge.init
        backend = backend if backend is not None else spec.execution.backend
    else:
        stop, init = StopSpec(max_iters=8), "landmark"
    be = get_backend(backend)

    def compress(grads, residual=None):
        leaves, treedef = jax.tree.flatten(grads)
        res_leaves = (treedef.flatten_up_to(residual) if residual is not None
                      else [jnp.zeros_like(l) for l in leaves])
        out, new_res = [], []
        for i, (g, r) in enumerate(zip(leaves, res_leaves)):
            gc = g + r if error_feedback else g
            key = jax.random.fold_in(jax.random.PRNGKey(seed), i)
            deq, _ = quantize_leaf(gc, levels, key, backend=be,
                                   stop=stop, init=init)
            out.append(deq)
            new_res.append((gc - deq) if error_feedback else r)
        return (jax.tree.unflatten(treedef, out),
                jax.tree.unflatten(treedef, new_res))

    return compress


def compressed_bytes(grads, levels: int) -> tuple[int, int]:
    """(raw fp32 bytes, compressed payload bytes) for the cross-pod exchange."""
    import math
    bits = max(1, math.ceil(math.log2(levels)))
    raw = comp = 0
    for g in jax.tree.leaves(grads):
        raw += g.size * 4
        comp += (g.size * bits) // 8 + levels * 4
    return raw, comp
