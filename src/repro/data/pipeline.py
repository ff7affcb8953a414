"""Data pipeline with the paper's clustering applied to batch composition.

``ClusterBalancedSampler`` builds document *sketches* (cheap hashed bag-of-
tokens embeddings), runs the paper's two-level sampled k-means over them, and
then draws batches cluster-uniformly (rare clusters are not swamped by
near-duplicate documents — the sampled-clustering version of dedup /
mixture balancing).  Everything is deterministic in (seed, step): restart
replays the stream exactly (fault tolerance without iterator snapshots).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import ClusterSpec, sampled_kmeans
from repro.core.metrics import HIGHEST, nearest


def doc_sketch(tokens: np.ndarray, dim: int = 32) -> np.ndarray:
    """(n_docs, seq) int tokens -> (n_docs, dim) hashed bag-of-tokens."""
    h1 = (tokens.astype(np.int64) * 2654435761 % 2 ** 31) % dim
    out = np.zeros((tokens.shape[0], dim), np.float32)
    rows = np.repeat(np.arange(tokens.shape[0]), tokens.shape[1])
    np.add.at(out, (rows, h1.reshape(-1)), 1.0)
    norms = np.linalg.norm(out, axis=1, keepdims=True)
    return out / np.maximum(norms, 1e-6)


class ClusterBalancedSampler:
    """Cluster a corpus of documents once (paper pipeline), then sample
    batches uniformly over clusters."""

    def __init__(self, docs_tokens: np.ndarray, n_clusters: int | None = None,
                 *, n_sub: int = 8, compression: int = 5, seed: int = 0,
                 spec: ClusterSpec | None = None):
        self.docs = docs_tokens
        sketches = jnp.asarray(doc_sketch(docs_tokens))
        if spec is None:
            spec = ClusterSpec.make(16 if n_clusters is None else n_clusters,
                                    scheme="equal", n_sub=n_sub,
                                    compression=compression)
        elif n_clusters is not None and n_clusters != spec.merge.k:
            raise ValueError(f"n_clusters={n_clusters} disagrees with "
                             f"spec.merge.k={spec.merge.k}")
        res = sampled_kmeans(sketches, spec.merge.k, spec=spec,
                             key=jax.random.PRNGKey(seed))
        d2 = (jnp.sum(sketches ** 2, -1, keepdims=True)
              + jnp.sum(res.centers ** 2, -1)[None, :]
              - 2.0 * jnp.matmul(sketches, res.centers.T,
                                 precision=HIGHEST))
        self.assignment = np.asarray(nearest(d2))
        self.n_clusters = spec.merge.k
        self.by_cluster = [np.nonzero(self.assignment == c)[0]
                           for c in range(self.n_clusters)]
        self.by_cluster = [ids for ids in self.by_cluster if len(ids)]
        self.seed = seed

    def batch_indices(self, step: int, batch_size: int) -> np.ndarray:
        rng = np.random.default_rng((self.seed * 7_919 + step) % 2 ** 63)
        cl = rng.integers(0, len(self.by_cluster), batch_size)
        return np.array([
            self.by_cluster[c][rng.integers(0, len(self.by_cluster[c]))]
            for c in cl])

    def batch(self, step: int, batch_size: int, seq_len: int) -> dict:
        ids = self.batch_indices(step, batch_size)
        toks = self.docs[ids, : seq_len + 1].astype(np.int32)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
