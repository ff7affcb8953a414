"""Seeded data for the cells, made on the device in one jitted call.

Each configuration file names a generator and its parameters under
``data``; :func:`make` looks the generator up by that name.  Every seed
gives the same shapes and the same multiset of cluster sizes, in another
order, so the work of a run does not depend on the seed.

  ``blobs``  the paper's synthetic set: ``n_clusters`` Gaussian blobs of
             ``n // n_clusters`` points each, centers uniform on
             ``[0, box)^dim``, standard deviation ``spread``; rows shuffled.
  ``gmm``    a Gaussian mixture at an embedding width: ``components``
             means uniform on ``[0, box)^dim``, noise ``sigma``; component
             sizes follow a Zipf law (``zipf`` exponent over a permutation
             of the ranks), so inverted lists come out uneven.  Rows are
             shuffled, so any prefix is a sample of the whole.  Queries come
             from the same mixture with their own quotas.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number (also above 32 bits)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 32)),
                              seed >> 32)


def zipf_quotas(total: int, parts: int, exponent: float) -> np.ndarray:
    """``parts`` whole-number sizes summing to ``total``, proportional to
    ``rank ** -exponent`` (rank 1 first); the same for every seed."""
    w = np.arange(1, parts + 1, dtype=np.float64) ** -float(exponent)
    raw = total * w / w.sum()
    q = np.floor(raw).astype(np.int64)
    rest = total - int(q.sum())
    q[np.argsort(raw - q, kind="stable")[::-1][:rest]] += 1
    return q


@functools.partial(jax.jit, static_argnames=("n", "n_clusters", "dim"))
def _blobs(key, *, n: int, n_clusters: int, dim: int, box, spread):
    kc, kn, kp = jax.random.split(key, 3)
    centers = jax.random.uniform(kc, (n_clusters, dim), jnp.float32) * box
    ids = jnp.arange(n, dtype=jnp.int32) // (n // n_clusters)
    x = centers[ids] + spread * jax.random.normal(kn, (n, dim), jnp.float32)
    return x[jax.random.permutation(kp, n)], centers


def blobs(key, *, n: int, dim: int, n_clusters: int, box: float,
          spread: float) -> dict:
    if n % n_clusters:
        raise ValueError(f"blobs: n={n} is not a multiple of {n_clusters}")
    x, centers = _blobs(key, n=n, n_clusters=n_clusters, dim=dim,
                        box=float(box), spread=float(spread))
    return {"x": x, "centers": centers}


@functools.partial(jax.jit, static_argnames=("dim", "components"))
def _mixture(key, quota_ranks, *, dim: int, components: int, box, sigma):
    """Rows of a mixture whose component ``j`` gets ``quota[rank[j]]`` rows
    (``quota_ranks``: the row's rank, already expanded to one per row)."""
    km, kperm, kn, kshuf = jax.random.split(key, 4)
    means = jax.random.uniform(km, (components, dim), jnp.float32) * box
    # rank r (0 = largest quota) belongs to component perm[r]
    perm = jax.random.permutation(kperm, components)
    comp = perm[quota_ranks]
    n = quota_ranks.shape[0]
    x = means[comp] + sigma * jax.random.normal(kn, (n, dim), jnp.float32)
    return x[jax.random.permutation(kshuf, n)], means, perm


def gmm(key, *, n: int, dim: int, components: int, zipf: float, box: float,
        sigma: float, queries: int = 0) -> dict:
    """Points, and ``queries`` query rows, from one Zipf-weighted mixture;
    the queries use the same means and component permutation."""
    kd, kq = jax.random.split(key)
    ranks = np.repeat(np.arange(components),
                      zipf_quotas(n, components, zipf)).astype(np.int32)
    x, means, perm = _mixture(kd, jnp.asarray(ranks), dim=dim,
                              components=components, box=float(box),
                              sigma=float(sigma))
    out = {"x": x, "centers": means}
    if queries:
        qranks = np.repeat(np.arange(components),
                           zipf_quotas(queries, components, zipf))
        q = _queries(kq, means, perm, jnp.asarray(qranks, jnp.int32),
                     sigma=float(sigma))
        out["queries"] = q
    return out


@jax.jit
def _queries(key, means, perm, quota_ranks, *, sigma):
    kn, kshuf = jax.random.split(key)
    comp = perm[quota_ranks]
    n = quota_ranks.shape[0]
    q = means[comp] + sigma * jax.random.normal(
        kn, (n, means.shape[1]), jnp.float32)
    return q[jax.random.permutation(kshuf, n)]


GENERATORS = {"blobs": blobs, "gmm": gmm}


def make(data_cfg: dict, key) -> dict:
    """Arrays of one configuration's ``data`` section, on the default
    device, ready (blocked on)."""
    params = {k: v for k, v in data_cfg.items() if k != "generator"}
    out = GENERATORS[data_cfg["generator"]](key, **params)
    return jax.block_until_ready(out)
