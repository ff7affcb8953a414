"""Faults planted in the program under test, to show that a run's
comparison catches them (``tests/test_faults.py`` at a test size;
``control.py --fault`` at a cell's own size on the chip).

  step_unchanged       a Lloyd step returns its centers unchanged
  half_batch           half of each partition's points left out of the
                       local k-means
  answer_altered       a fit's first center moved by 1 where it is produced
  distance_three_pass  the distance tile shared by the ``lloyd`` and
                       ``assign`` kernels computes its cross term in three
                       bf16 passes (hi*hi + hi*lo + lo*hi) instead of at
                       ``Precision.HIGHEST``
  lloyd_three_pass     the same, in the fused ``lloyd`` kernel only (the
                       Lloyd iterations; the final assignment stays exact)
  distance_one_pass    both kernels' cross term in one bf16 pass
  search_stale         a search answers with the previous request's answer
  search_half          half of a request's queries answered for all of it
  search_altered       every returned id moved by one
"""
from __future__ import annotations

import contextlib
import importlib

import numpy as np


def _trunc(a):
    """``a`` cut to its leading bfloat16 bits, as float32."""
    import jax
    import jax.numpy as jnp
    bits = jax.lax.bitcast_convert_type(a, jnp.int32)
    return jax.lax.bitcast_convert_type(bits & jnp.int32(-65536),
                                        jnp.float32)


def _tile(passes: int):
    """A copy of ``kernels/assign.py: distance_tile`` whose cross term
    takes ``passes`` (1 or 3) bf16 passes."""
    import jax
    import jax.numpy as jnp

    def dot(a, b):
        return jax.lax.dot_general(
            a, b, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    def distance_tile(x, c, ki, *, block_k, k_actual):
        x = x.astype(jnp.float32)
        c = c.astype(jnp.float32)
        x2 = jnp.sum(x * x, axis=-1, keepdims=True).T
        c2 = jnp.sum(c * c, axis=-1, keepdims=True)
        ch, xh = _trunc(c), _trunc(x)
        xc = dot(ch, xh)
        if passes == 3:
            xc = xc + (dot(ch, _trunc(x - xh)) + dot(_trunc(c - ch), xh))
        d2 = jnp.maximum(x2 + c2 - 2.0 * xc, 0.0)
        row = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, d2.shape, 0)
        d2 = jnp.where(row < k_actual, d2, 3.0e38)
        local_min = jnp.min(d2, axis=0, keepdims=True)
        local_arg = ki * block_k + jnp.argmin(
            d2, axis=0, keepdims=True).astype(jnp.int32)
        return local_min, local_arg
    return distance_tile


def _patches(fault: str) -> list:
    """``(object, attribute, replacement)`` for ``fault``."""
    mod = importlib.import_module
    api, kmeans = mod("repro.api"), mod("repro.core.kmeans")
    pipeline, ivf = mod("repro.core.pipeline"), mod("repro.index.ivf")
    lloyd_k, assign_k = mod("repro.kernels.lloyd"), mod("repro.kernels.assign")
    if fault == "step_unchanged":
        return [(kmeans, "_centers_from_stats", lambda sums, counts, old: old)]
    if fault == "half_batch":
        real = pipeline.gather_partitions

        def half(x, part, weights=None):
            pts, w = real(x, part, weights)
            keep = np.arange(w.shape[1]) < w.shape[1] // 2
            return pts, w * keep[None, :]
        return [(pipeline, "gather_partitions", half)]
    if fault == "answer_altered":
        real = api.fit_from_spec

        # the signature is kept: the donating path jits this function
        # with ``spec`` and ``backend`` static by name
        def altered(x, spec, key=None, *, backend=None, logger=None):
            r = real(x, spec, key, backend=backend, logger=logger)
            return r._replace(centers=r.centers.at[0].add(1.0))
        return [(api, "fit_from_spec", altered)]
    if fault == "distance_three_pass":
        return [(lloyd_k, "distance_tile", _tile(3)),
                (assign_k, "distance_tile", _tile(3))]
    if fault == "lloyd_three_pass":
        return [(lloyd_k, "distance_tile", _tile(3))]
    if fault == "distance_one_pass":
        return [(lloyd_k, "distance_tile", _tile(1)),
                (assign_k, "distance_tile", _tile(1))]
    if fault in ("search_stale", "search_half", "search_altered"):
        real = ivf.search
        last = {}

        def broken(index, queries, k=10, **kw):
            if fault == "search_half":
                h = queries.shape[0] // 2
                d, i = real(index, queries[:h], k, **kw)
                return (np.concatenate([d, d])[:queries.shape[0]],
                        np.concatenate([i, i])[:queries.shape[0]])
            d, i = real(index, queries, k, **kw)
            if fault == "search_altered":
                return d, i + 1
            prev = last.get("ans", (d, i))
            last["ans"] = (d, i)
            return prev
        return [(ivf, "search", broken)]
    raise ValueError(f"no fault {fault!r}")


@contextlib.contextmanager
def planted(fault: str):
    """The program with ``fault`` planted, for the duration of the block;
    compiled programs are dropped on the way in and out, so none built
    with the fault outlives it."""
    import jax
    patches = _patches(fault)
    saved = [(obj, name, getattr(obj, name)) for obj, name, _ in patches]
    jax.clear_caches()
    for obj, name, new in patches:
        setattr(obj, name, new)
    try:
        yield
    finally:
        for obj, name, old in reversed(saved):
            setattr(obj, name, old)
        jax.clear_caches()
