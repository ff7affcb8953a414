"""Closed-loop ANN search, one client: requests of ``batch`` queries, drawn
from the configuration's query pool in a seeded order, each answered by
``IVFIndex.search`` (k, nprobe from the traffic file) and blocked on.

Set-up makes the points and the query pool on the device and builds the
index with ``build_index`` from the configuration's ``index.spec``.  The
points, the pool and the index come from the traffic's ``index_seed``, the
same for every run: the index's list lengths set the work of every query,
and a new index per seed moved throughput between seeds by up to a sixth
on a TPU v5e.  The run's seed draws the order of the requests and the
sample compared.  After
the window that sample of the answered requests is compared with the plain
reference's exact nearest neighbours:

  invalid     share of returned ids that are not rows of the base set, or
              repeat within a query's answer (exact)
  miss        1 - recall@k: share of the exact k nearest not returned
  out100      share of returned ids that are not among the exact 100
              nearest
  dist_excess summed exact squared distance of the returned ids over that
              of the exact k nearest, minus 1 (per query, then the mean)
"""
from __future__ import annotations

import numpy as np

import data as bench_data
import reference


WIDE = 100


def search_numbers(x, q, found: np.ndarray, k: int) -> dict:
    """The compared numbers for returned ids ``found`` (nq, k) of queries
    ``q`` against the exact neighbours of ``q`` in ``x``."""
    import jax.numpy as jnp
    n = int(x.shape[0])
    true = np.asarray(reference.exact_knn(x, q, k=WIDE))
    srt = np.sort(found, axis=1)
    bad = ((found < 0) | (found >= n)).sum() + (srt[:, 1:] == srt[:, :-1]).sum()
    safe = np.clip(found, 0, n - 1)
    d_found = np.asarray(reference.row_sqdist(x, q, jnp.asarray(safe)))
    d_true = np.asarray(reference.row_sqdist(x, q, jnp.asarray(true[:, :k])))
    hit_k = (true[:, :k, None] == found[:, None, :]).any(axis=2)
    in_wide = (found[:, :, None] == true[:, None, :]).any(axis=2)
    excess = d_found.sum(axis=1) / np.maximum(d_true.sum(axis=1), 1e-30)
    return {"invalid": float(bad) / found.size,
            "miss": 1.0 - float(hit_k.mean()),
            "out100": 1.0 - float(in_wide.mean()),
            "dist_excess": float(np.mean(excess)) - 1.0}


class Driver:
    def __init__(self, cell, seed: int, devices, index_spec=None):
        self.cell = cell
        self.seed = seed
        self.devices = devices
        self.answers = {}
        self._index_spec = index_spec

    def setup(self):
        import jax
        from repro.index import IndexSpec, build_index
        cfg, tr = self.cell.config, self.cell.traffic
        k_data, k_build = jax.random.split(
            bench_data.seed_key(int(tr["index_seed"])))
        k_req, self.k_pick = jax.random.split(bench_data.seed_key(self.seed))
        d = bench_data.make(cfg["data"], k_data)
        self.x, pool = d["x"], d["queries"]
        spec = self._index_spec or IndexSpec.from_dict(cfg["index"]["spec"])
        self.index, self.build_stats = build_index(self.x, spec, k_build)
        self.k, self.nprobe = int(tr["k"]), int(tr["nprobe"])
        b = int(tr["batch"])
        n_req = pool.shape[0] // b
        order = jax.random.permutation(k_req, pool.shape[0])[:n_req * b]
        qs = pool[order].reshape(n_req, b, pool.shape[1])
        self.requests = [qs[i] for i in range(n_req)]
        jax.block_until_ready(self.requests)
        self._search(self.requests[0])    # compile every program

    def _search(self, q):
        import jax
        _, ids = self.index.search(q, k=self.k, nprobe=self.nprobe)
        return jax.block_until_ready(ids)

    def step(self, i: int) -> int:
        r = i % len(self.requests)
        self.answers[r] = self._search(self.requests[r])
        return int(self.requests[r].shape[0])

    def layer_counts(self, window) -> dict:
        """Shape counts of what the window scanned: per query, ``nprobe``
        lists of ``cap`` slots each, of which the probed lists' real
        members are candidates (routing recomputed on the index's coarse
        centers)."""
        import jax
        import jax.numpy as jnp
        idx = self.index
        counts = np.asarray(idx.counts)
        q = jnp.concatenate(self.requests)
        _, cells = jax.lax.top_k(
            -reference.sqdist(q, idx.coarse_centers, reference.HIGHEST),
            self.nprobe)
        per_query_real = counts[np.asarray(cells)].sum() / q.shape[0]
        nq = int(sum(window.units))
        return {"queries": nq, "nprobe": self.nprobe, "cap": idx.cap,
                "candidates": per_query_real * nq,
                "m": int(idx.codes.shape[2]),
                "codes": int(idx.codebooks.shape[1]),
                "slots": nq * self.nprobe * idx.cap,
                "mean_list": float(counts.mean())}

    def release(self):
        self.index = None

    def check(self, window) -> dict:
        import jax
        import jax.numpy as jnp
        served = sorted(self.answers)
        n = min(int(self.cell.traffic["check_requests"]), len(served))
        pick = np.asarray(jax.random.permutation(self.k_pick,
                                                 len(served)))[:n]
        rs = [served[j] for j in sorted(pick)]
        q = jnp.concatenate([self.requests[r] for r in rs])
        found = np.concatenate([np.asarray(self.answers[r]) for r in rs])
        return search_numbers(self.x, q, found, self.k)


def control_readings(cell, seed: int, devices, steps: int = 0) -> dict:
    """The control in the program's place: the program's own 4-bit PQ path
    (``pq.bits=4``, the step below the configured 8 bits), over its first
    ``steps`` requests (all of the pool with 0), through the same
    comparison."""
    import dataclasses
    from repro.index import IndexSpec
    import harness
    spec = IndexSpec.from_dict(cell.config["index"]["spec"])
    spec = dataclasses.replace(spec, pq=dataclasses.replace(spec.pq, bits=4))
    d = Driver(cell, seed, devices, index_spec=spec)
    d.setup()
    window = harness.Window([], [], [], 0.0, 0)
    for i in range(steps or len(d.requests)):
        window.units.append(d.step(i))
    d.release()
    return d.check(window)
