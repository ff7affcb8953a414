"""Closed-loop fits, one client: ``SampledKMeans.fit`` back to back on the
resident points, a fresh key per fit, each blocked on until its result is
ready.  Where the spec donates its input (``execution.donate``: the whole
fit runs as one compiled program), each fit is handed a fresh device copy
of the points, and the copy is timed with the fit.

The configuration's ``data`` section makes the points from the traffic's
``data_seed``, the same for every run: the layout of the clusters moved a
fit's time by a tenth between seeds on a TPU v5e, while two runs of one
seed agreed.  The run's seed draws the order of the rows and the fits'
keys.  The configuration's ``fit.spec`` (``ClusterSpec.to_dict`` form) is
the job.  One fit of those the window
completed is kept, drawn from the seed by reservoir sampling (fit ``i``
replaces the kept one with probability ``1 / (i + 1)``), so the draw is
uniform over the window's fits and at most two fits' outputs are held at a
time.  After the window the plain reference replays it from the same key,
and the two are compared:

  mass_gap    |sum of the pool's member counts - points|  (exact)
  assign_miss share of points whose partition's nearest pool row, by the
              reference, is not the one the fit counted them to
  fold_gap    share of points counted to other pool rows than in the
              reference's replay of the partition and local stage
  sse_err     |the fit's own SSE - the reference's SSE of its centers|,
              over the latter
  fit_gap     the reference's SSE of the fit's centers over that of the
              replay's centers, minus 1
"""
from __future__ import annotations

import numpy as np

import data as bench_data
import reference


def fit_numbers(x, spec: dict, key, out: dict) -> dict:
    """The compared numbers for one fit's output ``out`` (``centers``,
    ``sse``, ``pool``, ``pool_w`` as numpy-convertible arrays), against the
    reference replay of the same fit from ``key``."""
    import jax.numpy as jnp
    n = int(x.shape[0])
    n_sub = spec["partition"]["n_sub"]
    cap = -(-n // n_sub)
    k_local = max(1, cap // spec["local"]["compression"])
    ref = reference.sampled_kmeans(x, spec, key)
    lo = jnp.min(x, axis=0)
    span = jnp.maximum(jnp.max(x, axis=0) - lo, 1e-9)
    xs = (x - lo) / span
    pool_w = np.asarray(out["pool_w"], np.float64)
    checked = np.asarray(reference.fold_counts(
        xs, (jnp.asarray(out["pool"]) - lo) / span, n_sub=n_sub,
        k_local=k_local), np.float64)
    s_prog = float(reference.min_sqdist_sum(x, jnp.asarray(out["centers"])))
    s_ref = float(reference.min_sqdist_sum(x, ref["centers"]))
    ref_w = np.asarray(ref["pool_w"], np.float64)
    return {
        "mass_gap": abs(float(pool_w.sum()) - n),
        "assign_miss": float(np.abs(pool_w - checked).sum()) / (2 * n),
        "fold_gap": float(np.abs(pool_w - ref_w).sum()) / (2 * n),
        "sse_err": abs(float(out["sse"]) - s_prog) / s_prog,
        "fit_gap": s_prog / s_ref - 1.0,
    }


def points_and_key(cell, seed: int):
    """The run's points (the traffic's ``data_seed`` points, rows in an
    order drawn from ``seed``) and the key its fits are keyed from."""
    import jax
    x = bench_data.make(cell.config["data"], bench_data.seed_key(
        int(cell.traffic["data_seed"])))["x"]
    k_perm, k_run = jax.random.split(bench_data.seed_key(seed))
    x = jax.block_until_ready(x[jax.random.permutation(k_perm, x.shape[0])])
    return x, k_run


class Driver:
    def __init__(self, cell, seed: int, devices):
        self.cell = cell
        self.seed = seed
        self.devices = devices
        self.kept = None                 # (index, outputs) of the drawn fit
        self.pick = np.random.default_rng([seed, 1])

    def setup(self):
        import jax
        from repro.api import SampledKMeans
        from repro.core.spec import ClusterSpec
        self.x, self.k_run = points_and_key(self.cell, self.seed)
        self.spec_dict = self.cell.config["fit"]["spec"]
        self.donate = bool(self.spec_dict["execution"].get("donate", False))
        self.est = SampledKMeans(ClusterSpec.from_dict(self.spec_dict))
        # warm every program the window runs, from a key the window never
        # uses
        self._fit(jax.random.fold_in(self.k_run, 1 << 30))

    def _fit(self, key):
        import jax
        import jax.numpy as jnp
        self.est.fit(jnp.copy(self.x) if self.donate else self.x, key=key)
        r = self.est.result_
        return jax.block_until_ready(
            {"centers": r.centers, "sse": r.sse, "pool": r.local_centers,
             "pool_w": r.local_weights})

    def step(self, i: int) -> int:
        import jax
        out = self._fit(jax.random.fold_in(self.k_run, i))
        if self.pick.random() * (i + 1) < 1.0:
            self.kept = (i, out)
        return int(self.x.shape[0])

    def layer_counts(self, window) -> dict:
        return {"fits": len(window.latencies)}

    def release(self):
        self.est = None

    def check(self, window) -> dict:
        import jax
        j, out = self.kept
        self.kept = None
        return fit_numbers(self.x, self.spec_dict,
                           jax.random.fold_in(self.k_run, j), out)


def control_readings(cell, seed: int, devices, steps: int = 0) -> dict:
    """The control in the program's place: the plain reference with its
    matmuls in three bf16 passes, for the key of the window's first fit
    (``steps`` is unused: a fit is one request)."""
    import jax
    x, k_run = points_and_key(cell, seed)
    spec = cell.config["fit"]["spec"]
    key = jax.random.fold_in(k_run, 0)
    out = reference.sampled_kmeans(x, spec, key, reference.THREE_PASS)
    return fit_numbers(x, spec, key, out)
