"""Closed-loop sharded out-of-core fits, one client: each request is
``SampledKMeans(spec, mesh=<the cell's chips on one "data" axis>)
.fit(ArraySource(<host array>), key)`` with a fresh key, blocked on until
its result is ready (``mode="chunked_dist"``: every fit feeds each chip its
share of the host rows, chunk by chunk).

The points live in host memory, as the deployment holds them: the
configuration's ``data`` section is made on the host's CPU from the
traffic's ``data_seed`` (the same for every run), mapped onto the
configuration's ``data_map`` range and rounded, and held as a float32 numpy
array; the run's seed draws the order of the rows and the fits' keys.

Set-up first fits a few thousand rows twice on the first chip, with the
cell's spec cut to that size, and refuses to go on (exit code 1) if the
second fit compiled anything: the cell measures a fit that compiles
nothing once warm.  Then
one fit at the cell's size warms every program the window runs.

One fit of the window is kept by reservoir sampling (fit ``i`` replaces
the kept one with probability ``1 / (i + 1)``).  After the window the
fold of every chunk is taken again from that fit's key through the
program's compiled fold (``_fold_scaled_chunk``, deterministic: the same
chunk and key give the same pool), and the plain reference
(``reference_dist.py``) replays the fit from the same key.  Compared,
stage by stage, so that the near-ties of one stage do not carry into the
next:

  mass_gap     |sum of the pool's member counts - points|  (exact)
  assign_miss  share of points whose fold partition's nearest pool row,
               by the reference, is not the one the fold counted them to
  fold_gap     share of points counted to other pool rows than in the
               reference's fold of the same chunk
  level_miss   share of the points whose fold row's nearest level row, by
               the reference, is not the one the level counted it to
  level_gap    share of the points counted to other level rows than in the
               reference's level of the program's own fold pools
  sse_err      |the fit's own SSE - the reference's pool SSE of its pool
               and centers|, over the latter
  fit_gap      the SSE over all points of the fit's centers over that of
               the reference replay's centers, minus 1
"""
from __future__ import annotations

import dataclasses

import numpy as np

import data as bench_data
import reference
import reference_dist


def host_points(cell, seed: int):
    """The run's points on the host: the ``data_seed`` mixture, made on the
    host's CPU, mapped onto ``data_map`` and rounded, rows in an order
    drawn from ``seed``; and the key the fits are keyed from."""
    import jax
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        x = bench_data.make(cell.config["data"], bench_data.seed_key(
            int(cell.traffic["data_seed"])))["x"]
        lo, hi = jax.numpy.min(x), jax.numpy.max(x)
        m = cell.config["data_map"]
        x = jax.numpy.round((x - lo) / (hi - lo) * (m["hi"] - m["lo"])
                            + m["lo"])
        k_perm, k_run = jax.random.split(bench_data.seed_key(seed))
        perm = np.asarray(jax.random.permutation(k_perm, x.shape[0]))
        x = np.asarray(x, np.float32)
    return x[perm], k_run


def fold_pools(x: np.ndarray, spec_dict: dict, devices, key) -> list:
    """Per shard, per chunk: the program's fold ``(scaled pool, weights)``
    of the fit keyed ``key``, taken again through the compiled fold on the
    chip that folded it."""
    import jax
    from repro.core.backend import get_backend
    from repro.core.pipeline import _fold_scaled_chunk
    from repro.core.spec import ClusterSpec
    spec = ClusterSpec.from_dict(spec_dict)
    be = get_backend(spec.execution.backend)
    base = spec.level_schedule()[0]
    lo, span = reference_dist.scale_params(x)
    key_local, _, _ = reference_dist.keys(key, len(devices))
    out = []
    for i, (a, b) in enumerate(reference_dist.shard_rows(x.shape[0],
                                                         len(devices))):
        dev = devices[i]
        lo_d, span_d = jax.device_put(lo, dev), jax.device_put(span, dev)
        pools = []
        for j, (s, e) in enumerate(reference_dist.chunk_rows(
                a, b, spec.chunk.chunk_points)):
            lv = (base if e - s >= base.n_sub
                  else dataclasses.replace(base, n_sub=max(1, e - s)))
            c, w, _, _ = _fold_scaled_chunk(
                jax.device_put(x[s:e], dev), lo_d, span_d,
                reference_dist.chunk_key(key_local, i, j), lv=lv, backend=be)
            pools.append((c, w))
        out.append(pools)
    return out


def fit_numbers(x: np.ndarray, spec: dict, n_dev: int, key, out: dict
                ) -> dict:
    """The compared numbers for one fit's output ``out`` (``centers``,
    ``sse``, ``pool``, ``pool_w`` in the input space, ``folds`` as
    ``fold_pools`` gives them) against the reference replay from ``key``."""
    import jax
    import jax.numpy as jnp
    rd = reference_dist
    if len(spec.get("levels", [])) != 1:
        raise ValueError("fit_dist_loop: the checks are written for one "
                         "reduce level")
    lv = spec["levels"][0]
    n = int(x.shape[0])
    ref = rd.sampled_kmeans_dist(x, spec, n_dev, key)
    lo, span = (jnp.asarray(a) for a in rd.scale_params(x))
    _, _, shard_keys = rd.keys(key, n_dev)
    miss = gap = lmiss = lgap = 0.0
    pool_w = np.asarray(out["pool_w"], np.float64)
    pool_s = (jnp.asarray(out["pool"]) - lo) / span
    rows = pool_s.shape[0] // n_dev
    shards = rd.shard_rows(n, n_dev)
    for i, (a, b) in enumerate(shards):
        chunks = rd.chunk_rows(a, b, spec["chunk"]["chunk_points"])
        for (s, e), (pc, pw), (_, rw) in zip(chunks, out["folds"][i],
                                             ref["folds"][i]):
            n_sub = min(spec["partition"]["n_sub"], e - s)
            k_local = max(1, -(-(e - s) // n_sub)
                          // spec["local"]["compression"])
            pc, pw = jax.device_get((pc, pw))
            counted = reference.fold_counts(
                (jnp.asarray(x[s:e]) - lo) / span, jnp.asarray(pc),
                n_sub=n_sub, k_local=k_local)
            pw = np.asarray(pw, np.float64)
            miss += np.abs(pw - np.asarray(counted, np.float64)).sum()
            gap += np.abs(pw - np.asarray(rw, np.float64)).sum()
        fin = jnp.concatenate([jnp.asarray(jax.device_get(c))
                               for c, _ in out["folds"][i]])
        fw = jnp.concatenate([jnp.asarray(jax.device_get(w))
                              for _, w in out["folds"][i]])
        w_i = pool_w[i * rows:(i + 1) * rows]
        counted = rd.level_counts(fin, fw, pool_s[i * rows:(i + 1) * rows],
                                  n_sub=lv["n_sub"],
                                  compression=lv["compression"])
        _, rlw = rd.level(fin, fw, jax.random.fold_in(shard_keys[i], 1),
                          n_sub=lv["n_sub"], compression=lv["compression"],
                          iters=lv["iters"])
        lmiss += np.abs(w_i - np.asarray(counted, np.float64)).sum()
        lgap += np.abs(w_i - np.asarray(rlw, np.float64)).sum()
    centers = jnp.asarray(out["centers"])
    s_pool = float(rd.pool_sse(jnp.asarray(out["pool"]),
                               jnp.asarray(pool_w, jnp.float32), centers))
    xd = jnp.asarray(x)
    s_fit = float(reference.min_sqdist_sum(xd, centers))
    s_ref = float(reference.min_sqdist_sum(xd, ref["centers"]))
    return {
        "mass_gap": abs(float(pool_w.sum()) - n),
        "assign_miss": miss / (2 * n),
        "fold_gap": gap / (2 * n),
        "level_miss": lmiss / (2 * n),
        "level_gap": lgap / (2 * n),
        "sse_err": abs(float(out["sse"]) - s_pool) / s_pool,
        "fit_gap": s_fit / s_ref - 1.0,
    }


def probe_spec(spec: dict, chunk_points: int) -> dict:
    """The cell's spec at a probe's size: chunks of ``chunk_points`` rows,
    a merge of 16 centers; everything else as configured."""
    out = {k: (dict(v) if isinstance(v, dict) else v)
           for k, v in spec.items()}
    out["chunk"]["chunk_points"] = chunk_points
    out["merge"]["k"] = 16
    return out


class Driver:
    def __init__(self, cell, seed: int, devices):
        self.cell = cell
        self.seed = seed
        self.devices = list(devices)
        self.kept = None                 # (index, outputs) of the drawn fit
        self.pick = np.random.default_rng([seed, 1])

    def _estimator(self, spec_dict, devices):
        """The estimator of ``spec_dict`` on a mesh of ``devices``."""
        import jax
        from repro.api import SampledKMeans
        from repro.core.spec import ClusterSpec
        mesh = jax.sharding.Mesh(np.asarray(devices),
                                 (spec_dict["execution"]["mesh_axis"],),
                                 axis_types=(jax.sharding.AxisType.Auto,))
        return SampledKMeans(ClusterSpec.from_dict(spec_dict), mesh=mesh)

    def setup(self):
        import jax
        from repro.data.source import ArraySource
        self.spec_dict = self.cell.config["fit"]["spec"]
        self._probe()
        self.x, self.k_run = host_points(self.cell, self.seed)
        self.src = ArraySource(self.x)
        self.est = self._estimator(self.spec_dict, self.devices)
        # warm every program the window runs, from a key the window never
        # uses
        self._fit(jax.random.fold_in(self.k_run, 1 << 30))

    def _probe(self):
        """Two fits of 2 chunks, 64 rows a partition, on the first chip
        alone (every chip compiles its own programs, so one is enough to
        see whether a warm fit compiles); the second must compile
        nothing."""
        import jax
        from repro.data.source import ArraySource
        n_sub = self.spec_dict["partition"]["n_sub"]
        cp = 64 * n_sub
        x = np.random.default_rng(0).normal(size=(
            2 * cp, self.cell.config["data"]["dim"])).astype(np.float32)
        est = self._estimator(probe_spec(self.spec_dict, cp),
                              self.devices[:1])
        est.fit(ArraySource(x), key=jax.random.PRNGKey(0))
        jax.block_until_ready(est.result_.centers)
        seen = []

        def listen(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                seen.append(secs)
        jax.monitoring.register_event_duration_secs_listener(listen)
        try:
            est.fit(ArraySource(x), key=jax.random.PRNGKey(1))
            jax.block_until_ready(est.result_.centers)
        finally:
            jax.monitoring.unregister_event_duration_listener(listen)
        if seen:
            raise RuntimeError(
                f"fit_dist_loop: a warm chunked_dist fit compiled "
                f"{len(seen)} program(s); this cell measures a fit that "
                f"compiles nothing once warm")

    def _fit(self, key):
        import jax
        self.est.fit(self.src, key=key)
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
        key = jax.random.fold_in(self.k_run, j)
        out = dict(out, folds=fold_pools(self.x, self.spec_dict,
                                         self.devices, key))
        return fit_numbers(self.x, self.spec_dict, len(self.devices), key,
                           out)


def control_readings(cell, seed: int, devices, steps: int = 0) -> dict:
    """The control in the program's place: the plain reference with its
    matmuls in three bf16 passes, for the key of the window's first fit
    (``steps`` is unused: a fit is one request)."""
    import jax
    x, k_run = host_points(cell, seed)
    spec = cell.config["fit"]["spec"]
    key = jax.random.fold_in(k_run, 0)
    out = reference_dist.sampled_kmeans_dist(x, spec, len(devices), key,
                                             reference.THREE_PASS)
    return fit_numbers(x, spec, len(devices), key, out)
