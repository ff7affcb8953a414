#!/usr/bin/env python3
"""Bring-up smoke: the sampled-clustering fit and the IVF search on a TPU.

Drives the main paths once through the entry points a user calls, checks
what comes out against the repo's own references, and prints as its last
line ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Timings, SSEs and recall go to earlier lines, labelled with the device, as
information about one run — not benchmark metrics.

  python chip_smoke.py              # one chip: phases (a), (b), (c)
  python chip_smoke.py --chips 4    # four chips: the mesh paths only

One chip:
  (a) the paper's headline job: ``workload_spec("synthetic_500k")``
      (500,000 x 2 blobs, k=1,000, n_sub=64, c=5) through
      ``SampledKMeans.fit`` on the default ``auto`` backend, against
      ``standard_kmeans`` on the same chip and against the ``jnp`` backend;
  (b) a resident fit at the BIGANN width: 1,048,576 x 128 f32 (512 MiB on
      the device), k=1,024, against the ``jnp`` backend;
  (c) the IVF index at the ``benchmarks/specs/index_200k.json`` shapes:
      ``build_index`` + ``index.search`` (Pallas ADC scan), recall@10
      against ``exact_search``.
Four chips (``--chips 4``):
  ``fit_chunked_dist`` over a 4,194,304 x 16 ``SyntheticSource`` against a
  one-device ``fit_chunked``, and the shard_map fit of phase (a) with both
  merge paths against the one-device fit.

Exits non-zero, with no result line, when JAX finds no TPU, when a kernel
would be interpreted, when ``REPRO_KMEANS_BACKEND`` or
``REPRO_SCAN_BACKEND`` is set, when the repo's ``src/`` is not next to this
file, or when any check fails.  Tiles come from the committed table only
(``REPRO_TUNE_CACHE`` is ignored).  Everything runs in this one process.
"""
import argparse
import json
import os
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
SRC = HERE / "src"

# (a) sampled vs full k-means SSE, (sampled - full) / full: the paper
# reports the sampled fit within a few percent of full k-means; the
# 100k-point analogue of this job (k=200, same n_sub and c) measured +6.1%
# and +7.1% on the CPU, so anything past +-10% is a fault, not the method
PAPER_REL_SSE = 0.10
# same fit, auto (Pallas) backend vs the jnp reference at full f32
# precision: both compute the same Lloyd iterations from the same key
BACKEND_REL_SSE = 0.01
# (c) recall@10 at nprobe=2 (the CPU build of this index reached 0.986)
RECALL_FLOOR = 0.9
# --chips 4: chunked_dist vs fit_chunked (tests/test_chunked_dist.py), and
# the shard_map fit vs the one-device fit of the same spec
CHUNKED_DIST_REL_SSE = 0.25
SHARD_MAP_REL_SSE = 0.10


class SmokeFailure(Exception):
    pass


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"  check ok: {what}", flush=True)


def rel(a: float, b: float) -> float:
    return (a - b) / b


class CompileClock:
    """Seconds spent in XLA compiles (or persistent-cache loads) and cache
    hits, from JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.hits = 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def exact_sse(x, centers):
    """SSE of ``x`` under ``centers`` at full f32 matmul precision (the
    TPU's default f32 matmul is a single bf16 pass)."""
    import jax
    from repro.core.metrics import sse
    with jax.default_matmul_precision("highest"):
        return float(sse(x, centers, block=65_536))


def timed(fn):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def fit_sampled(spec, x, key):
    from repro.api import SampledKMeans
    est = SampledKMeans(spec)
    _, wall = timed(lambda: est.fit(x, key=key).centers_)
    return est, wall


def phase_a(dev, key, seed, workload="synthetic_500k"):
    import jax
    import jax.numpy as jnp
    from repro.configs.paper_clustering import PAPER_WORKLOADS, workload_spec
    from repro.core import standard_kmeans
    from repro.data.synthetic import blobs

    n = PAPER_WORKLOADS[workload]["n"]
    spec = workload_spec(workload)
    print(f"[{dev}] (a) paper job {workload}: {n:,} x 2 blobs, "
          f"k={spec.merge.k}, n_sub={spec.partition.n_sub}, "
          f"c={spec.local.compression}", flush=True)
    pts, _, _ = blobs(n, dim=2, seed=seed)
    x = jnp.asarray(pts)
    est, wall = fit_sampled(spec, x, key)
    check(est.plan(x.shape).backend.name == "pallas_tuned",
          "(a) the fit planned the pallas_tuned backend")
    _, warm = timed(lambda: est.fit(x, key=key).centers_)
    sse_s = exact_sse(x, est.centers_)
    print(f"[{dev}] (a) sampled fit: {wall:.2f} s cold (compile incl.), "
          f"{warm:.2f} s warm; SSE {sse_s:.1f} (fit's own figure "
          f"{float(est.result_.sse):.1f})", flush=True)
    full, fwall = timed(lambda: standard_kmeans(x, spec.merge.k, key=key))
    sse_f = exact_sse(x, full.centers)
    r = rel(sse_s, sse_f)
    print(f"[{dev}] (a) standard k-means: {fwall:.2f} s; SSE {sse_f:.1f}; "
          f"sampled vs full {r:+.4f}", flush=True)
    check(abs(r) <= PAPER_REL_SSE,
          f"(a) sampled SSE within {PAPER_REL_SSE:.0%} of full k-means "
          f"({r:+.4f})")
    with jax.default_matmul_precision("highest"):
        ref, rwall = fit_sampled(spec.replace(backend="jnp"), x, key)
        sse_r = exact_sse(x, ref.centers_)
    r = rel(sse_s, sse_r)
    print(f"[{dev}] (a) jnp backend (highest precision): {rwall:.2f} s; "
          f"SSE {sse_r:.1f}; auto vs jnp {r:+.6f}", flush=True)
    check(abs(r) <= BACKEND_REL_SSE,
          f"(a) auto and jnp backends agree within {BACKEND_REL_SSE:.0%} "
          f"({r:+.6f})")


def embedding_points(key, n=1 << 20, dim=128, n_clusters=1024):
    """Gaussian blobs made on the device (512 MiB at the defaults)."""
    import jax
    import jax.numpy as jnp
    kc, ki, kn = jax.random.split(key, 3)
    centers = jax.random.uniform(kc, (n_clusters, dim), jnp.float32,
                                 0.0, 10.0)
    ids = jax.random.randint(ki, (n,), 0, n_clusters)
    return centers[ids] + 0.4 * jax.random.normal(kn, (n, dim), jnp.float32)


def phase_b(dev, key, n=1 << 20, dim=128, k=1024, n_sub=64,
            compression=32):
    import jax
    from repro.core.spec import ClusterSpec

    print(f"[{dev}] (b) resident fit at embedding width: {n:,} x {dim} "
          f"f32, k={k}, n_sub={n_sub}, c={compression}", flush=True)
    x, gen = timed(lambda: embedding_points(jax.random.fold_in(key, 1),
                                            n=n, dim=dim, n_clusters=k))
    spec = ClusterSpec.make(k, n_sub=n_sub, compression=compression)
    est, wall = fit_sampled(spec, x, key)
    check(est.plan(x.shape).backend.name == "pallas_tuned",
          "(b) the fit planned the pallas_tuned backend")
    _, warm = timed(lambda: est.fit(x, key=key).centers_)
    sse_s = exact_sse(x, est.centers_)
    print(f"[{dev}] (b) data made on the device in {gen:.2f} s; sampled "
          f"fit {wall:.2f} s cold, {warm:.2f} s warm; SSE {sse_s:.1f}",
          flush=True)
    with jax.default_matmul_precision("highest"):
        ref, rwall = fit_sampled(spec.replace(backend="jnp"), x, key)
        sse_r = exact_sse(x, ref.centers_)
    r = rel(sse_s, sse_r)
    print(f"[{dev}] (b) jnp backend (highest precision): {rwall:.2f} s; "
          f"SSE {sse_r:.1f}; auto vs jnp {r:+.6f}", flush=True)
    check(abs(r) <= BACKEND_REL_SSE,
          f"(b) auto and jnp backends agree within {BACKEND_REL_SSE:.0%} "
          f"({r:+.6f})")


def phase_c(dev, key, seed, spec_file="index_200k.json"):
    import jax
    import numpy as np
    from repro.data.source import SyntheticSource
    from repro.index import IndexSpec, build_index, exact_search, recall_at_k

    payload = json.loads((HERE / "benchmarks" / "specs" /
                          spec_file).read_text())
    spec = IndexSpec.from_dict(payload["index_spec"])
    w = payload["workload"]
    n, dim, n_clusters = int(w["n"]), int(w["dim"]), int(w["n_clusters"])
    k, q_block = int(w["k"]), int(w["q_block"])
    print(f"[{dev}] (c) IVF/PQ index: {n:,} x {dim}, nlist={spec.nlist}, "
          f"{spec.pq.n_subspaces} subspaces x {spec.pq.bits} bits, "
          f"nprobe={spec.nprobe}, {w['queries']} queries, k={k}",
          flush=True)
    src = SyntheticSource(n, dim=dim, n_clusters=n_clusters, seed=seed)
    rng = np.random.default_rng(seed + 1)
    queries = (src.centers[rng.integers(0, n_clusters, int(w["queries"]))]
               + rng.normal(0, float(w["query_noise"]),
                            (int(w["queries"]), dim))).astype(np.float32)
    (index, _), bwall = timed(lambda: build_index(src, spec, key))
    (_, ids), swall = timed(lambda: index.search(queries, k=k,
                                                 q_block=q_block))
    (_, ids), warm = timed(lambda: index.search(queries, k=k,
                                                q_block=q_block))
    with jax.default_matmul_precision("highest"):
        _, true_i = exact_search(src, queries, k=k,
                                 chunk_points=spec.coarse.chunk.chunk_points)
    recall = recall_at_k(ids, true_i)
    print(f"[{dev}] (c) build {bwall:.2f} s; search {swall:.3f} s cold, "
          f"{warm:.3f} s warm ({len(queries) / warm:.0f} queries/s, one "
          f"run); recall@{k} {recall:.4f}", flush=True)
    check(recall >= RECALL_FLOOR,
          f"(c) recall@{k} {recall:.4f} >= {RECALL_FLOOR}")


def peak_bytes(device) -> int:
    return int(device.memory_stats()["peak_bytes_in_use"])


def four_chips(dev, key, seed, chunk=262_144, workload="synthetic_500k",
               n_devices=4):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import compat
    from repro.configs.paper_clustering import workload_spec
    from repro.core import (ChunkSpec, ClusterSpec, ExecutionSpec, LevelSpec,
                            LocalSpec, MergeSpec, PartitionSpec, fit_chunked,
                            make_distributed_sampled_kmeans)
    from repro.core.distributed import fit_chunked_dist
    from repro.data.source import SyntheticSource
    from repro.data.synthetic import blobs

    devices = jax.devices()
    check(len(devices) == n_devices,
          f"{n_devices} devices visible ({len(devices)})")
    mesh = compat.make_mesh((n_devices,), ("data",))

    # chunked_dist: 16 chunks of 262,144 x 16, four per device
    spec = ClusterSpec(
        partition=PartitionSpec(n_sub=16),
        local=LocalSpec(compression=64, iters=4),
        merge=MergeSpec(k=256, iters=10, weighted=True),
        levels=(LevelSpec(n_sub=8, compression=8, iters=4),),
        chunk=ChunkSpec(chunk_points=chunk, sse="exact"),
        execution=ExecutionSpec(mode="chunked_dist",
                                merge_path="distributed"),
    )
    # many more blobs than centers: a smooth SSE landscape, so the two
    # executors' different pools land on comparable solutions
    src = SyntheticSource(16 * chunk, dim=16, n_clusters=8192, seed=seed)
    print(f"[{dev}] chunked_dist: {src.n_points:,} x 16 over a "
          f"{n_devices}-device mesh, k=256", flush=True)
    (res, st), wall = timed(lambda: fit_chunked_dist(src, spec, mesh, key))
    peaks = [peak_bytes(d) for d in devices]
    print(f"[{dev}] chunked_dist: {wall:.2f} s (compile incl.); points per "
          f"device {list(st.per_device_points)}; peak bytes per device "
          f"{peaks}", flush=True)
    check(st.n_devices == n_devices and min(st.per_device_points) > 0
          and sum(st.per_device_points) == src.n_points,
          "chunked_dist: every device folded points")
    chunk_bytes = chunk * 16 * 4
    check(all(p >= chunk_bytes for p in peaks),
          "chunked_dist: every device held at least one chunk")
    (ref, _), rwall = timed(lambda: fit_chunked(src, spec, key))
    r = rel(float(res.sse), float(ref.sse))
    print(f"[{dev}] fit_chunked on one device: {rwall:.2f} s; SSE "
          f"{float(ref.sse):.1f} vs chunked_dist {float(res.sse):.1f} "
          f"({r:+.4f})", flush=True)
    check(abs(r) < CHUNKED_DIST_REL_SSE,
          f"chunked_dist SSE within {CHUNKED_DIST_REL_SSE} of fit_chunked")

    # the shard_map fit of phase (a), both merge paths
    from repro.configs.paper_clustering import PAPER_WORKLOADS
    n = PAPER_WORKLOADS[workload]["n"]
    spec_a = workload_spec(workload)
    pts, _, _ = blobs(n, dim=2, seed=seed)
    x = jnp.asarray(pts)
    one, owall = fit_sampled(spec_a, x, key)
    sse_one = exact_sse(x, one.centers_)
    print(f"[{dev}] (a) one-device sampled fit: {owall:.2f} s; SSE "
          f"{sse_one:.1f}", flush=True)
    xd = jax.device_put(x, NamedSharding(mesh, P("data")))
    placed = sorted(s.device.id for s in xd.addressable_shards)
    rows = n // n_devices
    check(placed == sorted(d.id for d in devices)
          and all(s.data.shape == (rows, 2)
                  for s in xd.addressable_shards),
          f"shard_map input: one {rows:,}-row shard on each device")
    for merge in ("replicated", "distributed"):
        fn = make_distributed_sampled_kmeans(
            mesh, spec=spec_a.replace(
                n_sub=spec_a.partition.n_sub // n_devices), merge=merge)
        out, dwall = timed(lambda: fn(xd, key))
        sse_d = exact_sse(x, out.centers)
        r = rel(sse_d, sse_one)
        print(f"[{dev}] (a) shard_map x{n_devices} ({merge}): {dwall:.2f} s "
              f"(compile incl.); SSE {sse_d:.1f}; vs one device {r:+.4f}",
              flush=True)
        check(abs(r) <= SHARD_MAP_REL_SSE,
              f"shard_map ({merge}) SSE within {SHARD_MAP_REL_SSE:.0%} of "
              f"the one-device fit")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases (a)-(c) on one chip; 4: the mesh paths")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for every generated dataset and PRNG key")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repo sources at {SRC}", file=sys.stderr)
        return 2
    for var in ("REPRO_KMEANS_BACKEND", "REPRO_SCAN_BACKEND"):
        if os.environ.get(var):
            print(f"chip_smoke: {var} is set; the smoke checks the default "
                  f"backend resolution — unset it", file=sys.stderr)
            return 2
    os.environ.pop("REPRO_TUNE_CACHE", None)   # committed tiles only
    sys.path.insert(0, str(SRC))

    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache(HERE)
    import jax
    clock = CompileClock()
    from repro.core.backend import get_backend
    from repro.kernels import default_interpret, resolve_scan_backend

    devices = jax.devices()
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices)}
    if d0.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {d0.platform}: "
              f"{d0.device_kind})", file=sys.stderr)
        return 1
    label = f"{d0.device_kind} x{len(devices)}"
    print(f"[{label}] compile cache: {cache_dir}", flush=True)
    check(not default_interpret(), "Pallas kernels compile (no interpret)")
    check(get_backend("auto").name == "pallas_tuned",
          "auto resolves to pallas_tuned")
    check(resolve_scan_backend() == "pallas", "the ADC scan is Pallas")

    key = jax.random.PRNGKey(args.seed)
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(label, key, args.seed)
    else:
        phase_a(label, key, args.seed)
        phase_b(label, key)
        phase_c(label, key, args.seed)
    check("repro.launch.dryrun" not in sys.modules,
          "nothing imported the XLA_FLAGS-rewriting dryrun module")
    print(f"[{label}] total {time.perf_counter() - t0:.1f} s; compile "
          f"{clock.seconds:.1f} s; persistent-cache hits {clock.hits}",
          flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
