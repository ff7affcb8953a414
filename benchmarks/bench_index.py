"""IVF/PQ index benchmark: recall@k and queries/sec vs the brute-force
exact-scan baseline, from a serialized :class:`repro.index.IndexSpec`.

  PYTHONPATH=src python -m benchmarks.bench_index \\
      --spec benchmarks/specs/index_smoke.json

The spec JSON holds an ``index_spec`` section (``IndexSpec.to_dict()``
output — the same artifact the library executes, like ``run.py --spec``)
plus a ``workload`` section sizing the synthetic corpus and the query
sweep::

  {
    "name": "index_smoke",
    "index_spec": { ... IndexSpec.to_dict() ... },
    "workload": {
      "n": 200000, "dim": 64, "n_clusters": 256, "seed": 7,
      "queries": 256, "query_noise": 0.4, "k": 10, "repeats": 3,
      "nprobes": [1, 2, 4, 8], "q_block": 64,
      "source": "synthetic"          # synthetic | iter
    }
  }

``source: "synthetic"`` streams a :class:`~repro.data.source.SyntheticSource`
(chunk-addressable, nothing resident); ``"iter"`` wraps the same generator
in an opaque :class:`~repro.data.source.IterSource` factory — the nightly
5M-point build goes through that path to prove the index never needs the
corpus in memory.  Ground truth comes from the streaming
:func:`repro.index.exact_search` fold (the ``min_sqdist``-style baseline);
the brute-force qps number scans the resident corpus when it fits the
residency budget, else the same streaming fold.

The artifact (``BENCH_<name>.json``, ``bench: "index"``) carries the full
nprobe sweep plus headline ``recall_at_10`` / ``qps`` measured at the
spec's own ``nprobe`` — the pair the CI gate compares against the
committed baseline (see ``benchmarks/gate.py``).
"""
import argparse
import json
import pathlib
import time

ARTIFACTS = pathlib.Path(__file__).resolve().parent / "artifacts"

# corpora below this many resident bytes time the brute-force baseline on
# a device array; larger ones fall back to the streaming fold
RESIDENT_BUDGET_BYTES = 2_000_000_000


def run_spec_file(path: str, csv) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.backend import get_backend
    from repro.data.source import IterSource, SyntheticSource
    from repro.index import (IndexSpec, build_index, exact_search,
                             recall_at_k)
    from repro.kernels.scan import resolve_scan_backend
    from repro.telemetry import calibrate, peak_rss_mb

    payload = json.loads(open(path).read())
    ispec = IndexSpec.from_dict(payload["index_spec"])
    w = payload.get("workload", {})
    n, dim = int(w.get("n", 100_000)), int(w.get("dim", 64))
    n_clusters = int(w.get("n_clusters", ispec.nlist))
    seed = int(w.get("seed", 0))
    n_queries = int(w.get("queries", 256))
    query_noise = float(w.get("query_noise", 0.4))
    k = int(w.get("k", 10))
    repeats = int(w.get("repeats", 3))
    nprobes = [int(p) for p in w.get("nprobes", [ispec.nprobe])]
    q_block = int(w.get("q_block", 64))
    source_kind = w.get("source", "synthetic")
    name = payload.get("name", pathlib.Path(path).stem)

    chunk_points = ispec.coarse.chunk.chunk_points
    synth = SyntheticSource(n, dim=dim, n_clusters=n_clusters, seed=seed)
    if source_kind == "iter":
        src = IterSource(lambda: synth.chunks(chunk_points),
                         dim=dim, n_points=n)
        mode = "chunked_iter"
    elif source_kind == "synthetic":
        src = synth
        mode = "chunked"
    else:
        raise ValueError(f"unknown workload source {source_kind!r}")

    rng = np.random.default_rng(seed + 1)
    queries = (synth.centers[rng.integers(0, n_clusters, n_queries)]
               + rng.normal(0, query_noise, (n_queries, dim))
               ).astype(np.float32)

    t0 = time.perf_counter()
    index, stats = build_index(src, ispec, jax.random.PRNGKey(seed))
    jax.block_until_ready(index.codes)
    build_s = time.perf_counter() - t0

    # ground truth + brute-force baseline
    true_d, true_i = exact_search(src, queries, k=k,
                                  chunk_points=chunk_points)
    if n * dim * 4 <= RESIDENT_BUDGET_BYTES:
        corpus = jnp.asarray(np.concatenate(list(src.chunks(chunk_points))))
        brute_mode = "resident"
    else:
        corpus = src
        brute_mode = "streaming"
    exact_search(corpus, queries, k=k)                       # warm
    brute_times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        _, bi = exact_search(corpus, queries, k=k)
        jax.block_until_ready(bi)
        brute_times.append(time.perf_counter() - t0)
    brute_qps = n_queries / min(brute_times)
    del corpus

    sweep = []
    for nprobe in nprobes:
        index.search(queries, k=k, nprobe=nprobe, q_block=q_block)  # warm
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            _, ids = index.search(queries, k=k, nprobe=nprobe,
                                  q_block=q_block)
            jax.block_until_ready(ids)
            times.append(time.perf_counter() - t0)
        point = {"nprobe": nprobe,
                 "recall": recall_at_k(ids, true_i),
                 "qps": n_queries / min(times)}
        sweep.append(point)
        csv(f"index/{name}/nprobe{nprobe}", min(times) * 1e6,
            f"recall@{k}={point['recall']:.4f};qps={point['qps']:.0f};"
            f"brute_qps={brute_qps:.0f}")

    headline = next((p for p in sweep if p["nprobe"] == ispec.nprobe),
                    sweep[-1])
    record = {
        "schema": 1,
        "bench": "index",
        "name": name,
        "spec_file": str(path),
        "spec_hash": ispec.stable_hash(),
        "mode": mode,
        "backend": get_backend(ispec.coarse.execution.backend).name,
        "scan_backend": resolve_scan_backend(None),
        "calib_mflops": calibrate(),
        "workload": {"n": n, "dim": dim, "n_clusters": n_clusters,
                     "seed": seed, "queries": n_queries, "k": k,
                     "repeats": repeats, "q_block": q_block,
                     "source": source_kind},
        "build_s": build_s,
        "build_points_per_sec": n / build_s,
        "build_stats": stats._asdict(),
        "brute_mode": brute_mode,
        "brute_qps": brute_qps,
        "sweep": sweep,
        f"recall_at_{k}": headline["recall"],
        "qps": headline["qps"],
        "qps_speedup": headline["qps"] / brute_qps,
        "peak_rss_mb": peak_rss_mb(),
    }
    ARTIFACTS.mkdir(parents=True, exist_ok=True)
    (ARTIFACTS / f"BENCH_{name}.json").write_text(json.dumps(record,
                                                             indent=1))
    csv(f"index/{name}", build_s * 1e6,
        f"build_pps={n / build_s:.0f};recall@{k}={headline['recall']:.4f};"
        f"qps={headline['qps']:.0f};speedup={headline['qps'] / brute_qps:.2f};"
        f"rss_mb={peak_rss_mb():.0f}")
    return record


def _csv(name, us, derived):
    print(f"{name},{us:.1f},{derived}", flush=True)


def main(argv=None) -> int:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--spec", required=True, metavar="FILE",
                    help="serialized IndexSpec benchmark JSON "
                         "(see benchmarks/specs/index_*.json)")
    args = ap.parse_args(argv)
    run_spec_file(args.spec, _csv)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
