"""Benchmark harness — one module per paper table + framework benches.

  PYTHONPATH=src python -m benchmarks.run [--only table1,table2,...]
  PYTHONPATH=src python -m benchmarks.run --spec benchmarks/specs/paper_500k.json

Prints ``name,us_per_call,derived`` CSV lines.  Roofline numbers come from
the dry-run artifacts (benchmarks/artifacts/dryrun/) via
``python -m benchmarks.roofline_report``.

``--spec FILE`` runs one clustering benchmark from a *serialized spec*: the
JSON holds a ``cluster_spec`` section (``ClusterSpec.to_dict()`` output —
the single source of truth for every stage option) plus a ``workload``
section sizing the synthetic data (``n``, ``dim``, optional ``seed``,
``repeats``).  Benchmark configs are therefore the same artifact the
library executes — no kwarg re-spelling between config and run.
"""
import argparse
import json
import pathlib
import sys
import time

ARTIFACTS = pathlib.Path(__file__).resolve().parent / "artifacts"


def _peak_rss_mb() -> float:
    """Process high-water-mark resident set, MB (delegates to the telemetry
    helper so every artifact reports the same number the run loggers emit)."""
    from repro.telemetry import peak_rss_mb
    return peak_rss_mb()


def run_spec_file(path: str, csv) -> None:
    import jax
    import jax.numpy as jnp

    from repro.api import SampledKMeans
    from repro.core.spec import ClusterSpec
    from repro.data.source import SyntheticSource
    from repro.data.synthetic import blobs

    payload = json.loads(open(path).read())
    spec = ClusterSpec.from_dict(payload["cluster_spec"])
    w = payload.get("workload", {})
    n, dim = int(w.get("n", 100_000)), int(w.get("dim", 2))
    seed, repeats = int(w.get("seed", 0)), int(w.get("repeats", 3))

    chunked = spec.execution.mode == "chunked"
    if chunked:
        # out-of-core workloads never materialize: the source generates
        # each chunk on demand, so the peak-RSS field below actually
        # demonstrates the memory ceiling
        x = SyntheticSource(n, dim=dim, n_clusters=spec.merge.k, seed=seed)
        mode = "chunked"
    else:
        pts, _, _ = blobs(n, n_clusters=spec.merge.k, dim=dim, seed=seed)
        x = jnp.asarray(pts)
        mode = None
    est = SampledKMeans(spec)
    key = jax.random.PRNGKey(seed)
    est.fit(x, key=key)                      # compile + warm
    jax.block_until_ready(est.sse_)
    if mode is None:
        mode = est.plan(tuple(x.shape)).mode
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        est.fit(x, key=key)
        jax.block_until_ready(est.sse_)
        times.append(time.perf_counter() - t0)
    name = payload.get("name", pathlib.Path(path).stem)
    points_per_sec = n / min(times)
    csv(f"spec/{name}", min(times) * 1e6,
        f"sse={float(est.sse_):.2f};n={n};k={spec.merge.k};"
        f"levels={spec.n_levels};mode={mode};"
        f"pps={points_per_sec:.0f};rss_mb={_peak_rss_mb():.0f}")
    # drop a JSON artifact next to the perf records so CI's benchmark
    # upload captures serialized-spec runs too (chunked runs get their own
    # BENCH_chunked_* prefix so the out-of-core perf trajectory is greppable)
    ARTIFACTS.mkdir(parents=True, exist_ok=True)
    from repro.core.backend import get_backend
    from repro.telemetry import calibrate
    record = {
        "schema": 1,
        "bench": "spec_file",
        "name": name,
        "spec_file": str(path),
        "spec_hash": spec.stable_hash(),
        "mode": mode,
        "backend": get_backend(spec.execution.backend).name,
        "calib_mflops": calibrate(),
        "workload": {"n": n, "dim": dim, "seed": seed, "repeats": repeats},
        "pool_schedule": list(spec.chunked_pool_schedule(n) if chunked
                              else spec.pool_schedule(n)),
        "us_best": min(times) * 1e6,
        "points_per_sec": points_per_sec,
        "peak_rss_mb": _peak_rss_mb(),
        "sse": float(est.sse_),
    }
    if est.chunk_stats_ is not None:
        record["chunk_stats"] = est.chunk_stats_._asdict()
    prefix = "" if name.startswith("chunked") else "spec_"
    (ARTIFACTS / f"BENCH_{prefix}{name}.json").write_text(
        json.dumps(record, indent=1))


def _csv(name, us, derived):
    print(f"{name},{us:.1f},{derived}", flush=True)


MODULES = [
    ("table1_accuracy", "benchmarks.bench_accuracy"),
    ("table2_scaling", "benchmarks.bench_scaling"),
    ("table3_compression", "benchmarks.bench_compression"),
    ("cluster_attn", "benchmarks.bench_cluster_attn"),
    ("stream", "benchmarks.bench_stream"),
    ("kernels", "benchmarks.bench_kernels"),
    ("grad_compress", "benchmarks.bench_grad_compress"),
]


def main() -> None:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list of bench keys to run")
    ap.add_argument("--spec", default=None, metavar="FILE",
                    help="run one clustering bench from a serialized "
                         "ClusterSpec JSON (see benchmarks/specs/)")
    args = ap.parse_args()
    if args.spec:
        run_spec_file(args.spec, _csv)
        return
    only = set(args.only.split(",")) if args.only else None

    import importlib
    t00 = time.time()
    for key, modname in MODULES:
        if only and key not in only and modname.split(".")[-1] not in only:
            continue
        t0 = time.time()
        print(f"# === {key} ({modname}) ===", flush=True)
        mod = importlib.import_module(modname)
        try:
            mod.run(_csv)
        except Exception as e:  # keep the harness going; report the failure
            _csv(f"{key}/ERROR", 0.0, repr(e)[:120])
        print(f"# {key} done in {time.time() - t0:.1f}s", flush=True)
    print(f"# total {time.time() - t00:.1f}s", flush=True)


if __name__ == '__main__':
    main()
