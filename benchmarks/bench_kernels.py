"""Kernel micro-benchmarks + the tile-autotune sweep campaign.

On this CPU container the Pallas kernels execute in interpret mode (purely
a correctness vehicle), so wall-times compare the *jnp fallback paths* the
CPU uses; the TPU kernels are exercised for shape coverage + allclose.

The sweep half drives :mod:`repro.kernels.autotune` over an (M, d, K) grid
and drops one ``BENCH_tune_<kernel>_<shape>.json`` artifact per swept
point (``bench: "tune"`` — ingested by ``benchmarks/trajectory.py``,
gated by ``benchmarks/gate.py``):

  PYTHONPATH=src python -m benchmarks.bench_kernels --sweep           # full grid
  PYTHONPATH=src python -m benchmarks.bench_kernels --sweep --smoke   # CI: 1 shape, 2 configs
  PYTHONPATH=src python -m benchmarks.bench_kernels --check-defaults  # table loads?

Run the same sweep on a real device through ``benchmarks/run_device.sh``
(tcmalloc + XLA env recipe); point ``REPRO_TUNE_CACHE`` at a JSON path to
persist the winners across processes.
"""
import argparse
import json
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kmeans import assign_jnp, update_centers
from repro.kernels import assign_argmin, centroid_update, lloyd_step
from repro.kernels.ref import lloyd_step_ref

ARTIFACTS = pathlib.Path(__file__).resolve().parent / "artifacts"

# the full campaign grid (requested shapes; interpret mode shrinks them)
SWEEP_GRID = [
    (262_144, 64, 256),
    (1_048_576, 128, 512),
    (65_536, 8, 64),
]
# the CI smoke: one tiny shape, exactly two (distinct effective) configs
SMOKE_SHAPE = (2048, 16, 16)
SMOKE_CANDIDATES = ({"block_m": 256, "block_k": 256},
                    {"block_m": 128, "block_k": 128})


def _bench(fn, *args, iters=5):
    fn(*args)  # compile
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def run(csv):
    rng = np.random.default_rng(0)
    for (m, d, k) in [(100_000, 2, 200), (50_000, 64, 512)]:
        x = jnp.asarray(rng.normal(size=(m, d)), jnp.float32)
        c = jnp.asarray(rng.normal(size=(k, d)), jnp.float32)
        t = _bench(jax.jit(assign_jnp), x, c)
        gflops = 2 * m * k * d / t / 1e9
        csv(f"kernel/assign_jnp/{m}x{d}x{k}", t * 1e6, f"{gflops:.1f}GFLOP/s")
        idx, _ = assign_jnp(x, c)
        w = jnp.ones((m,), jnp.float32)
        t = _bench(jax.jit(lambda xx, ii, ww: update_centers(
            xx, ww, ii, k, jnp.zeros((k, d)))), x, idx, w)
        csv(f"kernel/centroid_jnp/{m}x{d}x{k}", t * 1e6,
            f"{m * k * (d + 1) * 2 / t / 1e9:.1f}GFLOP/s")
    # pallas interpret correctness spot check at bench shapes
    x = jnp.asarray(rng.normal(size=(4096, 64)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(256, 64)), jnp.float32)
    i1, d1 = assign_argmin(x, c)
    i2, d2 = assign_jnp(x, c)
    ok = bool(jnp.mean((i1 == i2).astype(jnp.float32)) > 0.99)
    csv("kernel/assign_pallas_interpret_allclose", 0.0, f"match={ok}")
    # fused Lloyd step vs the two-pass oracle at the same shape
    w = jnp.ones((x.shape[0],), jnp.float32)
    sums, counts, sse, fi, _ = lloyd_step(x, w, c)
    rsums, rcounts, rsse, _, _ = lloyd_step_ref(x, w, c)
    ok = bool(jnp.allclose(sums, rsums, rtol=1e-3, atol=1e-3)
              and jnp.allclose(counts, rcounts)
              and jnp.allclose(sse, rsse, rtol=1e-3))
    csv("kernel/lloyd_fused_interpret_allclose", 0.0, f"match={ok}")
    return []


# ---------------------------------------------------------------------------
# The autotune sweep campaign
# ---------------------------------------------------------------------------

def _shrink(m, d, k, interpret):
    """Interpret mode is a correctness vehicle: shrink the measured shape
    (and record both) so the sweep finishes in CI time."""
    return (min(m, 4096), d, min(k, 64)) if interpret else (m, d, k)


def sweep_point(kernel, m, d, k, *, candidates=None, iters=3, warmup=1,
                save=True, out_dir=ARTIFACTS):
    """Tune one (kernel, M, d, K) point and drop its BENCH_tune artifact.

    The winner's throughput vs the hardcoded default config is asserted
    >= 1.0x — the default is always a swept candidate, so a violation
    means the harness itself is broken, not the kernel.
    """
    from repro.kernels import autotune, default_interpret
    from repro.roofline.analysis import predicted_vs_measured
    from repro.telemetry.logger import calibrate

    interpret = default_interpret()
    tm, td, tk = _shrink(m, d, k, interpret)
    cands = None
    if candidates is not None:
        cands = [autotune.TileConfig.from_dict(c) for c in candidates]
    res = autotune.tune(kernel, m=tm, d=td, k=tk, candidates=cands,
                        iters=iters, warmup=warmup, save=save)
    device_kind, backend = autotune.device_info()
    entry = {
        "bench": "tune",
        "kernel": kernel,
        "mode": "interpret" if interpret else "compiled",
        "requested": {"m": m, "d": d, "k": k},
        "measured": {"m": tm, "d": td, "k": tk},
        "dtype": "float32",
        "device_kind": device_kind,
        "backend": backend,
        "key": res.key,
        "config": res.config.to_dict(),
        "best_us": res.best_time_s * 1e6,
        "default_us": res.default_time_s * 1e6,
        "speedup_vs_default": res.speedup_vs_default,
        "numerics_verified": True,   # tune() rejects before timing otherwise
        "n_candidates": len(res.candidates),
        "n_rejected": sum(1 for c in res.candidates if not c.ok),
        "candidates": [
            {"config": c.config.to_dict(),
             "us": None if c.time_s is None else c.time_s * 1e6,
             "ok": c.ok, "note": c.note}
            for c in res.candidates],
        # interpreted timings have no roof to be measured against
        "roofline": None if interpret else predicted_vs_measured(
            kernel, res.best_time_s, device_kind=device_kind,
            block_m=res.config.block_m or 256, m=tm, d=td, k=tk),
        "calib_mflops": calibrate(),
    }
    out_dir = pathlib.Path(out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    out = out_dir / f"BENCH_tune_{kernel}_M{m}_d{d}_K{k}.json"
    out.write_text(json.dumps(entry, indent=1))
    entry["json"] = str(out)
    assert entry["speedup_vs_default"] >= 1.0, (
        f"tune({kernel}) winner {entry['config']} is "
        f"{entry['speedup_vs_default']:.3f}x the default — the default "
        f"config must be in the sweep, so this is a harness bug")
    return entry


def run_sweep(*, kernel="lloyd", grid=None, smoke=False, iters=3, warmup=1,
              save=True, out_dir=ARTIFACTS):
    """The campaign entry: the full (M, d, K) grid, or the 2-config CI
    smoke (``smoke=True``)."""
    if smoke:
        shapes = [SMOKE_SHAPE]
        candidates = SMOKE_CANDIDATES
        iters = min(iters, 2)
    else:
        shapes = grid or SWEEP_GRID
        candidates = None
    entries = []
    for (m, d, k) in shapes:
        e = sweep_point(kernel, m, d, k, candidates=candidates,
                        iters=iters, warmup=warmup, save=save,
                        out_dir=out_dir)
        print(f"# {kernel} M{m}_d{d}_K{k} [{e['mode']}]: "
              f"{e['config']} {e['best_us']:.0f}us "
              f"({e['speedup_vs_default']:.2f}x default, "
              f"{e['n_rejected']} rejected) -> {e['json']}")
        entries.append(e)
    return entries


def check_defaults():
    """CI hook: the committed fallback table parses, and a lookup with an
    empty cache resolves through it (or the hardcoded default) for every
    kernel."""
    from repro.kernels import autotune, tune_table
    n = tune_table.validate_table()
    autotune.clear_caches()
    probes = {"lloyd": dict(m=4096, d=64, k=64),
              "assign": dict(m=4096, d=64, k=64),
              "centroid": dict(m=4096, d=64, k=64),
              "scan": dict(b=8, l=1024, msub=8, c=16)}
    for kernel, dims in probes.items():
        cfg, source = autotune.lookup(kernel, with_source=True,
                                      path=None, **dims)
        assert any(cfg), f"{kernel}: all-zero config from {source}"
        assert source in ("table", "default"), (
            f"{kernel}: cold lookup resolved from {source!r}, expected the "
            f"committed table or the hardcoded default")
        print(f"# {kernel}: {cfg.to_dict()} from {source}")
    print(f"# tune_table OK ({n} entries)")
    return n


def main(argv=None) -> int:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--sweep", action="store_true",
                    help="run the autotune sweep campaign")
    ap.add_argument("--smoke", action="store_true",
                    help="with --sweep: 1 tiny shape, 2 configs (CI)")
    ap.add_argument("--check-defaults", action="store_true",
                    help="validate the committed tune_table and exit")
    ap.add_argument("--kernel", default="lloyd",
                    choices=("lloyd", "assign", "centroid"),
                    help="which kernel the (M, d, K) sweep drives")
    ap.add_argument("--shapes", default=None,
                    help="override grid: 'M,d,K;M,d,K;...'")
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--warmup", type=int, default=1)
    ap.add_argument("--no-save", action="store_true",
                    help="do not write winners to REPRO_TUNE_CACHE")
    ap.add_argument("--out-dir", default=str(ARTIFACTS))
    args = ap.parse_args(argv)

    if args.check_defaults:
        check_defaults()
        return 0
    if args.sweep:
        grid = None
        if args.shapes:
            grid = [tuple(int(v) for v in s.split(","))
                    for s in args.shapes.split(";") if s]
        run_sweep(kernel=args.kernel, grid=grid, smoke=args.smoke,
                  iters=args.iters, warmup=args.warmup,
                  save=not args.no_save, out_dir=args.out_dir)
        return 0
    run(lambda n, us, d: print(f"{n},{us:.1f},{d}"))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
