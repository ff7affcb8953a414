"""§Perf hillclimbing harness: rerun one (arch x shape) cell's roofline
parts under a named variant, record hypothesis -> change -> before/after.

  PYTHONPATH=src python -m benchmarks.perf_iter --arch llama3-8b \
      --shape train_4k --variant bf16_grads

Variants are registered below; each is a (description, builder-kwargs /
monkeypatch) pair.  Results append to benchmarks/artifacts/perf/<cell>.json.

The ``--lloyd`` mode benchmarks one Lloyd iteration through every
``LloydBackend`` (jnp vs unfused pallas vs fused pallas) instead:

  PYTHONPATH=src python -m benchmarks.perf_iter --lloyd \
      --m 262144 --d 64 --k 256

On a compiled backend (TPU) it times per-iteration cost and asserts the
fused kernel beats the unfused one; under the Pallas interpreter (CPU CI)
it asserts numerics only.  Either way the figures land in
``benchmarks/artifacts/BENCH_lloyd_M{m}_d{d}_K{k}.json``.
"""
import os
import sys

if ("--lloyd" not in sys.argv and "--api" not in sys.argv
        and "--levels" not in sys.argv and "--stop" not in sys.argv):
    # the roofline cells pretend to be a 512-chip pod; the Lloyd bench wants
    # the real device so its timings mean something
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=512")
import argparse
import dataclasses
import json
import pathlib
import time

import jax

from repro import compat
from repro.configs import SHAPES, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch import dryrun as dr
from repro.roofline.analysis import (PartCost, cost_of_compiled, model_flops,
                                     roofline_terms)

PERF = pathlib.Path(__file__).resolve().parent / "artifacts" / "perf"


def measure_train(cfg, shape, mesh, *, n_micro=None, act_model=False,
                  grad_dtype=None, q_chunk=None, remat=None, act_seq=False):
    """A/B-differenced roofline terms for a train cell under overrides."""
    import repro.train.step as step_mod
    model = dr.build_model(cfg)
    layers_per_step = model.groups[0].layers_per_step
    n_super = cfg.n_layers // layers_per_step
    plan = step_mod.default_plan(cfg, shape, dr._dp_size(mesh))
    overrides = {}
    if grad_dtype:
        overrides["grad_dtype"] = grad_dtype
    if q_chunk:
        overrides["q_chunk"] = q_chunk
    if remat is not None:
        overrides["remat"] = remat
    if overrides:
        plan = dataclasses.replace(plan, **overrides)
    nm = n_micro or plan.n_micro

    orig_default = step_mod.default_plan

    def patched(cfg_, shape_, dp):
        p = orig_default(cfg_, shape_, dp)
        return dataclasses.replace(p, **overrides) if overrides else p

    step_mod.default_plan = patched
    dr.default_plan = patched
    try:
        micro_shape = dataclasses.replace(
            shape, global_batch=max(shape.global_batch // nm,
                                    dr._dp_size(mesh)))
        cfg_a = dr._variant(cfg, 1, layers_per_step)
        cfg_b = dr._variant(cfg, 2, layers_per_step)
        if act_seq:
            # sequence-parallel residual stream: patch the act spec builder
            from jax.sharding import PartitionSpec as P
            orig_btp = dr.build_train_program

            def build_sp(cfg_, shape_, mesh_, **kw):
                kw.pop("act_model", None)
                fn, args, plan = orig_btp(cfg_, shape_, mesh_, **kw,
                                          act_model=False)
                return fn, args, plan
            # monkeypatch act spec inside the builder via step module
            import repro.train.step as _sm
            orig_mlf = _sm.make_loss_fn

            def mlf(model, cfg_, shape_, plan, act_spec, unroll=False):
                return orig_mlf(model, cfg_, shape_, plan,
                                P("data", "model", None), unroll=unroll)
            _sm.make_loss_fn = mlf
            dr.make_loss_fn = mlf
        with compat.set_mesh(mesh):
            fa, aa, _ = dr.build_train_program(
                cfg_a, micro_shape, mesh, n_micro=1, grad_only=True,
                unroll=True, act_model=act_model)
            ca, _ = dr.lower_compile(fa, aa)
            A = cost_of_compiled(ca)
            del ca, fa
            fb, ab, _ = dr.build_train_program(
                cfg_b, micro_shape, mesh, n_micro=1, grad_only=True,
                unroll=True, act_model=act_model)
            cb, _ = dr.lower_compile(fb, ab)
            B = cost_of_compiled(cb)
            del cb, fb
            fo, ao = dr.build_opt_program(cfg, shape, mesh)
            co, _ = dr.lower_compile(fo, ao)
            OPT = cost_of_compiled(co)
            del co, fo
    finally:
        step_mod.default_plan = orig_default
        dr.default_plan = orig_default
    blk = B - A
    stem = A - blk
    total = (stem + blk.scaled(n_super)).scaled(nm) + OPT
    return total


def measure_decode(cfg, shape, mesh, *, window=None, compression=None,
                   full_cache=False):
    sh = shape
    if window or compression:
        sh = dataclasses.replace(
            shape,
            cluster_window=window or shape.cluster_window,
            cluster_compression=compression or shape.cluster_compression)
    if full_cache:
        # comparison point: what the paper's clustered-KV replaces
        sh = dataclasses.replace(sh, cluster_compression=0)
    model = dr.build_model(cfg)
    layers_per_step = model.groups[0].layers_per_step
    n_super = cfg.n_layers // layers_per_step
    cfg_a = dr._variant(cfg, 1, layers_per_step)
    cfg_b = dr._variant(cfg, 2, layers_per_step)
    with compat.set_mesh(mesh):
        fa, aa = dr.build_decode_program(cfg_a, sh, mesh, unroll=True)[:2]
        ca, _ = dr.lower_compile(fa, aa)
        A = cost_of_compiled(ca)
        del ca, fa
        fb, ab = dr.build_decode_program(cfg_b, sh, mesh, unroll=True)[:2]
        cb, _ = dr.lower_compile(fb, ab)
        B = cost_of_compiled(cb)
        del cb, fb
    blk = B - A
    stem = A - blk
    return stem + blk.scaled(n_super)


def record(arch, shape_name, variant, hypothesis, total: PartCost):
    PERF.mkdir(parents=True, exist_ok=True)
    f = PERF / f"{arch}__{shape_name}.json"
    hist = json.loads(f.read_text()) if f.exists() else []
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_chips = 256
    terms = roofline_terms(total)
    mf = model_flops(cfg, shape, shape.kind) / mesh_chips
    dom = max(terms, key=terms.get)
    entry = {
        "variant": variant,
        "hypothesis": hypothesis,
        "terms": terms,
        "dominant": dom,
        "useful_flop_ratio": mf / max(total.flops, 1.0),
        "roofline_fraction": (mf / 197e12) / max(terms[dom], 1e-30),
        "coll_by_op": total.coll_by_op,
    }
    hist.append(entry)
    f.write_text(json.dumps(hist, indent=1))
    return entry


def run_lloyd_bench(m: int, d: int, k: int, *, timing_iters: int = 5,
                    assert_speedup: float | None = None) -> dict:
    """Per-Lloyd-iteration cost of every registered backend on one shape.

    Numerics are always cross-checked against the jnp oracle.  Timing is
    only meaningful with compiled kernels — under the interpreter the
    check shrinks the shape and records the mode so nobody mistakes
    interpreter overhead for a kernel regression.
    """
    import jax.numpy as jnp
    import numpy as np

    from repro.core.backend import get_backend
    from repro.kernels import default_interpret
    from repro.kernels.ref import lloyd_step_ref

    interpret = default_interpret()
    tm, td, tk = (min(m, 2048), d, min(k, 64)) if interpret else (m, d, k)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(tm, td)), jnp.float32)
    w = jnp.ones((tm,), jnp.float32)
    c = jnp.asarray(rng.normal(size=(tk, td)), jnp.float32)

    ref = lloyd_step_ref(x, w, c)
    entry = {
        "bench": "lloyd_step", "mode": "interpret" if interpret else "compiled",
        "requested": {"m": m, "d": d, "k": k},
        "measured": {"m": tm, "d": td, "k": tk},
        "backends": {},
    }
    for name in ("jnp", "pallas", "pallas_fused"):
        be = get_backend(name)
        prep = be.prepare(x, w)
        step = jax.jit(lambda centers, be=be, prep=prep: be.step(prep, centers))
        sums, counts, sse = jax.block_until_ready(step(c))
        np.testing.assert_allclose(np.asarray(sums), np.asarray(ref[0]),
                                   rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(np.asarray(counts), np.asarray(ref[1]),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(float(sse), float(ref[2]), rtol=1e-3)
        times = []
        for _ in range(timing_iters):
            t0 = time.perf_counter()
            jax.block_until_ready(step(c))
            times.append(time.perf_counter() - t0)
        entry["backends"][name] = {
            "us_per_iter": float(np.median(times) * 1e6),
            "numerics_ok": True,
        }

    b = entry["backends"]
    entry["speedup_fused_vs_pallas"] = (
        b["pallas"]["us_per_iter"] / b["pallas_fused"]["us_per_iter"])
    entry["speedup_fused_vs_jnp"] = (
        b["jnp"]["us_per_iter"] / b["pallas_fused"]["us_per_iter"])

    PERF.parent.mkdir(parents=True, exist_ok=True)
    out = PERF.parent / f"BENCH_lloyd_M{m}_d{d}_K{k}.json"
    out.write_text(json.dumps(entry, indent=1))
    entry["json"] = str(out)

    if assert_speedup is not None and not interpret:
        got = entry["speedup_fused_vs_pallas"]
        assert got >= assert_speedup, (
            f"fused Lloyd step only {got:.2f}x over the unfused pallas path "
            f"(wanted >= {assert_speedup}x)")
    return entry


def run_api_bench(n: int, d: int, k: int, *, timing_iters: int = 5,
                  max_overhead: float | None = 0.05) -> dict:
    """Facade-overhead check: ``SampledKMeans(spec).fit`` vs calling
    ``sampled_kmeans(spec=...)`` directly on the same data/key/spec.

    Both run the identical ``fit_from_spec`` trace, so any delta is pure
    host-side dispatch (plan + registry resolution).  Centers must agree
    bit-for-bit; the median-time ratio lands in
    ``benchmarks/artifacts/BENCH_api_N{n}_d{d}_K{k}.json`` and, when
    ``max_overhead`` is set, is asserted to stay under it.
    """
    import jax.numpy as jnp
    import numpy as np

    from repro.api import SampledKMeans
    from repro.core import sampled_kmeans
    from repro.core.spec import ClusterSpec
    from repro.data.synthetic import blobs

    spec = ClusterSpec.make(k, n_sub=16, compression=5)
    pts, _, _ = blobs(n, n_clusters=k, dim=d, seed=0)
    x = jnp.asarray(pts)
    key = jax.random.PRNGKey(0)

    def direct():
        return jax.block_until_ready(
            sampled_kmeans(x, k, spec=spec, key=key).sse)

    est = SampledKMeans(spec)

    def facade():
        return jax.block_until_ready(est.fit(x, key=key).sse_)

    # parity first (also warms both paths)
    r_direct = sampled_kmeans(x, k, spec=spec, key=key)
    est.fit(x, key=key)
    np.testing.assert_array_equal(np.asarray(r_direct.centers),
                                  np.asarray(est.centers_))
    assert float(r_direct.sse) == float(est.sse_)

    def med(fn):
        ts = []
        for _ in range(timing_iters):
            t0 = time.perf_counter()
            fn()
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts))

    t_direct, t_facade = med(direct), med(facade)
    entry = {
        "bench": "api_facade_overhead",
        "shape": {"n": n, "d": d, "k": k},
        "us_direct": t_direct * 1e6,
        "us_facade": t_facade * 1e6,
        "overhead": t_facade / t_direct - 1.0,
        "bit_for_bit": True,
    }
    PERF.parent.mkdir(parents=True, exist_ok=True)
    out = PERF.parent / f"BENCH_api_N{n}_d{d}_K{k}.json"
    out.write_text(json.dumps(entry, indent=1))
    entry["json"] = str(out)
    if max_overhead is not None:
        assert entry["overhead"] <= max_overhead, (
            f"SampledKMeans facade {entry['overhead']:+.1%} over direct "
            f"sampled_kmeans (allowed {max_overhead:+.1%})")
    return entry


def run_levels_bench(n: int, d: int, k: int, *, timing_iters: int = 3,
                     max_sse_ratio: float = 1.25) -> dict:
    """Hierarchical reduce tree vs the flat two-level merge.

    Fits the same blobs workload with ``levels=()`` and with one extra
    reduce level, recording wall-clock, SSE ratio and the representative-
    pool schedule (the hierarchy's point: the merge stage sees
    ``pool[-1]`` rows instead of ``pool[0]``).  SSE quality is asserted in
    every mode; timing is reported but only meaningful on compiled
    backends.  Lands in ``benchmarks/artifacts/BENCH_levels_*.json``.
    """
    import jax.numpy as jnp
    import numpy as np

    from repro.core import fit_from_spec
    from repro.core.spec import ClusterSpec, LevelSpec
    from repro.data.synthetic import blobs

    flat = ClusterSpec.make(k, n_sub=64, compression=5, local_iters=6,
                            global_iters=10)
    hier = flat.replace(levels=(LevelSpec(n_sub=16, compression=4,
                                          iters=6),))
    pts, _, _ = blobs(n, n_clusters=k, dim=d, seed=0)
    x = jnp.asarray(pts)
    key = jax.random.PRNGKey(0)

    def med(spec):
        fit = jax.jit(fit_from_spec, static_argnames=("spec",))
        sse = float(jax.block_until_ready(fit(x, spec, key).sse))  # warm
        ts = []
        for _ in range(timing_iters):
            t0 = time.perf_counter()
            jax.block_until_ready(fit(x, spec, key).sse)
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts)), sse

    t_flat, sse_flat = med(flat)
    t_hier, sse_hier = med(hier)
    entry = {
        "bench": "hierarchical_levels",
        "shape": {"n": n, "d": d, "k": k},
        "pool_flat": list(flat.pool_schedule(n)),
        "pool_hier": list(hier.pool_schedule(n)),
        "us_flat": t_flat * 1e6,
        "us_hier": t_hier * 1e6,
        "speedup": t_flat / t_hier,
        "sse_flat": sse_flat,
        "sse_hier": sse_hier,
        "sse_ratio": sse_hier / sse_flat,
    }
    PERF.parent.mkdir(parents=True, exist_ok=True)
    out = PERF.parent / f"BENCH_levels_N{n}_d{d}_K{k}.json"
    out.write_text(json.dumps(entry, indent=1))
    entry["json"] = str(out)
    if max_sse_ratio is not None:
        assert entry["sse_ratio"] <= max_sse_ratio, (
            f"hierarchical SSE {entry['sse_ratio']:.3f}x flat "
            f"(allowed {max_sse_ratio}x)")
    return entry


def run_stop_bench(n: int, d: int, k: int, *, tol: float = 1e-3,
                   timing_iters: int = 3,
                   max_sse_ratio: float = 1.01) -> dict:
    """Convergence-driven stopping (``StopSpec(tol=...)``) vs the fixed
    Lloyd budget on an easy-blobs workload.

    Runs the same spec twice — once with the legacy fixed ``global_iters``
    budget, once with a ``tol`` convergence criterion on both stages — and
    records the merged-stage ``iters_run`` (read from the ``stage_iters``
    telemetry of an eager fit), wall-clock for the jitted fit, and the SSE
    ratio.  The point of the artifact: early exit must actually trigger
    (``iters_run < iters_budget``) while quality stays within
    ``max_sse_ratio`` of the fixed-budget answer.  Lands in
    ``benchmarks/artifacts/BENCH_stop_N{n}_d{d}_K{k}.json`` and is gated
    by ``benchmarks/gate.py`` (``iters_run`` / ``sse_ratio``).
    """
    import jax.numpy as jnp
    import numpy as np

    from repro.core import fit_from_spec
    from repro.core.spec import ClusterSpec
    from repro.data.synthetic import blobs
    from repro.telemetry import RecordingLogger

    fixed = ClusterSpec.make(k, n_sub=64, compression=5, local_iters=6,
                             global_iters=25)
    conv = ClusterSpec.make(k, n_sub=64, compression=5, local_iters=6,
                            global_iters=25, tol=tol)
    pts, _, _ = blobs(n, n_clusters=k, dim=d, seed=0)
    x = jnp.asarray(pts)
    key = jax.random.PRNGKey(0)

    # eager instrumented run: the stage_iters events carry the true merge
    # trip count (telemetry is host-side only, so numbers match the jitted
    # fit bit-for-bit)
    log = RecordingLogger()
    fit_from_spec(x, conv, key, logger=log)
    stage = {e["stage"]: e for e in log.events
             if e.get("name") == "stage_iters"}
    merge = stage["merge"]

    def med(spec):
        fit = jax.jit(fit_from_spec, static_argnames=("spec",))
        sse = float(jax.block_until_ready(fit(x, spec, key).sse))  # warm
        ts = []
        for _ in range(timing_iters):
            t0 = time.perf_counter()
            jax.block_until_ready(fit(x, spec, key).sse)
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts)), sse

    t_fixed, sse_fixed = med(fixed)
    t_stop, sse_stop = med(conv)
    entry = {
        "bench": "stop_convergence",
        "shape": {"n": n, "d": d, "k": k},
        "tol": tol,
        "spec_hash_fixed": fixed.stable_hash(),
        "spec_hash_stop": conv.stable_hash(),
        "iters_budget": int(merge["iters_budget"]),
        "iters_run": int(merge["iters_run"]),
        "iters_saved": int(merge["iters_saved"]),
        "fold_iters_run": int(stage["fold"]["iters_run"]),
        "fold_iters_budget": int(stage["fold"]["iters_budget"]),
        "us_fixed": t_fixed * 1e6,
        "us_stop": t_stop * 1e6,
        "speedup": t_fixed / t_stop,
        "sse_fixed": sse_fixed,
        "sse_stop": sse_stop,
        "sse_ratio": sse_stop / sse_fixed,
    }
    PERF.parent.mkdir(parents=True, exist_ok=True)
    out = PERF.parent / f"BENCH_stop_N{n}_d{d}_K{k}.json"
    out.write_text(json.dumps(entry, indent=1))
    entry["json"] = str(out)
    assert entry["iters_run"] < entry["iters_budget"], (
        f"tol={tol} never tripped early exit: merge ran "
        f"{entry['iters_run']}/{entry['iters_budget']} iterations")
    if max_sse_ratio is not None:
        assert entry["sse_ratio"] <= max_sse_ratio, (
            f"early-stopped SSE {entry['sse_ratio']:.4f}x fixed-budget "
            f"(allowed {max_sse_ratio}x)")
    return entry


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    if "--stop" in sys.argv:
        ap.add_argument("--stop", action="store_true")
        ap.add_argument("--n", type=int, default=200_000)
        ap.add_argument("--d", type=int, default=8)
        ap.add_argument("--k", type=int, default=64)
        ap.add_argument("--tol", type=float, default=1e-3)
        ap.add_argument("--timing-iters", type=int, default=3)
        ap.add_argument("--max-sse-ratio", type=float, default=1.01,
                        help="assert early-stopped SSE <= this x fixed")
        args = ap.parse_args()
        e = run_stop_bench(args.n, args.d, args.k, tol=args.tol,
                           timing_iters=args.timing_iters,
                           max_sse_ratio=args.max_sse_ratio)
        print(json.dumps(e, indent=1))
        sys.exit(0)
    if "--levels" in sys.argv:
        ap.add_argument("--levels", action="store_true")
        ap.add_argument("--n", type=int, default=200_000)
        ap.add_argument("--d", type=int, default=8)
        ap.add_argument("--k", type=int, default=64)
        ap.add_argument("--timing-iters", type=int, default=3)
        ap.add_argument("--max-sse-ratio", type=float, default=1.25,
                        help="assert hierarchical SSE <= this x flat")
        args = ap.parse_args()
        e = run_levels_bench(args.n, args.d, args.k,
                             timing_iters=args.timing_iters,
                             max_sse_ratio=args.max_sse_ratio)
        print(json.dumps(e, indent=1))
        sys.exit(0)
    if "--api" in sys.argv:
        ap.add_argument("--api", action="store_true")
        ap.add_argument("--n", type=int, default=100_000)
        ap.add_argument("--d", type=int, default=2)
        ap.add_argument("--k", type=int, default=64)
        ap.add_argument("--timing-iters", type=int, default=5)
        ap.add_argument("--max-overhead", type=float, default=0.05,
                        help="assert facade <= this fractional overhead "
                             "over direct sampled_kmeans")
        args = ap.parse_args()
        e = run_api_bench(args.n, args.d, args.k,
                          timing_iters=args.timing_iters,
                          max_overhead=args.max_overhead)
        print(json.dumps(e, indent=1))
        sys.exit(0)
    if "--lloyd" in sys.argv:
        ap.add_argument("--lloyd", action="store_true")
        ap.add_argument("--m", type=int, default=262144)
        ap.add_argument("--d", type=int, default=64)
        ap.add_argument("--k", type=int, default=256)
        ap.add_argument("--timing-iters", type=int, default=5)
        ap.add_argument("--min-speedup", type=float, default=1.5,
                        help="assert fused >= this x over unfused pallas "
                             "(compiled mode only)")
        args = ap.parse_args()
        e = run_lloyd_bench(args.m, args.d, args.k,
                            timing_iters=args.timing_iters,
                            assert_speedup=args.min_speedup)
        print(json.dumps(e, indent=1))
        sys.exit(0)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--hypothesis", default="")
    ap.add_argument("--n-micro", type=int, default=None)
    ap.add_argument("--grad-dtype", default=None)
    ap.add_argument("--q-chunk", type=int, default=None)
    ap.add_argument("--act-model", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--window", type=int, default=None)
    ap.add_argument("--compression", type=int, default=None)
    ap.add_argument("--full-cache", action="store_true")
    ap.add_argument("--moe-ep-data", action="store_true",
                    help="experts sharded over 'data', ffn hidden over "
                         "'model' (kills the per-micro ZeRO gather of "
                         "expert weights)")
    ap.add_argument("--act-seq", action="store_true",
                    help="sequence-parallel residual stream (S over 'model')")
    ap.add_argument("--zero2", action="store_true",
                    help="replicate block weights over 'data' (ZeRO-2: "
                         "only grads+optimizer stay sharded) — removes the "
                         "per-micro weight all-gather")
    args = ap.parse_args()

    import repro.train.sharding as shmod
    from repro.models import lm as lmmod
    from jax.sharding import PartitionSpec as P

    if args.moe_ep_data:
        new_rules = []
        for pat, spec in shmod.RULES:
            if pat == r"moe/we[13]$":
                spec = P(None, "data", None, "model")
            elif pat == r"moe/we2$":
                spec = P(None, "data", "model", None)
            new_rules.append((pat, spec))
        shmod.RULES = tuple(new_rules)
        lmmod.EXPERT_SPEC_OVERRIDE = P(None, "data", None, "model")

    if args.zero2:
        orig_ps = shmod.param_specs

        def zero2_param_specs(params_like, mesh_):
            specs = orig_ps(params_like, mesh_)

            def strip_data(s):
                parts = [None if a == "data" else
                         (tuple(x for x in a if x != "data") or None
                          if isinstance(a, tuple) else a) for a in s]
                return P(*parts)

            return jax.tree.map(strip_data, specs,
                                is_leaf=lambda x: isinstance(x, P))

        shmod.param_specs = zero2_param_specs
        dr.param_specs = zero2_param_specs

    cfg = get_config(args.arch)
    shape = SHAPES[args.shape]
    mesh = make_production_mesh()
    if shape.kind == "train":
        total = measure_train(cfg, shape, mesh, n_micro=args.n_micro,
                              act_model=args.act_model,
                              grad_dtype=args.grad_dtype,
                              q_chunk=args.q_chunk,
                              remat=(False if args.no_remat else None),
                              act_seq=args.act_seq)
    else:
        total = measure_decode(cfg, shape, mesh, window=args.window,
                               compression=args.compression,
                               full_cache=args.full_cache)
    e = record(args.arch, args.shape, args.variant, args.hypothesis, total)
    print(json.dumps(e, indent=1))
