"""Telemetry, trajectory store, and CI gate.

Three layers under test:

* ``repro.telemetry`` — event schema, timer nesting, registry, and the
  load-bearing guarantee that a NULL logger changes *nothing* (logged vs
  unlogged fits must be bit-for-bit identical).
* ``benchmarks.trajectory`` — artifact normalization and malformed-input
  tolerance (a crashed benchmark must never poison the store).
* ``benchmarks.gate`` — the regression gate trips on injected slowdown /
  SSE inflation and stays quiet on a clean copy.
"""
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmarks import gate, trajectory  # noqa: E402
from repro.telemetry import (NULL, JsonlLogger, MedianWindow, NullLogger,
                             RecordingLogger, calibrate, get_run_logger,
                             peak_rss_mb, validate_event)


# ---------------------------------------------------------------- schema --

def test_event_schema_roundtrip():
    rec = RecordingLogger()
    rec.event("fit", n=100, backend="jnp")
    with rec.timer("stage", rows=5):
        pass
    rec.rate("tick", units="points").tick(100, dur=0.5)
    assert len(rec.events) == 3
    for e in rec.events:
        validate_event(e)                       # raises on malformed
        again = json.loads(json.dumps(e))       # JSON round-trip is exact
        assert again == e
    kinds = [e["kind"] for e in rec.events]
    assert kinds == ["event", "timer", "rate"]
    assert rec.events[1]["dur"] >= 0
    assert rec.events[2]["rate"] == pytest.approx(200.0)


def test_validate_event_rejects_malformed():
    with pytest.raises(ValueError):
        validate_event({"kind": "event"})               # missing keys
    with pytest.raises(ValueError):
        validate_event({"schema": 1, "kind": "nope", "name": "x", "t": 0.0})
    with pytest.raises(ValueError):
        validate_event({"schema": 1, "kind": "timer", "name": "x",
                        "t": 0.0})                      # timer without dur


def test_timer_nesting_depth_and_path():
    rec = RecordingLogger()
    with rec.timer("outer"):
        with rec.timer("inner"):
            rec.event("leaf")
    leaf, inner, outer = rec.events
    assert leaf["path"] == "outer/inner/leaf" and leaf["depth"] == 2
    assert inner["path"] == "outer/inner" and inner["depth"] == 1
    assert outer["path"] == "outer" and outer["depth"] == 0
    assert outer["dur"] >= inner["dur"]


def test_median_window():
    w = MedianWindow(window=3)
    assert w.median is None
    for v in (1.0, 100.0, 3.0):
        w.push(v)
    assert w.median == 3.0
    w.push(5.0)                 # evicts 1.0 -> window is {100, 3, 5}
    assert w.median == 5.0


def test_registry_and_null():
    assert get_run_logger(None) is NULL
    assert get_run_logger("off") is NULL
    assert isinstance(get_run_logger("memory"), RecordingLogger)
    rec = RecordingLogger()
    assert get_run_logger(rec) is rec
    with pytest.raises(ValueError, match="unknown telemetry logger"):
        get_run_logger("no-such-logger")
    # the NULL path allocates nothing per call
    with NULL.timer("x") as t:
        assert isinstance(t, NullLogger)
    NULL.rate("r").tick(10)
    NULL.event("e")


def test_jsonl_logger(tmp_path):
    path = tmp_path / "run.jsonl"
    log = JsonlLogger(path)
    with log.timer("fit"):
        log.event("mid", k=3)
    lines = path.read_text().strip().split("\n")
    assert len(lines) == 2
    for line in lines:
        validate_event(json.loads(line))


def test_helpers():
    assert peak_rss_mb() > 1.0
    assert calibrate(repeats=1) > 1.0


# ----------------------------------------------------- no-op parity ------

def _spec(**kw):
    from repro.core.spec import ClusterSpec
    return ClusterSpec.make(4, n_sub=4, compression=3, **kw)


def test_fit_from_spec_logged_vs_unlogged_bit_for_bit(blob_data):
    from repro.core import fit_from_spec
    x = jnp.asarray(blob_data[0])
    key = jax.random.PRNGKey(7)
    spec = _spec()
    plain = fit_from_spec(x, spec, key)
    rec = RecordingLogger()
    logged = fit_from_spec(x, spec, key, logger=rec)
    np.testing.assert_array_equal(np.asarray(plain.centers),
                                  np.asarray(logged.centers))
    assert float(plain.sse) == float(logged.sse)
    names = [e["name"] for e in rec.events]
    assert "fold" in names and "merge" in names
    assert names[-1] == "fit_from_spec"
    summary = rec.events[-1]
    assert summary["points_per_sec"] > 0 and summary["n"] == x.shape[0]


def test_fit_chunked_logged_vs_unlogged_bit_for_bit(blob_data):
    from repro.core import fit_chunked
    from repro.core.spec import ChunkSpec, ExecutionSpec
    x = jnp.asarray(blob_data[0])
    spec = _spec().replace(execution=ExecutionSpec(mode="chunked"),
                           chunk=ChunkSpec(chunk_points=256))
    key = jax.random.PRNGKey(3)
    plain, pstats = fit_chunked(x, spec, key)
    rec = RecordingLogger()
    logged, lstats = fit_chunked(x, spec, key, logger=rec)
    np.testing.assert_array_equal(np.asarray(plain.centers),
                                  np.asarray(logged.centers))
    assert float(plain.sse) == float(logged.sse)
    assert pstats == lstats
    rates = [e for e in rec.events if e["kind"] == "rate"]
    assert len(rates) == lstats.n_chunks       # one fold_rate tick per chunk
    assert rec.events[-1]["name"] == "fit_chunked"
    assert rec.events[-1]["peak_rss_mb"] > 0


def test_telemetry_via_spec_string_and_api(blob_data):
    """``ExecutionSpec.telemetry`` survives the JSON round-trip and the
    facade resolves it at plan time."""
    from repro.api import SampledKMeans
    from repro.core.spec import ClusterSpec, ExecutionSpec
    spec = _spec().replace(execution=ExecutionSpec(telemetry="memory"))
    again = ClusterSpec.from_dict(json.loads(json.dumps(spec.to_dict())))
    assert again == spec and again.execution.telemetry == "memory"

    x = jnp.asarray(blob_data[0])
    est = SampledKMeans(spec)
    est.fit(x, key=jax.random.PRNGKey(0))
    assert isinstance(est.logger, RecordingLogger)
    assert any(e["name"] == "fit_from_spec" for e in est.logger.events)

    # explicit logger argument overrides the spec string
    rec = RecordingLogger()
    est2 = SampledKMeans(_spec(), logger=rec)
    est2.fit(x, key=jax.random.PRNGKey(0))
    assert any(e["name"] == "fit_from_spec" for e in rec.events)
    np.testing.assert_array_equal(np.asarray(est.centers_),
                                  np.asarray(est2.centers_))


# ------------------------------------------------------------ stages ----

def _scope_path(op_name: str) -> list:
    """The scopes of one HLO ``op_name``, outermost first: its path
    components, with JAX's transform wrappers (``vmap(kmeans_init)``)
    taken off."""
    import re
    return [re.sub(r"^(\w+\()+|\)+$", "", c) for c in op_name.split("/")]


def _scope_names(op_name: str) -> set:
    return set(_scope_path(op_name))


def _donated_fit_op_names(x, spec):
    import re
    from repro.core import fit_from_spec
    fit = jax.jit(fit_from_spec, static_argnames=("spec", "backend"),
                  donate_argnums=0)
    text = fit.lower(x, spec, jax.random.PRNGKey(0)).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


def test_stage_logged_times_to_ready_and_null_passes_through():
    rec = RecordingLogger()
    y = jnp.arange(4.0)
    with rec.stage("work", rows=4) as st:
        out = st.ready(y * 2)
    assert out is st.outputs
    (ev,) = rec.events
    assert ev["kind"] == "timer" and ev["name"] == "work"
    assert ev["rows"] == 4 and ev["dur"] >= 0 and ev["path"] == "work"
    with NULL.stage("work", rows=4) as st:
        assert st.ready(y) is y
        assert st.outputs is None          # NULL never holds outputs
    from repro.telemetry import scope
    with scope("work") as st:
        assert st.ready(y) is y


def test_stage_names_the_traced_ops_and_opens_no_span_in_a_trace():
    from repro.telemetry import Stage

    def f(v):
        with NULL.stage("outer") as st:
            assert st._span is None        # host code runs once, here
            return v * 3
    text = jax.jit(f).lower(jnp.ones(3)).compile().as_text()
    assert 'op_name="jit(f)/outer/mul"' in text
    assert Stage("top")._span is not None  # eager: a host span


def test_donated_fit_program_carries_the_stage_scopes(blob_data):
    """The compiled donate-path fit names fold, kmeans_init, merge and sse
    in its ops' ``op_name``; seeding sits under ``kmeans_init`` in the
    fold's partitions and in the merge's restarts alike."""
    x = jnp.asarray(blob_data[0])
    names = _donated_fit_op_names(x, _spec())
    scopes = [_scope_names(n) for n in names]
    for stage in ("fold", "kmeans_init", "merge", "sse"):
        assert any(stage in s for s in scopes), stage
    assert any({"fold", "kmeans_init"} <= s and "merge" not in s
               for s in scopes)
    assert any({"merge", "kmeans_init"} <= s and "fold" not in s
               for s in scopes)
    # the fold stays the jit the accepted fold_ms.fit reader matches
    assert any("jit(_fold_scaled_chunk)" in n and "fold" in _scope_names(n)
               for n in names)


def test_donated_fit_program_names_the_lane_path_under_kmeans_init(
        blob_data):
    """At d=2 the k-means++ D^2 update runs coordinate-major under
    ``kmeans_pp_lanes``, nested in ``kmeans_init`` (which ``seed_ms.fit``
    reads), in the fold and in the merge."""
    x = jnp.asarray(blob_data[0][:, :2])
    paths = [_scope_path(n) for n in _donated_fit_op_names(x, _spec())]
    lanes = [p for p in paths if "kmeans_pp_lanes" in p]
    assert all("kmeans_init" in p[:p.index("kmeans_pp_lanes")]
               for p in lanes)
    assert any("fold" in p and "merge" not in p for p in lanes)
    assert any("merge" in p and "fold" not in p for p in lanes)


def test_null_fit_stages_never_sync(blob_data, monkeypatch):
    from repro.core import fit_from_spec
    x = jnp.asarray(blob_data[0])
    calls = []
    real = jax.block_until_ready

    def counting(v):
        calls.append(1)
        return real(v)
    monkeypatch.setattr(jax, "block_until_ready", counting)
    res = fit_from_spec(x, _spec(), jax.random.PRNGKey(1))
    assert not calls
    real(res)
    fit_from_spec(x, _spec(), jax.random.PRNGKey(1),
                  logger=RecordingLogger())
    assert len(calls) == 3                 # fold, merge, sse wait


def test_logged_stages_cover_the_eager_fit(blob_data):
    """The stage timers wait for their work: fold + merge + sse account
    for most of the fit's wall time (they timed the enqueue before)."""
    from repro.core import fit_from_spec
    x = jnp.asarray(blob_data[0])
    key = jax.random.PRNGKey(2)
    fit_from_spec(x, _spec(), key, logger=RecordingLogger())   # compiles
    rec = RecordingLogger()
    fit_from_spec(x, _spec(), key, logger=rec)
    dur = {e["name"]: e["dur"] for e in rec.events if e["kind"] == "timer"}
    wall = rec.named("fit_from_spec")[0]["wall_s"]
    assert set(dur) == {"fold", "merge", "sse"}
    assert dur["fold"] + dur["merge"] + dur["sse"] >= 0.8 * wall


def test_donated_fit_is_one_fit_stage(blob_data):
    from repro.api import SampledKMeans
    from repro.core.spec import ExecutionSpec
    x = jnp.asarray(blob_data[0])
    spec = _spec().replace(execution=ExecutionSpec(donate=True))
    rec = RecordingLogger()
    est = SampledKMeans(spec, logger=rec).fit(jnp.copy(x),
                                              key=jax.random.PRNGKey(0))
    (ev,) = rec.events
    assert ev["name"] == "fit" and ev["kind"] == "timer"
    assert ev["n"] == x.shape[0] and ev["k"] == spec.merge.k
    plain = SampledKMeans(spec).fit(jnp.copy(x), key=jax.random.PRNGKey(0))
    np.testing.assert_array_equal(np.asarray(est.centers_),
                                  np.asarray(plain.centers_))


def _kernel_calls():
    from repro.kernels.assign import assign_argmin_pallas
    from repro.kernels.centroid import centroid_update_pallas
    from repro.kernels.cluster_attn import cluster_attn_decode_pallas
    from repro.kernels.lloyd import lloyd_step_pallas
    from repro.kernels.scan import adc_scan_pallas
    x = jnp.ones((256, 8), jnp.float32)
    w = jnp.ones((256,), jnp.float32)
    c = jnp.ones((16, 8), jnp.float32)
    idx = jnp.zeros((256,), jnp.int32)
    q = jnp.ones((1, 4, 32), jnp.float32)
    kv = jnp.ones((1, 1, 64, 32), jnp.float32)
    return {
        "lloyd": lambda: lloyd_step_pallas(x, w, c),
        "assign": lambda: assign_argmin_pallas(x, c),
        "centroid": lambda: centroid_update_pallas(x, idx, w, 16,
                                                   block_m=256),
        "scan": lambda: adc_scan_pallas(jnp.ones((2, 4, 16), jnp.float32),
                                        jnp.zeros((2, 128, 4), jnp.int32)),
        "cluster_attn": lambda: cluster_attn_decode_pallas(
            q, kv, kv, jnp.ones((1, 1, 64), jnp.float32), 0.5),
    }


@pytest.mark.parametrize("kernel", ["lloyd", "assign", "centroid", "scan",
                                    "cluster_attn"])
def test_every_pallas_call_is_named(kernel):
    jaxpr = jax.make_jaxpr(_kernel_calls()[kernel])()
    names = [e.params["name"] for e in jaxpr.jaxpr.eqns
             if e.primitive.name == "pallas_call"]
    assert names == [kernel]


def test_no_unnamed_pallas_call_in_the_kernels():
    import ast
    kernels = REPO / "src" / "repro" / "kernels"
    calls = 0
    for path in sorted(kernels.glob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "pallas_call"):
                calls += 1
                assert "name" in {k.arg for k in node.keywords}, path.name
    assert calls == len(_kernel_calls())


def test_stream_tick_telemetry(blob_data):
    from repro.stream.engine import StreamConfig, StreamingClusterer
    rec = RecordingLogger()
    cfg = StreamConfig(k=4, n_sub=4, compression=3, buffer_size=64)
    eng = StreamingClusterer(cfg, logger=rec)
    st = eng.init(dim=3)
    x = jnp.asarray(blob_data[0][:128], jnp.float32)
    st = eng.update(st, x[:64])
    st = eng.update(st, x[64:])
    ticks = [e for e in rec.events if e["name"] == "stream_tick"]
    assert len(ticks) == 2
    assert all(t["rate"] > 0 for t in ticks)
    # parity: same updates without a logger give identical state
    eng2 = StreamingClusterer(cfg)
    st2 = eng2.init(dim=3)
    st2 = eng2.update(st2, x[:64])
    st2 = eng2.update(st2, x[64:])
    np.testing.assert_array_equal(np.asarray(st.centers),
                                  np.asarray(st2.centers))


def test_spec_stable_hash_ignores_execution():
    from repro.core.spec import ExecutionSpec
    spec = _spec()
    h = spec.stable_hash()
    assert len(h) == 12
    assert spec.replace(
        execution=ExecutionSpec(telemetry="memory")).stable_hash() == h
    assert _spec(global_iters=3).stable_hash() != h


# ------------------------------------------------------- trajectory ------

def _spec_record(**over):
    rec = {
        "schema": 1, "bench": "spec_file", "name": "smoke",
        "spec_hash": "abc123def456", "mode": "single", "backend": "jnp",
        "calib_mflops": 1000.0, "points_per_sec": 5e5, "us_best": 2e4,
        "sse": 123.0, "peak_rss_mb": 400.0,
    }
    rec.update(over)
    return rec


def test_trajectory_normalize_each_kind():
    pts = trajectory.normalize(_spec_record())
    assert len(pts) == 1 and pts[0]["key"] == "abc123def456|single|jnp"
    assert pts[0]["metrics"]["points_per_sec"] == 5e5

    lloyd = {"bench": "lloyd_step", "mode": "compiled",
             "requested": {"m": 1024, "d": 8, "k": 16},
             "backends": {"jnp": {"us_per_iter": 10.0},
                          "pallas_fused": {"us_per_iter": 4.0}}}
    pts = trajectory.normalize(lloyd)
    assert {p["key"] for p in pts} == {
        "lloyd_M1024_d8_K16|compiled|jnp",
        "lloyd_M1024_d8_K16|compiled|pallas_fused"}

    api = {"bench": "api_facade_overhead", "shape": {"n": 1, "d": 2, "k": 3},
           "overhead": 0.01, "us_direct": 5.0, "us_facade": 5.05}
    assert trajectory.normalize(api)[0]["metrics"]["overhead"] == 0.01

    lv = {"bench": "hierarchical_levels", "shape": {"n": 1, "d": 2, "k": 3},
          "sse_ratio": 1.01, "speedup": 1.4}
    assert trajectory.normalize(lv)[0]["metrics"]["sse_ratio"] == 1.01


def test_trajectory_rejects_malformed():
    with pytest.raises(trajectory.SkipArtifact):
        trajectory.normalize(["not", "a", "dict"])
    with pytest.raises(trajectory.SkipArtifact):
        trajectory.normalize({"no_bench": True})
    with pytest.raises(trajectory.SkipArtifact):
        trajectory.normalize({"bench": "mystery_bench"})
    with pytest.raises(trajectory.SkipArtifact):
        trajectory.normalize({"bench": "spec_file", "name": "x",
                              "sse": "NaN-ish-string"})   # no numeric metric


def test_trajectory_ingest_skips_bad_files(tmp_path):
    (tmp_path / "BENCH_good.json").write_text(json.dumps(_spec_record()))
    (tmp_path / "BENCH_broken.json").write_text("{not json")
    (tmp_path / "BENCH_partial.json").write_text(
        json.dumps({"bench": "spec_file", "name": "partial"}))
    (tmp_path / "BENCH_unknown.json").write_text(
        json.dumps({"bench": "from_the_future"}))
    (tmp_path / "not_an_artifact.json").write_text("{}")   # ignored: no BENCH_
    points, skipped = trajectory.ingest(tmp_path)
    assert len(points) == 1 and points[0]["name"] == "smoke"
    assert sorted(name for name, _ in skipped) == [
        "BENCH_broken.json", "BENCH_partial.json", "BENCH_unknown.json"]


def test_trajectory_append_replaces_same_label(tmp_path):
    traj = trajectory.load_trajectory(tmp_path / "missing.json")
    pts = trajectory.normalize(_spec_record())
    trajectory.append_points(traj, pts, label="sha1", t=1.0)
    trajectory.append_points(traj, pts, label="sha1", t=2.0)   # re-run
    trajectory.append_points(traj, pts, label="sha2", t=3.0)
    hist = traj["series"]["abc123def456|single|jnp"]
    assert [h["label"] for h in hist] == ["sha1", "sha2"]
    assert hist[0]["t"] == 2.0


# ------------------------------------------------------------- gate ------

def _points(**over):
    return trajectory.normalize(_spec_record(**over), "<test>")


def test_gate_clean_copy_passes():
    base = _points()
    checks, notes = gate.compare_points(base, base)
    assert checks and all(c["status"] == "ok" for c in checks)
    assert not notes


def test_gate_trips_on_throughput_regression():
    checks, _ = gate.compare_points(_points(),
                                    _points(points_per_sec=5e5 * 0.70))
    bad = [c for c in checks if c["status"] == "FAIL"]
    assert [c["metric"] for c in bad] == ["points_per_sec"]
    # 20% off is inside the 25% tolerance: must NOT trip
    checks, _ = gate.compare_points(_points(),
                                    _points(points_per_sec=5e5 * 0.80))
    assert all(c["status"] == "ok" for c in checks)


def test_gate_trips_on_sse_inflation():
    checks, _ = gate.compare_points(_points(), _points(sse=123.0 * 1.10))
    bad = [c for c in checks if c["status"] == "FAIL"]
    assert [c["metric"] for c in bad] == ["sse"]
    checks, _ = gate.compare_points(_points(), _points(sse=123.0 * 1.04))
    assert all(c["status"] == "ok" for c in checks)


def test_gate_calibration_normalizes_throughput():
    base = _points(calib_mflops=1000.0)
    # current machine is 2x faster and measured 1.6x the throughput:
    # normalized back to the baseline box that's a 20% drop — inside tol
    cur = _points(calib_mflops=2000.0, points_per_sec=5e5 * 1.6)
    checks, _ = gate.compare_points(base, cur)
    pps = [c for c in checks if c["metric"] == "points_per_sec"]
    assert pps[0]["status"] == "ok"
    assert pps[0]["normalized"] == pytest.approx(5e5 * 0.8)
    # same raw number with equal calib would also pass; 1.3x on a 2x
    # machine is a 35% normalized drop — must trip
    cur = _points(calib_mflops=2000.0, points_per_sec=5e5 * 1.3)
    checks, _ = gate.compare_points(base, cur)
    pps = [c for c in checks if c["metric"] == "points_per_sec"]
    assert pps[0]["status"] == "FAIL"


def test_gate_missing_baseline_is_note_not_failure():
    cur = _points(spec_hash="brand-new-bench")
    checks, notes = gate.compare_points([], cur)
    assert not checks
    assert len(notes) == 1 and "no baseline" in notes[0]
    assert gate.report(checks, notes, out=sys.stderr) is True


def test_gate_interpret_mode_timing_skipped():
    lloyd = {"bench": "lloyd_step", "mode": "interpret",
             "requested": {"m": 64, "d": 2, "k": 4},
             "backends": {"jnp": {"us_per_iter": 10.0}}}
    base = trajectory.normalize(lloyd, "<t>")
    cur = trajectory.normalize(dict(lloyd, backends={
        "jnp": {"us_per_iter": 1000.0}}), "<t>")
    checks, _ = gate.compare_points(base, cur)
    assert not checks           # interpreter overhead never gates


def test_gate_self_test_and_cli(tmp_path, capsys):
    bdir = tmp_path / "baselines"
    bdir.mkdir()
    (bdir / "BENCH_smoke.json").write_text(json.dumps(_spec_record()))
    assert gate.main(["--baselines", str(bdir), "--self-test"]) == 0
    out = capsys.readouterr().out
    assert "slowdown trips" in out and "SSE inflation trips" in out

    cdir = tmp_path / "current"
    cdir.mkdir()
    (cdir / "BENCH_smoke.json").write_text(json.dumps(_spec_record()))
    assert gate.main(["--baselines", str(bdir),
                      "--current", str(cdir)]) == 0
    (cdir / "BENCH_smoke.json").write_text(json.dumps(
        _spec_record(points_per_sec=5e5 * 0.5)))
    assert gate.main(["--baselines", str(bdir),
                      "--current", str(cdir)]) == 1
