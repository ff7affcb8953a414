"""The kernel tile autotuner: cache layering/keying, the sweep contract
(verification, determinism, the >=1.0x-vs-default guarantee), the tile
contract satellites (TileError, clamps), and the ``pallas_tuned`` backend's
bit-for-bit parity with ``pallas_fused`` at equal tiles."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.backend import (PallasFusedBackend, PallasTunedBackend,
                                available_backends, get_backend)
from repro.kernels import TileError, autotune, tune_table
from repro.kernels.autotune import TileConfig
from repro.kernels.tiles import (clamp_block_k, clamp_block_l, clamp_block_m,
                                 pad_to, require_block_m)


@pytest.fixture(autouse=True)
def _fresh_caches(monkeypatch):
    """Every test starts with empty tuner caches and no persistent path."""
    monkeypatch.delenv(autotune.ENV_VAR, raising=False)
    autotune.clear_caches()
    yield
    autotune.clear_caches()


# ---------------------------------------------------------------------------
# tiles.py satellites: TileError + clamps
# ---------------------------------------------------------------------------

def test_unpadded_m_raises_typed_error_with_pad_hint():
    from repro.kernels.lloyd import lloyd_step_pallas
    x = jnp.zeros((1000, 128), jnp.float32)
    w = jnp.ones((1000,), jnp.float32)
    c = jnp.zeros((8, 128), jnp.float32)
    with pytest.raises(TileError) as ei:
        lloyd_step_pallas(x, w, c, block_m=256)
    assert isinstance(ei.value, ValueError)          # except ValueError works
    assert ei.value.extent == 1000 and ei.value.block == 256
    assert "1024" in str(ei.value)                   # the pad recipe
    assert "lloyd_step_pallas" in str(ei.value)


@pytest.mark.parametrize("kernel_mod,fname", [
    ("assign", "assign_argmin_pallas"), ("centroid", "centroid_update_pallas")])
def test_unfused_kernels_share_the_tile_error(kernel_mod, fname):
    import importlib
    mod = importlib.import_module(f"repro.kernels.{kernel_mod}")
    fn = getattr(mod, fname)
    x = jnp.zeros((100, 128), jnp.float32)
    with pytest.raises(TileError, match=fname):
        if kernel_mod == "assign":
            fn(x, jnp.zeros((8, 128), jnp.float32), block_m=64)
        else:
            fn(x, jnp.zeros((100,), jnp.int32), jnp.ones((100,)), 8,
               block_m=64)


def test_clamp_block_k_handles_tiny_k_without_silent_bump():
    # k < 8: every requested tile collapses to ONE 8-wide kernel — the
    # tuner dedupes through this same function, so no phantom configs
    assert clamp_block_k(3, 4) == 8
    assert clamp_block_k(3, 256) == 8
    assert clamp_block_k(16, 256) == 16
    assert clamp_block_k(200, 256) == pad_to(200, 8)
    assert clamp_block_k(1000, 256) == 256
    assert clamp_block_m(6, 512) == 8
    assert clamp_block_l(500, 1024) == pad_to(500, 8)


def test_tiny_k_kernel_runs_and_matches_oracle(rng):
    """The k<8 clamp is not just cosmetic: the kernel actually runs one
    8-wide tile and matches the oracle whatever block_k was requested."""
    from repro.kernels import lloyd_step
    from repro.kernels.ref import lloyd_step_ref
    x = jnp.asarray(rng.normal(size=(64, 5)), jnp.float32)
    w = jnp.ones((64,), jnp.float32)
    c = jnp.asarray(rng.normal(size=(3, 5)), jnp.float32)
    for bk in (4, 256):
        sums, counts, sse, _, _ = lloyd_step(x, w, c, block_k=bk)
        rsums, rcounts, rsse, _, _ = lloyd_step_ref(x, w, c)
        np.testing.assert_allclose(np.asarray(sums), np.asarray(rsums),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(float(sse), float(rsse), rtol=1e-4)


# ---------------------------------------------------------------------------
# cache keying + layering
# ---------------------------------------------------------------------------

def test_cache_key_buckets_nearby_shapes_together():
    k1 = autotune.cache_key("lloyd", m=200_000, d=64, k=200,
                            device_kind="x", backend="cpu")
    k2 = autotune.cache_key("lloyd", m=262_144, d=64, k=256,
                            device_kind="x", backend="cpu")
    assert k1 == k2                       # same pow2/lane bucket
    k3 = autotune.cache_key("lloyd", m=300_000, d=64, k=256,
                            device_kind="x", backend="cpu")
    assert k3 != k1                       # crosses the 2^18 boundary
    # dtype, device kind, and kernel all split the key
    assert autotune.cache_key("lloyd", m=200_000, d=64, k=200,
                              dtype=jnp.bfloat16, device_kind="x",
                              backend="cpu") != k1
    assert autotune.cache_key("assign", m=200_000, d=64, k=200,
                              device_kind="x", backend="cpu") != k1
    assert autotune.cache_key("lloyd", m=200_000, d=64, k=200,
                              device_kind="y", backend="cpu") != k1


def test_lookup_hits_memory_after_first_resolution():
    cfg, src = autotune.lookup("lloyd", m=4096, d=64, k=64, with_source=True)
    assert src in ("table", "default") and any(cfg)
    cfg2, src2 = autotune.lookup("lloyd", m=4096, d=64, k=64,
                                 with_source=True)
    assert src2 == "memory" and cfg2 == cfg
    # a different shape bucket misses
    _, src3 = autotune.lookup("lloyd", m=40_960, d=64, k=64,
                              with_source=True)
    assert src3 != "memory"


def test_persistent_cache_round_trip(tmp_path):
    p = tmp_path / "tune.json"
    key = autotune.cache_key("lloyd", m=4096, d=64, k=64,
                             device_kind="testdev", backend="cpu")
    assert autotune.save_entry(key, TileConfig(block_m=128, block_k=64),
                               path=p)
    autotune.clear_caches()               # a "new process"
    cfg, src = autotune.lookup("lloyd", m=4096, d=64, k=64,
                               device_kind="testdev", backend="cpu",
                               path=p, with_source=True)
    assert src == "disk"
    assert cfg == TileConfig(block_m=128, block_k=64)
    # the file itself is the documented schema
    doc = json.loads(p.read_text())
    assert doc["schema"] == autotune.CACHE_SCHEMA
    assert doc["entries"][key] == {"block_m": 128, "block_k": 64}


def test_persistent_cache_env_var(tmp_path, monkeypatch):
    p = tmp_path / "tune.json"
    monkeypatch.setenv(autotune.ENV_VAR, str(p))
    key = autotune.cache_key("scan", b=8, l=1024, msub=8, c=16,
                             device_kind="testdev", backend="cpu")
    assert autotune.save_entry(key, TileConfig(block_l=128))
    autotune.clear_caches()
    cfg, src = autotune.lookup("scan", b=8, l=1024, msub=8, c=16,
                               device_kind="testdev", backend="cpu",
                               with_source=True)
    assert (src, cfg) == ("disk", TileConfig(block_l=128))


def test_corrupt_cache_file_falls_through(tmp_path):
    p = tmp_path / "tune.json"
    p.write_text("{ this is not json")
    cfg, src = autotune.lookup("lloyd", m=4096, d=64, k=64, path=p,
                               with_source=True)
    assert src in ("table", "default") and any(cfg)
    # partially-corrupt: good entries survive, bad ones are skipped
    key = autotune.cache_key("lloyd", m=4096, d=64, k=64,
                             device_kind="dv", backend="cpu")
    p.write_text(json.dumps({"schema": 1, "entries": {
        key: {"block_m": 64, "block_k": 64},
        "bad": {"block_m": "huge"}, "worse": [1, 2]}}))
    autotune.clear_caches()
    cfg, src = autotune.lookup("lloyd", m=4096, d=64, k=64,
                               device_kind="dv", backend="cpu", path=p,
                               with_source=True)
    assert (src, cfg) == ("disk", TileConfig(block_m=64, block_k=64))


def test_committed_table_loads_and_validates():
    assert tune_table.validate_table() > 0
    cfg = tune_table.load_default("lloyd", "TPU v5 lite")
    assert cfg == TileConfig(block_m=512, block_k=256)
    # unknown device kinds fall to the "*" row, never None for our kernels
    assert any(tune_table.load_default("lloyd", "Quantum FPGA 9000"))


def test_device_roof_knows_v5e_and_nothing_else():
    from repro.roofline.analysis import device_roof, predicted_vs_measured
    roof = device_roof("TPU v5 lite")
    assert roof == {"peak_flops": 197e12, "hbm_bw": 819e9}
    for unknown in ("cpu", "Quantum FPGA 9000", ""):
        with pytest.raises(KeyError, match="no published roof"):
            device_roof(unknown)
    rep = predicted_vs_measured("lloyd", 1e-3, device_kind="TPU v5 lite",
                                m=262_144, d=128, k=1024)
    assert rep["predicted_s"] > 0 and rep["dominant"] in ("compute",
                                                          "memory")


def test_lookup_rejects_bad_dims():
    with pytest.raises(ValueError, match="unknown tunable kernel"):
        autotune.lookup("warp", m=8, d=8, k=8)
    with pytest.raises(ValueError, match="missing"):
        autotune.lookup("lloyd", m=8, d=8)
    with pytest.raises(ValueError, match="unexpected"):
        autotune.lookup("lloyd", m=8, d=8, k=8, l=8)


# ---------------------------------------------------------------------------
# the sweep
# ---------------------------------------------------------------------------

def _stub_timer(times):
    """A deterministic time_fn: pops preset durations in call order."""
    seq = list(times)

    def time_fn(run_once):
        run_once()              # still executes the candidate
        return seq.pop(0)
    return time_fn


def test_tune_is_deterministic_under_a_fixed_timing_stub():
    cands = [TileConfig(128, 128), TileConfig(256, 256), TileConfig(64, 64)]
    picks = set()
    for _ in range(3):
        autotune.clear_caches()
        res = autotune.tune("lloyd", m=512, d=16, k=16, candidates=cands,
                            time_fn=_stub_timer([3e-3, 1e-3, 2e-3]),
                            save=False)
        picks.add(res.config)
    assert picks == {TileConfig(block_m=256, block_k=16)}  # 2nd = fastest
    # and an exact tie breaks on sweep order, deterministically
    autotune.clear_caches()
    res = autotune.tune("lloyd", m=512, d=16, k=16, candidates=cands,
                        time_fn=_stub_timer([1e-3, 1e-3, 1e-3]), save=False)
    assert res.config == TileConfig(block_m=128, block_k=16)


def test_tune_dedupes_candidates_through_the_clamps():
    # k=16: every block_k collapses to 16; block_m 512 and 1024 both clamp
    # within m=512 -> one effective config each for bm in {128, 512}
    cands = [TileConfig(512, 64), TileConfig(512, 256), TileConfig(1024, 512),
             TileConfig(128, 128)]
    res = autotune.tune("lloyd", m=512, d=16, k=16, candidates=cands,
                        time_fn=_stub_timer([1e-3] * 10), save=False)
    effective = [c.config for c in res.candidates]
    assert len(effective) == len(set(effective))
    # 512/1024 clamp to the one 512-row tile; + 128; + the auto-added
    # default (256) = 3 distinct kernels, not 4+ phantoms
    assert set(effective) == {TileConfig(512, 16), TileConfig(128, 16),
                              TileConfig(256, 16)}


def test_tune_rejects_numeric_mismatch(monkeypatch):
    """A candidate whose outputs disagree with the jnp oracle may never
    win, however fast it times."""
    real_case = autotune.CASES["lloyd"]

    def poisoned(dims, dtype, seed, interpret):
        case = real_case(dims, dtype, seed, interpret)

        def run(cfg):
            out = case.run(cfg)
            if cfg.block_m == 128:      # corrupt exactly one candidate
                return (out[0] + 1.0,) + tuple(out[1:])
            return out
        return autotune.Case(run, case.ref)

    monkeypatch.setitem(autotune.CASES, "lloyd", poisoned)
    res = autotune.tune("lloyd", m=512, d=16, k=16,
                        candidates=[TileConfig(128, 128),
                                    TileConfig(256, 256)],
                        time_fn=_stub_timer([1e-9, 1e-3]), save=False)
    assert res.config == TileConfig(block_m=256, block_k=16)
    rejected = [c for c in res.candidates if not c.ok]
    assert len(rejected) == 1
    assert rejected[0].config.block_m == 128
    assert rejected[0].time_s is None and "err" in rejected[0].note


def test_tune_all_rejected_is_an_error(monkeypatch):
    real_case = autotune.CASES["lloyd"]

    def broken(dims, dtype, seed, interpret):
        real = real_case(dims, dtype, seed, interpret)
        return autotune.Case(lambda cfg: (real.ref()[0] + 1.0,) * 5,
                             real.ref)
    monkeypatch.setitem(autotune.CASES, "lloyd", broken)
    with pytest.raises(RuntimeError, match="every candidate was rejected"):
        autotune.tune("lloyd", m=512, d=16, k=16,
                      candidates=[TileConfig(256, 256)], save=False)


def test_tune_winner_never_loses_to_default_and_caches():
    res = autotune.tune("lloyd", m=512, d=16, k=16,
                        candidates=[TileConfig(64, 64)],    # default auto-joins
                        time_fn=_stub_timer([5e-3, 1e-3]), save=False)
    assert res.speedup_vs_default >= 1.0
    assert res.config == TileConfig(block_m=256, block_k=16)  # the default won
    # the winner landed in the in-process cache under the same key
    cfg, src = autotune.lookup("lloyd", m=512, d=16, k=16, with_source=True)
    assert (src, cfg) == ("memory", res.config)
    assert cfg == autotune.TileConfig.from_dict(
        json.loads(json.dumps(res.config.to_dict())))   # JSON round-trip


@pytest.mark.parametrize("kernel,dims", [
    ("assign", dict(m=512, d=16, k=16)),
    ("centroid", dict(m=512, d=16, k=16)),
    ("scan", dict(b=2, l=300, msub=4, c=16)),
])
def test_tune_sweeps_every_kernel(kernel, dims):
    res = autotune.tune(kernel, candidates=None, iters=1, warmup=0,
                        save=False, **dims,
                        time_fn=None if kernel == "scan" else
                        _stub_timer([1e-3] * 32))
    assert any(res.config)
    assert res.speedup_vs_default >= 1.0
    assert all(c.ok for c in res.candidates)


# ---------------------------------------------------------------------------
# scan block_l-from-tuner regression
# ---------------------------------------------------------------------------

def test_scan_tuner_block_l_interpret_parity(rng):
    from repro.kernels.ref import adc_scan_ref
    from repro.kernels.scan import adc_scan_pallas
    luts = jnp.asarray(rng.normal(size=(3, 8, 16)), jnp.float32)
    codes = jnp.asarray(rng.integers(0, 16, size=(3, 500, 8)), jnp.int32)
    want = adc_scan_ref(luts, codes)
    got_auto = adc_scan_pallas(luts, codes)             # tuner-resolved
    np.testing.assert_allclose(np.asarray(got_auto), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    for bl in (64, 128, 1024):                          # explicit pins
        got = adc_scan_pallas(luts, codes, block_l=bl)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(got_auto))


def test_scan_block_l_none_consults_the_cache(tmp_path):
    """A persistent-cache winner actually steers the kernel's tile."""
    from repro.kernels.scan import adc_scan_pallas
    seen = {}
    key = autotune.cache_key("scan", b=2, l=256, msub=4, c=16)
    autotune.save_entry(key, TileConfig(block_l=64), path=tmp_path / "t.json")
    autotune.clear_caches()
    orig_lookup = autotune.lookup

    def spying(kernel, **kw):
        cfg = orig_lookup(kernel, path=tmp_path / "t.json", **kw)
        seen["cfg"] = cfg
        return cfg
    try:
        autotune.lookup = spying
        luts = jnp.zeros((2, 4, 16), jnp.float32)
        codes = jnp.zeros((2, 256, 4), jnp.int32)
        adc_scan_pallas(luts, codes)
    finally:
        autotune.lookup = orig_lookup
    assert seen["cfg"] == TileConfig(block_l=64)


# ---------------------------------------------------------------------------
# pallas_tuned backend
# ---------------------------------------------------------------------------

def test_pallas_tuned_registered():
    assert "pallas_tuned" in available_backends()
    be = get_backend("pallas_tuned")
    assert isinstance(be, PallasTunedBackend)
    assert isinstance(be, PallasFusedBackend)


def test_with_k_hint_is_functional_and_hashable():
    be = get_backend("pallas_tuned")
    b32 = be.with_k_hint(32)
    assert b32 is not be and b32.k_hint == 32 and be.k_hint is None
    assert b32 is b32.with_k_hint(32)               # idempotent
    # structural eq/hash: two same-hint instances key one jit cache entry
    assert b32 == PallasTunedBackend(k_hint=32)
    assert hash(b32) == hash(PallasTunedBackend(k_hint=32))
    assert b32 != PallasTunedBackend(k_hint=64)


def test_pallas_tuned_bit_for_bit_equals_fused_at_equal_tiles(rng,
                                                              monkeypatch):
    """THE parity pin: identical tiles -> the tuned backend is the fused
    backend, bit for bit, through a full kmeans fit."""
    from repro.core import kmeans
    monkeypatch.setattr(
        autotune, "lookup",
        lambda kernel, **kw: TileConfig(block_m=256, block_k=256))
    x = jnp.asarray(rng.normal(size=(1500, 24)), jnp.float32)
    key = jax.random.PRNGKey(7)
    fused = kmeans(x, 32, iters=5, key=key,
                   backend=PallasFusedBackend(block_m=256, block_k=256))
    tuned = kmeans(x, 32, iters=5, key=key,
                   backend=get_backend("pallas_tuned").with_k_hint(32))
    np.testing.assert_array_equal(np.asarray(fused.centers),
                                  np.asarray(tuned.centers))
    np.testing.assert_array_equal(np.asarray(fused.assignment),
                                  np.asarray(tuned.assignment))
    assert float(fused.sse) == float(tuned.sse)


def test_pallas_tuned_step_matches_oracle(rng):
    from repro.kernels.ref import lloyd_step_ref
    x = jnp.asarray(rng.normal(size=(1000, 17)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.5, 1.5, 1000), jnp.float32)
    c = jnp.asarray(rng.normal(size=(13, 17)), jnp.float32)
    be = get_backend("pallas_tuned").with_k_hint(13)
    prep = be.prepare(x, w)
    sums, counts, sse = be.step(prep, c)
    rsums, rcounts, rsse, _, _ = lloyd_step_ref(x, w, c)
    np.testing.assert_allclose(np.asarray(sums), np.asarray(rsums),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(counts), np.asarray(rcounts),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(sse), float(rsse), rtol=1e-4)
    idx, dist = be.assign(prep, c)
    _, _, _, ridx, _ = lloyd_step_ref(x, w, c)
    assert (np.asarray(idx) == np.asarray(ridx)).mean() > 0.99


def test_plan_threads_k_hint_and_prewarms(monkeypatch):
    from repro.api import plan
    from repro.core.spec import ClusterSpec
    monkeypatch.setenv("REPRO_KMEANS_BACKEND", "pallas_tuned")
    calls = []
    orig = autotune.prewarm
    monkeypatch.setattr(autotune, "prewarm",
                        lambda kernel, **kw: calls.append((kernel, kw))
                        or orig(kernel, **kw))
    spec = ClusterSpec.make(40)
    pl = plan(spec, data_shape=(4096, 32))
    assert isinstance(pl.backend, PallasTunedBackend)
    assert pl.backend.k_hint == 40
    assert calls == [("lloyd", {"m": 4096, "d": 32, "k": 40})]


# ---------------------------------------------------------------------------
# the bench campaign surface (smoke-level: it is CI's own entry point)
# ---------------------------------------------------------------------------

def test_sweep_point_artifact_schema(tmp_path):
    from benchmarks.bench_kernels import sweep_point
    e = sweep_point("lloyd", 512, 16, 16,
                    candidates=({"block_m": 256, "block_k": 256},
                                {"block_m": 128, "block_k": 128}),
                    iters=1, warmup=0, save=False, out_dir=tmp_path)
    assert e["bench"] == "tune" and e["speedup_vs_default"] >= 1.0
    assert e["numerics_verified"] and e["n_candidates"] == 2
    # interpreted timings carry no roofline: the roof table knows devices
    # only, and the CPU is none of them
    assert e["mode"] == "interpret" and e["roofline"] is None
    on_disk = json.loads((tmp_path / "BENCH_tune_lloyd_M512_d16_K16.json")
                         .read_text())
    assert on_disk["config"] == e["config"]
    # and the trajectory layer ingests it under the tune kind
    from benchmarks.trajectory import normalize
    pts = normalize(on_disk, "BENCH_tune_lloyd_M512_d16_K16.json")
    assert len(pts) == 1 and pts[0]["bench"] == "tune"
    assert "speedup_vs_default" in pts[0]["metrics"]


def test_check_defaults_passes():
    from benchmarks.bench_kernels import check_defaults
    assert check_defaults() > 0
