"""The readers of the program's stage scopes, on a small recorded trace.

``data/fit_scopes.trace.json.gz``: three ``bench_step`` spans of the
jitted sampled fit (``execution.donate``; the paper cell's spec at 16,000
x 2 points, k=64, 16 partitions), each step the fit loop's own (a device
copy of the points, then ``SampledKMeans.fit``), traced on one TPU v5e
and kept with the event fields the reduction reads, inside the window.
Its operations carry the program's scopes (``fold``, ``kmeans_init``,
``merge``, ``sse``), its kernels their names (``…/lloyd/pallas_call``),
and its host the spans ``SampledKMeans.fit``, ``plan`` and ``fit``.  The
numbers below were read from that trace once; the reduction and the
readers have to give them again.

In this trace the device's clock runs about 1.1 ms ahead of the host's
(each step's copy of the points shows on the device before its dispatch
on the host), so its idle gaps, each some hundreds of microseconds to
2 ms, start at host times outside the program's spans and are named
``host idle``; the naming of a gap that does start inside ``plan`` is
checked on the recorded ``plan`` spans themselves.
"""
import pathlib
import shutil
import types

import pytest

import harness
import tracered

DATA = pathlib.Path(__file__).resolve().parent / "data"

STEPS = 3
WINDOW_S = 0.018819370000000002
BUSY_S = 0.009515101953999974
SEED_MS = 1.6567349213344158
MERGE_MS = 0.8648627680002426
FOLD_MS = 2.280925674000845
LLOYD_SHARE = 37.74376274013837
# the two seeding loops' own device time (the fold's D² loop over
# f32[16,200,2], the merge restarts' over f32[4,64,2]); a while op carries
# no name stack of its own, only the operations in its body do
SEED_WHILES_S = 0.004208766250000008 + 0.0007526152340000048
HOST_IDLE_S = 0.008893990858000033


def _reduce(tmp_path_factory, name):
    path = tmp_path_factory.mktemp("trace") / name
    shutil.copyfile(DATA / name, path)
    return tracered.reduce(tracered.load(str(path)), 1)


@pytest.fixture(scope="module")
def scoped(tmp_path_factory):
    return _reduce(tmp_path_factory, "fit_scopes.trace.json.gz")


@pytest.fixture(scope="module")
def unscoped(tmp_path_factory):
    return _reduce(tmp_path_factory, "fit_tiny.trace.json.gz")


def read(metric, reduction):
    mod = harness.load_module(harness.BENCH / "metrics" / f"{metric}.py")
    return mod.read(types.SimpleNamespace(trace=reduction))


def test_window(scoped):
    assert scoped.steps == STEPS
    assert scoped.n_devices == 1
    assert scoped.window_s == pytest.approx(WINDOW_S, rel=1e-9)
    assert scoped.busy_s == pytest.approx(BUSY_S, rel=1e-9)


@pytest.mark.parametrize("metric,value", [
    ("seed_ms.fit", SEED_MS), ("merge_ms.fit", MERGE_MS),
    ("fold_ms.fit", FOLD_MS), ("lloyd_share.fit", LLOYD_SHARE),
])
def test_readers(scoped, metric, value):
    assert read(metric, scoped) == pytest.approx(value, rel=1e-9)


def test_seeding_lies_inside_fold_and_merge(scoped):
    seed = read("seed_ms.fit", scoped)
    fold = read("fold_ms.fit", scoped)
    merge = read("merge_ms.fit", scoped)
    assert 0 < seed <= fold + merge
    assert (fold + merge) * scoped.steps <= 1e3 * scoped.busy_s


def test_accepted_patterns_still_find_their_ops(scoped):
    """``name=`` on the kernel and the new scopes hide nothing from the
    accepted readers' patterns."""
    lloyd = harness.load_module(harness.BENCH / "metrics" /
                                "lloyd_share.fit.py")
    assert scoped.kernel_s(lloyd.SCOPE, lloyd.SOURCE) > 0
    fold = harness.load_module(harness.BENCH / "metrics" / "fold_ms.fit.py")
    assert scoped.scope_s(fold.SCOPE) > 0
    assert any("/lloyd/pallas_call" in sc
               for ev in scoped.devices.values() for sc in ev.scope)


def test_seeding_scope_accounts_for_the_seeding_loops(scoped):
    seed_s = read("seed_ms.fit", scoped) * scoped.steps * 1e-3
    assert seed_s == pytest.approx(SEED_WHILES_S, rel=0.05)


def test_a_gap_inside_plan_is_named_plan(tmp_path_factory):
    """Every recorded ``plan`` span sits inside ``SampledKMeans.fit`` and
    names a gap that starts in it wherever no shorter host event covers
    that time."""
    path = tmp_path_factory.mktemp("trace") / "fit_scopes.trace.json.gz"
    shutil.copyfile(DATA / "fit_scopes.trace.json.gz", path)
    host = tracered.load(str(path))["host"]
    spans = [(n, s, e) for ev in host
             for n, s, e in zip(ev.names, ev.start, ev.end)]
    plans = [(s, e) for n, s, e in spans if n == "plan"]
    fits = [(s, e) for n, s, e in spans if n == "SampledKMeans.fit"]
    assert len(plans) == len(fits) == STEPS
    named = 0
    for s, e in plans:
        assert any(fs <= s and e <= fe for fs, fe in fits)
        shorter = [(a, b) for _, a, b in spans if b - a < e - s]
        # just inside plan, or where a shorter host event ends inside it
        for t in [s + 1.0] + sorted(b for _, b in shorter if s < b < e):
            if not any(a <= t < b for a, b in shorter):
                assert tracered._host_name_at(host, t) == "plan"
                named += 1
                break
    assert named == STEPS


def test_idle_gaps(scoped):
    gaps = {}
    for name, t in scoped.gaps:
        gaps[name] = gaps.get(name, 0.0) + t
    assert gaps["host idle"] == pytest.approx(HOST_IDLE_S, rel=1e-9)
    assert sum(gaps.values()) == pytest.approx(
        scoped.window_s - scoped.busy_s, rel=1e-6)


@pytest.mark.parametrize("metric", ["seed_ms.fit", "merge_ms.fit"])
def test_new_readers_find_nothing_in_an_unscoped_trace(unscoped, metric):
    assert unscoped.steps == 2
    assert read(metric, unscoped) is None
