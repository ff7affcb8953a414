"""Self-check of the trace reduction on a small recorded trace.

``data/fit_tiny.trace.json.gz``: two ``bench_step`` spans of a sampled fit
(32,000 x 2 points, k=64, 16 partitions) traced on one TPU v5e, kept with
the event fields the reduction reads.  The numbers below were read from
that trace once; the reduction has to give them again.
"""
import pathlib
import shutil

import numpy as np
import pytest

import harness
import tracered

DATA = pathlib.Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def reduction(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "fit_tiny.trace.json.gz"
    shutil.copyfile(DATA / "fit_tiny.trace.json.gz", path)
    assert tracered.find_trace(str(path.parent)) == str(path)
    return tracered.reduce(tracered.load(str(path)), 1)


def reader(metric):
    return harness.load_module(harness.BENCH / "metrics" / f"{metric}.py")


def test_window_and_busy(reduction):
    assert reduction.steps == 2
    assert reduction.n_devices == 1
    assert reduction.window_s == pytest.approx(1.531714146, rel=1e-9)
    assert reduction.busy_s == pytest.approx(0.020366889376, rel=1e-9)
    assert reduction.collective_s == 0.0


def test_kernel_and_scope_times(reduction):
    lloyd = reader("lloyd_share.fit")
    assert reduction.kernel_s(lloyd.SCOPE, lloyd.SOURCE) == pytest.approx(
        0.008320082108, rel=1e-9)
    roof = reader("lloyd_roofline.fit")
    assert (roof.SCOPE, roof.SOURCE) == (lloyd.SCOPE, lloyd.SOURCE)
    assert reduction.scope_s(reader("fold_ms.fit").SCOPE) == pytest.approx(
        0.016641386090, rel=1e-9)
    # the scan kernel is not in a fit's trace
    scan = reader("scan_roofline.search")
    assert reduction.kernel_s(scan.SCOPE, scan.SOURCE) == 0.0


def test_gaps_cover_the_idle_time(reduction):
    idle = sum(t for _, t in reduction.gaps)
    assert idle == pytest.approx(reduction.window_s - reduction.busy_s,
                                 rel=1e-6)
    bd = reduction.breakdown()
    assert 0 < len(bd["device_ops"]) <= 10
    assert 0 < len(bd["idle_gaps"]) <= 10


def test_union_merges_nested_and_clips():
    s = np.array([0.0, 1.0, 2.0, 10.0, 20.0])
    e = np.array([5.0, 2.0, 6.0, 12.0, 30.0])
    assert tracered.union(s, e, 1.0, 25.0) == [[1.0, 6.0], [10.0, 12.0],
                                              [20.0, 25.0]]
