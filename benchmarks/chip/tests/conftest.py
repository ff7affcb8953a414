"""Shared set-up for the benchmark's own tests: the benchmark's modules on
the path, and cells shrunk to a size a CPU test can hold."""
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

harness.prepare_env()


# A cell whose files are in place but which BENCHMARK.json does not list
# yet: it has no chip measurement (PERF.md, Open questions).  The tests
# drive it all the same, so that its files stay sound.
PENDING = [{"name": "sift-1m.search-np10", "config": "sift-1m",
            "traffic": "search-np10", "chips": 1}]


def shrink(cell):
    """The cell at a test size: same generator, spec and traffic, fewer
    rows, clusters and lists."""
    data = cell.config["data"]
    if cell.traffic["driver"] == "fit_loop":
        if data["generator"] == "blobs":
            data.update(n=8000, n_clusters=16)
        else:
            data.update(n=16384, components=64)
        cell.config["fit"]["spec"]["merge"]["k"] = 16
        cell.config["fit"]["spec"]["partition"]["n_sub"] = 8
    else:
        data.update(n=16384, components=64, queries=640)
        cell.config["index"]["spec"]["coarse"]["merge"]["k"] = 64
        cell.config["index"]["spec"]["train_points"] = 8192
        cell.traffic.update(nprobe=4)
        cell.traffic["arrivals"] = dict(cell.traffic["arrivals"],
                                        rate_per_s=20.0)
    return cell


@pytest.fixture
def small_cells(monkeypatch):
    """Cells found by name come back shrunk, and the chip check passes on
    whatever device JAX has; compiled programs do not outlive the test."""
    import jax
    real = harness.Cell.find.__func__

    def find(cls, name, bench=None):
        bench = bench or harness.load_json(harness.ROOT / "BENCHMARK.json")
        listed = {w["name"] for w in bench["workloads"]}
        bench["workloads"] += [w for w in PENDING if w["name"] not in listed]
        return shrink(real(cls, name, bench))
    monkeypatch.setattr(harness.Cell, "find", classmethod(find))
    monkeypatch.setattr(harness, "require_tpu",
                        lambda chips: jax.devices()[:chips])
    jax.config.update("jax_enable_compilation_cache", False)
    jax.clear_caches()
    yield
    jax.clear_caches()
