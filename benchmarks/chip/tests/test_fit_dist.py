"""The four-chip sharded fit cell, ``bigann-4m-x4.fit-dist``, at a test
size on the devices JAX has here (one on the CPU).

* ``reference_dist`` refuses the spec fields and splits it does not
  implement.
* A sound run is ``correct`` and compiles nothing inside its window.
* The control (the plain reference in three bf16 passes) and the program
  with its distance kernels in one bf16 pass each fail a limit; the same
  readings at the cell's own size on the chip are what
  ``limits/bigann-4m-x4.fit-dist.json`` was set from (``control.py``).
* A program whose warm fit compiles is refused in set-up.
"""
import contextlib
import copy
import importlib
import json

import jax
import pytest

import control
import faults
import harness
import reference_dist
import run

CELL = "bigann-4m-x4.fit-dist"


def shrink(cell):
    """The cell on the devices here: 4 chunks of 1,024 rows a device, 64
    rows a partition, a merge of 16 centers."""
    n_dev = min(cell.chips, len(jax.devices()))
    cell.chips = n_dev
    cell.config["data"].update(n=4096 * n_dev, components=64)
    spec = cell.config["fit"]["spec"]
    spec["chunk"]["chunk_points"] = 1024
    spec["merge"]["k"] = 16
    return cell


@pytest.fixture
def small_cell(monkeypatch):
    real = harness.Cell.find.__func__

    def find(cls, name, bench=None):
        cell = real(cls, name, bench)
        return shrink(cell) if name == CELL else cell
    monkeypatch.setattr(harness.Cell, "find", classmethod(find))
    monkeypatch.setattr(harness, "require_tpu",
                        lambda chips: jax.devices()[:chips])
    jax.config.update("jax_enable_compilation_cache", False)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _spec():
    return copy.deepcopy(harness.Cell.find(CELL).config["fit"]["spec"])


@pytest.mark.parametrize("path,value", [
    (("execution", "mode"), "chunked"),
    (("execution", "merge_path"), "replicated"),
    (("chunk", "sse"), "exact"),
    (("partition", "scheme"), "unequal"),
    (("local", "init"), "kmeans||"),
    (("scale",), False),
    (("levels", 0, "scheme"), "unequal"),
    (("levels", 0, "stop"), {"tol": 1e-4}),
    (("merge", "surprise"), 1),
])
def test_reference_refuses_what_it_does_not_implement(path, value):
    spec = _spec()
    reference_dist.require_supported(spec, 4194304, 4)
    node = spec
    for p in path[:-1]:
        node = node[p]
    node[path[-1]] = value
    with pytest.raises(ValueError, match="reference_dist"):
        reference_dist.require_supported(spec, 4194304, 4)


def test_reference_refuses_early_pool_folds():
    """Eight chunks on a shard would be folded early through the level."""
    spec = _spec()
    with pytest.raises(ValueError, match="early pool folds"):
        reference_dist.require_supported(spec, 8 * 262144, 1)


def result(capsys, seed=7):
    assert run.main(["--workload", CELL, "--seed", str(seed),
                     "--seconds", "1", "--trace", "0"]) == 0
    out = capsys.readouterr()
    return json.loads(out.out.strip().splitlines()[-1]), out.err


def test_sound_run_is_correct(small_cell, capsys):
    res, err = result(capsys, seed=2**33 + 5)
    assert res["correct"] is True, res["checks"]
    assert "inside the window" not in err


@pytest.mark.parametrize("seed", [3, 2**33 + 1, 77])
def test_control_is_not_correct(small_cell, seed):
    c = harness.Cell.find(CELL)
    readings = control.control_readings(c, seed, jax.devices()[:c.chips])
    checks = harness.checks_from(readings, c.limits)
    assert not all(ch.ok for ch in checks), readings


@pytest.mark.parametrize("fault", [None, "distance_one_pass", "half_batch"])
def test_fault_is_caught(small_cell, capsys, monkeypatch, fault):
    """On the Pallas kernels (interpreted off a TPU, only when asked for):
    sound, the run is correct; with a fault planted, it is not."""
    backend = importlib.import_module("repro.core.backend")
    monkeypatch.setattr(backend, "_resolve_auto", lambda: "pallas_tuned")
    with (faults.planted(fault) if fault else contextlib.nullcontext()):
        res, _ = result(capsys)
    assert res["correct"] is (fault is None), res["checks"]


def test_program_that_compiles_every_fit_is_refused(small_cell, monkeypatch):
    """The reduce level run eagerly (its vmapped scans retraced on every
    call) compiles inside every fit: set-up stops."""
    distributed = importlib.import_module("repro.core.distributed")
    monkeypatch.setattr(distributed, "reduce_pool",
                        distributed.reduce_pool.__wrapped__)
    c = harness.Cell.find(CELL)
    driver = control.driver_module(c).Driver(c, 1, jax.devices()[:c.chips])
    with pytest.raises(RuntimeError, match="compiled"):
        driver.setup()
