"""A run with the timed path broken underneath reports ``correct: false``.

Each fault (``faults.py``) is planted in the program's own code for one
run of a cell shrunk to a test size (the chip check is skipped); the
unbroken run of the same cell and seed reports ``correct: true``.  The
faults: a Lloyd step that returns its centers unchanged; half of each
partition's points left out of the local k-means; an answer altered where
it is produced; the distance kernels at three bf16 passes; for the search,
a stale answer (the previous request's), half of a request's queries
answered for all of it, and altered ids.
"""
import contextlib
import json

import pytest

import faults
import run

FIT_CELLS = ("paper-500k.fit", "sift-1m.fit")
SEARCH_CELL = "sift-1m.search-np10"


def result(capsys, cell, seed=7):
    assert run.main(["--workload", cell, "--seed", str(seed),
                     "--seconds", "2", "--trace", "0"]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", FIT_CELLS + (SEARCH_CELL,))
def test_sound_run_is_correct(small_cells, capsys, cell):
    assert result(capsys, cell)["correct"] is True


@pytest.mark.parametrize("cell", FIT_CELLS)
@pytest.mark.parametrize("fault", ["step_unchanged", "half_batch",
                                   "answer_altered"])
def test_fit_fault_is_caught(small_cells, capsys, cell, fault):
    with faults.planted(fault):
        res = result(capsys, cell)
    assert res["correct"] is False, res["checks"]


# three bf16 passes move no point of the sift cell's test size; at its own
# size they move some tens in a million (control.py --fault, on the chip)
@pytest.mark.parametrize("cell,fault", [
    (cell, fault) for cell in FIT_CELLS
    for fault in (None, "distance_one_pass", "distance_three_pass")
    if (cell, fault) != ("sift-1m.fit", "distance_three_pass")])
def test_kernel_precision_is_checked(small_cells, capsys, monkeypatch, cell,
                                     fault):
    """On the Pallas kernels, which off a TPU run interpreted and only when
    asked for: sound, the run is correct; with the distance kernels at
    lower precision, it is not."""
    import importlib
    backend = importlib.import_module("repro.core.backend")
    monkeypatch.setattr(backend, "_resolve_auto", lambda: "pallas_tuned")
    with (faults.planted(fault) if fault else contextlib.nullcontext()):
        res = result(capsys, cell)
    assert res["correct"] is (fault is None), res["checks"]


@pytest.mark.parametrize("fault", ["search_stale", "search_half",
                                   "search_altered"])
def test_search_fault_is_caught(small_cells, capsys, fault):
    with faults.planted(fault):
        res = result(capsys, SEARCH_CELL)
    assert res["correct"] is False, res["checks"]
