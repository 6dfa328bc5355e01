"""A run whose timed path is broken underneath comes out not correct.

Each test drives the rest of a run (``harness.run_cell``, without the look
for a chip) on a tiny cell on the CPU, with one fault planted in the
program's path:

- the state returned unchanged: every vertex left in block 0, where a
  partition starts;
- half of the batch left out: labels only for the first half of the
  vertices;
- an answer altered where it is produced: one vertex moved to another block
  after the program counted its objective.

The cells run on one chip, so there is no exchange between chips to leave
out.  The control, the program with its balance guarantee loosened to the
configuration's ``control`` ε, comes out not correct too; ``control.py``
reads it at the cells' own size on the chip.
"""
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(Path(__file__).parent)]

from benchmarks.chip import harness  # noqa: E402
from chipbench_tiny import CELLS, cpu_device, tiny_data  # noqa: E402


@pytest.fixture(scope="module")
def data(tmp_path_factory):
    return tiny_data(tmp_path_factory.mktemp("faults"))


def _run(bench, data, name):
    cell = harness.load_cell(name, bench, data)
    return cell, harness.run_cell(cell, 2 ** 35 + 9, 0.1, False,
                                  time.perf_counter(), cpu_device)


def unchanged(tool, inst):
    def solve(prepared, eps, seed, report=None):
        return 0, np.zeros(len(inst.vwgt), np.int64)
    return solve


def half_left_out(tool, inst):
    real = tool.solve

    def solve(prepared, eps, seed, report=None):
        obj, part = real(prepared, eps, seed, report)
        return obj, np.asarray(part)[:len(inst.vwgt) // 2]
    return solve


def answer_altered(tool, inst):
    real = tool.solve

    def solve(prepared, eps, seed, report=None):
        obj, part = real(prepared, eps, seed, report)
        part = np.array(part)
        k = int(part.max()) + 1
        for v in range(len(part)):
            moved = part.copy()
            moved[v] = (moved[v] + 1) % k
            if tool.objective(inst, moved) != obj:
                return obj, moved
        raise AssertionError("no single move changes the objective")
    return solve


@pytest.mark.parametrize("name", sorted(CELLS))
def test_sound_run_is_correct(data, name):
    _, out = _run(*data, name)
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("fault,check", [
    (unchanged, "overweight"), (half_left_out, "bad_labels"),
    (answer_altered, "objective_gap")])
@pytest.mark.parametrize("name", sorted(CELLS))
def test_broken_path_is_not_correct(data, monkeypatch, name, fault, check):
    bench, d = data
    cell = harness.load_cell(name, bench, d)
    monkeypatch.setattr(cell.tool, "solve", fault(cell.tool, cell.instance()))
    out = harness.run_cell(cell, 2 ** 35 + 9, 0.1, False, time.perf_counter(),
                           cpu_device)
    c = out["checks"][check]
    assert not out["correct"] and c["value"] > c["limit"], out["checks"]
    assert out["failed"] == out["attempted"] >= 1


@pytest.mark.parametrize("name", sorted(CELLS))
def test_control_is_not_correct(data, monkeypatch, name):
    """The timed path run at the control's looser ε overfills a block."""
    bench, d = data
    cell = harness.load_cell(name, bench, d)
    real = cell.tool.solve
    loose = float(cell.config["control"]["eps"])
    assert loose > cell.eps

    def solve(prepared, eps, seed, report=None):
        return real(prepared, loose, seed, report)
    monkeypatch.setattr(cell.tool, "solve", solve)
    out = harness.run_cell(cell, 2 ** 35 + 9, 0.1, False, time.perf_counter(),
                           cpu_device)
    c = out["checks"]["overweight"]
    assert not out["correct"] and c["value"] > 0, out["checks"]
