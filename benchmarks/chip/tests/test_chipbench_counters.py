"""The readers of the hierarchy-build and refinement spans and of the round
counters, on a hand-built window of recorder events: exact values where the
events are there, None where they are not (as on a program without them)."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import counters as C  # noqa: E402
from benchmarks.chip.harness import HERE, load_module  # noqa: E402


def _span(name, t0, t1):
    return [{"ph": "B", "name": name, "ts": t0},
            {"ph": "E", "name": name, "ts": t1}]


def _count(name, value):
    return {"ph": "C", "name": name, "ts": 0.0, "value": value}


# two solves: spans in microseconds, counters as the engine emits them
EVENTS = (
    _span("hierarchy", 0, 9_000_000)
    + _span("cluster", 100, 2_000_100) + _span("contract", 2_000_100,
                                                 3_000_100)
    + _span("cluster", 4_000_000, 4_500_000)
    + _span("contract", 5_000_000, 5_250_000)
    + _span("views", 6_000_000, 6_400_000)
    + _span("views", 7_000_000, 7_100_000)
    + [_count("refine/rounds", 64), _count("refine/rounds_moved", 10),
       _count("refine/moves", 300), _count("refine/rounds", 16),
       _count("refine/rounds_moved", 6), _count("refine/moves", 20),
       _count("coarsen/lp_rounds", 8), _count("coarsen/lp_rounds_moved", 8),
       _count("coarsen/lp_rounds", 8), _count("coarsen/lp_rounds_moved", 4)]
)

EXPECTED = {
    "cluster_s": (2.0 + 0.5) / 2,
    "contract_s": (1.0 + 0.25) / 2,
    "views_s": (0.4 + 0.1) / 2,
    "refine_wasted_rounds": 100.0 * (1 - 16 / 80),
    "cluster_wasted_rounds": 100.0 * (1 - 12 / 16),
}

READERS = [f"{base}.{kind}" for base in EXPECTED
           for kind in ("graph", "hypergraph")
           if (base, kind) != ("cluster_wasted_rounds", "graph")]


def _read(metric, events):
    read = load_module(HERE / "metrics" / f"{metric}.py").read
    return read(SimpleNamespace(events=events, n_solves=2))


@pytest.mark.parametrize("metric", READERS)
def test_reader_exact_on_hand_built_events(metric):
    assert _read(metric, EVENTS) == pytest.approx(
        EXPECTED[metric.split(".")[0]], rel=1e-12)


@pytest.mark.parametrize("metric", READERS)
def test_reader_none_without_events(metric):
    assert _read(metric, []) is None


def test_wasted_rounds_needs_both_counters():
    # the configured-rounds counter alone (no device counts) reads nothing
    only_rounds = [_count("refine/rounds", 6), _count("refine/moves", 3)]
    assert C.wasted_rounds(only_rounds, "refine/") is None
    assert C.wasted_rounds([_count("refine/rounds", 0),
                            _count("refine/rounds_moved", 0)],
                           "refine/") is None
    assert C.wasted_rounds([_count("refine/rounds", 4),
                            _count("refine/rounds_moved", 0)],
                           "refine/") == 100.0
    assert C.counter_total(EVENTS, "refine/moves") == 320
    assert C.counter_total(EVENTS, "nothing") is None
