"""Spans, device-counted round moves and named scopes inside hierarchy build
and refinement (DESIGN.md §11): ``cluster``/``contract``/``views`` spans,
the ``refine/*`` and ``coarsen/lp_*`` counters read back from the round
programs only for an enabled recorder, the scopes the round programs carry
in their metadata, and span attributes in the profiler trace."""
import hashlib
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import interface
from repro.core import lp as L
from repro.core import refine as R
from repro.core.hypergraph import metrics as HM
from repro.core.hypergraph import refine as HR
from repro.core.hypergraph.driver import kahypar
from repro.core.kaffpa import kaffpa
from repro.io.generators import barabasi_albert, grid2d, planted_hypergraph

GRID24 = grid2d(24, 24)
HP400 = planted_hypergraph(400, 600, blocks=4, seed=11)


def _run_graph(rec, mode=interface.FAST):
    g = GRID24
    return interface.kaffpa(g.n, None, g.xadj, None, g.adjncy, 4, 0.03,
                            seed=2, mode=mode, report=rec)


def _run_hyper(rec):
    hg = HP400
    return interface.kahypar(hg.n, hg.m, None, None, hg.eptr, hg.eind, 4,
                             0.03, seed=1, mode=interface.FAST, report=rec)


RUNS = {"kaffpa": _run_graph, "kahypar": _run_hyper}


def _paths(events, name):
    """The open span names (outermost first) at each ``name`` begin."""
    stack, out = [], []
    for ev in events:
        if ev["ph"] == "B":
            if ev["name"] == name:
                out.append(list(stack))
            stack.append(ev["name"])
        elif ev["ph"] == "E":
            assert stack.pop() == ev["name"]
    return out


# -- spans --------------------------------------------------------------------

@pytest.mark.parametrize("tool", sorted(RUNS))
def test_cluster_contract_views_spans_nest(tool):
    rec = obs.Recorder(tool, compile_counters=False)
    RUNS[tool](rec)
    for name in ("cluster", "contract"):
        paths = _paths(rec.events, name)
        assert paths, name
        assert all(p[-2:] == ["hierarchy", "coarsen"] for p in paths), paths
    views = _paths(rec.events, "views")
    assert views
    assert all({"uncoarsen", "initial_tournament"} & set(p) for p in views)
    for ev in rec.events:
        if ev["ph"] == "B" and ev["name"] in ("cluster", "contract"):
            assert set(ev["args"]) == {"level", "n"}
        if ev["ph"] == "B" and ev["name"] == "views":
            assert set(ev["args"]) == {"n"}


# -- device-counted round moves -----------------------------------------------

def _spy(monkeypatch, module, attr, row_rounds):
    """Wrap a round program; ``row_rounds(args)`` gives one call's
    b_pad × rounds_bucket from its arguments."""
    orig = getattr(module, attr)
    seen = []

    def spy(*args, **kw):
        seen.append(row_rounds(args))
        return orig(*args, **kw)
    monkeypatch.setattr(module, attr, spy)
    return seen


ROW_ROUNDS = {
    # kway: rkeys (b_pad, rounds_bucket, 2)
    "kaffpa": (R, "_refine_scan_batch",
               lambda a: a[3].shape[0] * a[3].shape[1]),
    # hypergraph: keys (b_pad, 2), rounds positional after k_pad
    "kahypar": (HR, "_hyper_refine_scan_batch",
                lambda a: a[1].shape[0] * a[6]),
}


@pytest.mark.parametrize("tool", sorted(RUNS))
def test_refine_rounds_count_every_row_round(monkeypatch, tool):
    seen = _spy(monkeypatch, *ROW_ROUNDS[tool])
    rec = obs.Recorder(tool, compile_counters=False)
    RUNS[tool](rec)
    c = rec.counters()
    assert len(seen) >= 2                    # tournament + refines
    assert c["refine/rounds"] == sum(seen)
    assert 0 < c["refine/rounds_moved"] <= c["refine/rounds"]
    assert c["refine/moves"] >= c["refine/rounds_moved"]


def test_refine_kway_moves_cover_the_label_diff():
    g = GRID24
    part = np.random.default_rng(4).integers(0, 4, g.n)
    rec = obs.Recorder("single", compile_counters=False)
    out = R.refine_kway(g, part, 4, 0.03, rounds=6, seed=1, batch_floor=2,
                        rounds_bucket=8, recorder=rec)
    c = rec.counters()
    assert c["refine/rounds"] == 2 * 8       # b_pad x rounds_bucket
    assert c["refine/rounds_moved"] <= 6     # real row, live rounds only
    assert c["refine/moves"] >= np.count_nonzero(out != part) > 0


def test_refine_hypergraph_moves_cover_the_label_diff():
    hg = HP400
    part = np.random.default_rng(5).integers(0, 4, hg.n)
    rec = obs.Recorder("single", compile_counters=False)
    out = HR.refine_hypergraph(hg, part, 4, 0.03, rounds=6, seed=1,
                               batch_floor=4, recorder=rec)
    c = rec.counters()
    assert c["refine/rounds"] == 4 * 6
    assert c["refine/rounds_moved"] <= 6
    assert c["refine/moves"] >= np.count_nonzero(out != part) > 0
    assert HM.connectivity(hg, out) <= HM.connectivity(hg, part)


def test_cluster_rounds_counted_per_call():
    rec = obs.Recorder("lp", compile_counters=False)
    g = barabasi_albert(300, 3, seed=2)
    clusters = L.size_constrained_lp(g, 12.0, iters=5, seed=3, recorder=rec)
    c = rec.counters()
    assert c["coarsen/lp_rounds"] == 5
    assert 0 < c["coarsen/lp_rounds_moved"] <= 5
    assert c["coarsen/lp_moves"] >= g.n - len(np.unique(clusters)) > 0


def test_counts_read_back_only_when_enabled(monkeypatch):
    fetched = []
    orig = L.count_round_moves
    monkeypatch.setattr(L, "count_round_moves",
                        lambda *a: (fetched.append(a[1]), orig(*a)))
    _run_graph(None, mode=interface.FASTSOCIAL)
    _run_hyper(None)
    assert fetched == []                     # obs.NULL: no counter transfer
    rec = obs.Recorder("on", compile_counters=False)
    _run_graph(rec, mode=interface.FASTSOCIAL)
    assert {"refine/", "coarsen/lp_"} <= set(fetched)
    assert rec.counters()["coarsen/lp_rounds"] > 0


# -- the disabled path is the parent's program --------------------------------

def _digest(part) -> str:
    return hashlib.sha256(np.asarray(part, np.int64).tobytes()).hexdigest()[:16]


PINNED = {   # partitions before the round counters and scopes were added
    "grid24_fast_k4_s2": (lambda: kaffpa(GRID24, 4, 0.03, "fast", seed=2),
                          "0b38e63a02c11cbf"),
    "ba2k_ecosocial_k8_s1": (
        lambda: kaffpa(barabasi_albert(2048, 4, seed=1), 8, 0.03,
                       "ecosocial", seed=1), "0c437da5611854d8"),
    "hp400_eco_k4_s1": (lambda: kahypar(HP400, 4, 0.03, "eco", seed=1),
                        "4e55c629d5715a1e"),
}


@pytest.mark.parametrize("case", sorted(PINNED))
def test_null_recorder_partitions_bit_identical(case):
    assert obs.current() is obs.NULL
    solve, digest = PINNED[case]
    assert _digest(solve()) == digest


# -- named scopes in the round programs ---------------------------------------

def _kway_text():
    from repro.analysis.registry import _build_kway
    fn, args = _build_kway(False)
    return jax.jit(fn).lower(*args).as_text(debug_info=True)


def _hyper_text():
    from repro.analysis.registry import _build_hyper
    fn, args = _build_hyper("km1")
    return jax.jit(fn).lower(*args).as_text(debug_info=True)


def _cluster_text():
    from repro.core.csr import to_coo
    coo = to_coo(grid2d(6, 6))
    labs = np.arange(coo.n_pad, dtype=np.int32)
    cap = np.full(coo.n_pad, 8.0, np.float32)
    return L._cluster_lp_round.lower(
        coo, labs, cap, np.asarray(jax.random.PRNGKey(0)), np.int32(1),
        np.zeros(4, np.int32), iters=4).as_text(debug_info=True)


SCOPES = {
    "refine_scan_batch": (_kway_text, ("affinity", "gain", "accept", "sizes",
                                       "cut", "reach")),
    "hyper_refine_scan_batch": (_hyper_text, ("affinity", "gain", "accept",
                                              "sizes", "cut")),
    "cluster_lp_round": (_cluster_text, ("rating", "accept", "lexsort")),
}


@pytest.mark.parametrize("program", sorted(SCOPES))
def test_named_scopes_in_round_programs(program):
    text_of, scopes = SCOPES[program]
    text = text_of()
    for s in scopes:     # an op's location: "<outer scopes>/<scope>/<op>"
        assert f"/{s}/" in text or f'"{s}/' in text, s


# -- span attributes in the profiler trace ------------------------------------

def test_span_attributes_become_trace_stats(tmp_path):
    from jax.profiler import ProfileData
    rec = obs.Recorder("xprof", compile_counters=False, annotate_xprof=True)
    jax.profiler.start_trace(str(tmp_path))
    with rec.span("refine", level=3, n=100):
        jax.numpy.ones(4).block_until_ready()
    jax.profiler.stop_trace()
    path = sorted(Path(tmp_path).rglob("*.xplane.pb"))[-1]
    found = [dict(ev.stats)
             for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name == "refine"]
    assert len(found) == 1
    assert found[0]["level"] == 3 and found[0]["n"] == 100
