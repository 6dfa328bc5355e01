"""The benchmark's plain references on hand-counted cases, the mesh
generator's copy against the program's generator, and the Graph 500
column-net generator against its specification."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import reference as R  # noqa: E402
from benchmarks.chip.harness import HERE, load_module  # noqa: E402


def path4():
    """0 -1- 1 -2- 2 -3- 3 (edge weights 1, 2, 3)."""
    return R.graph_from_edges(4, [0, 1, 2], [1, 2, 3], [1, 2, 3])


def hyper4():
    """nets {0,1,2} w2, {2,3} w1, {0,3} w5."""
    return R.hypergraph_from_pins(4, [0, 0, 0, 1, 1, 2, 2],
                                  [0, 1, 2, 2, 3, 0, 3], [2, 1, 5])


def test_edge_cut_hand_counted():
    g = path4()
    assert R.edge_cut(g, [0, 0, 1, 1]) == 2
    assert R.edge_cut(g, [0, 1, 0, 1]) == 6
    assert R.edge_cut(g, [1, 1, 1, 1]) == 0


def test_km1_hand_counted():
    hg = hyper4()
    assert R.km1(hg, [0, 0, 1, 1]) == 2 + 0 + 5
    assert R.km1(hg, [0, 1, 2, 3]) == 2 * 2 + 1 + 5
    assert R.km1(hg, [0, 0, 0, 0]) == 0


def test_balance_at_eps():
    vw = np.ones(4, np.int64)
    assert R.block_cap(vw, 2, 0.03) == pytest.approx(2.06)
    assert R.overweight(vw, [0, 0, 1, 1], 2, 0.03) == 0.0
    assert R.overweight(vw, [0, 0, 0, 1], 2, 0.03) == pytest.approx(3 - 2.06)
    assert R.overweight(np.array([5, 1, 1, 1]), [0, 1, 1, 1], 2,
                        0.0) == pytest.approx(1.0)
    assert R.overweight(vw, [0, 0, 0, 0], 4, 3.0) == 0.0


def test_bad_labels():
    assert R.bad_labels([0, 1, 1, 0], 4, 2) == 0
    assert R.bad_labels([0, 2, -1, 0], 4, 2) == 2
    assert R.bad_labels([0, 1], 4, 2) == 2
    assert R.bad_labels(np.array([0.0, 1.0, 1.0, 0.0]), 4, 2) == 4


def test_grid2d_copy_equals_program_generator():
    from repro.io.generators import grid2d
    gen = load_module(HERE / "instances" / "grid2d.py")
    for rows, cols in ((7, 5), (1024, 1024)):
        mine, theirs = gen.build({"rows": rows, "cols": cols}), \
            grid2d(rows, cols)
        assert mine.n == theirs.n
        for a in ("xadj", "adjncy", "vwgt", "adjwgt"):
            np.testing.assert_array_equal(getattr(mine, a),
                                          getattr(theirs, a))


G500 = {"edgefactor": 16, "A": 0.57, "B": 0.19, "C": 0.19}


def _g500(scale, seed):
    gen = load_module(HERE / "instances" / "graph500_colnet.py")
    return gen, gen.build(dict(G500, scale=scale, seed=seed))


def test_graph500_edges_follow_the_specification():
    """The specification's generator, restated: M = edgefactor · 2^scale
    edges; at each level the row bit is 1 with probability C + D and the
    column bit with C/(C + D) or B/(A + B); then the ids are permuted.
    The first level's quadrants come out A, B, C, D."""
    gen = load_module(HERE / "instances" / "graph500_colnet.py")
    scale, seed = 16, 2 ** 40 + 5
    n, m = 2 ** scale, 16 * 2 ** scale
    i, j = gen.kronecker_edges(scale, 16, 0.57, 0.19, 0.19,
                               np.random.default_rng(seed))
    rng = np.random.default_rng(seed)
    i0, j0, quad = np.zeros(m, np.int64), np.zeros(m, np.int64), None
    for bit in range(scale):
        ii = rng.random(m) > 0.57 + 0.19
        jj = rng.random(m) > np.where(ii, 0.19 / (1 - 0.76), 0.57 / 0.76)
        i0 += ii << bit
        j0 += jj << bit
        if quad is None:
            quad = np.bincount(ii * 2 + jj, minlength=4) / m
    perm = rng.permutation(n)
    np.testing.assert_array_equal(i, perm[i0])
    np.testing.assert_array_equal(j, perm[j0])
    np.testing.assert_allclose(quad, [0.57, 0.19, 0.19, 0.05], atol=0.005)


def test_graph500_column_net_is_the_symmetric_pattern():
    """Pins of net j are the rows of column j of A + Aᵀ + I; a row weighs
    its nonzeros; one-pin nets are left out; the same seed gives the same
    instance."""
    gen, hg = _g500(9, 2 ** 33 + 7)
    n = 2 ** 9
    i, j = gen.kronecker_edges(9, 16, 0.57, 0.19, 0.19,
                               np.random.default_rng(2 ** 33 + 7))
    a = np.zeros((n, n), bool)
    a[i, j] = a[j, i] = True
    a[np.arange(n), np.arange(n)] = True
    cols = [np.flatnonzero(a[:, c]) for c in range(n)]
    want = [c for c in cols if len(c) >= 2]
    got = [hg.eind[hg.eptr[e]:hg.eptr[e + 1]] for e in range(hg.m)]
    assert hg.n == n and hg.m == len(want)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    np.testing.assert_array_equal(hg.vwgt, a.sum(axis=1))
    assert (hg.ewgt == 1).all()
    again = _g500(9, 2 ** 33 + 7)[1]
    np.testing.assert_array_equal(again.eind, hg.eind)


def test_graph500_km1_is_the_spmv_volume():
    """(λ−1) of the column-net hypergraph equals the words a conformal
    y = Ax sends: x_c goes from the owner of row c to every other block
    that holds a row with a nonzero in column c."""
    gen, hg = _g500(8, 11)
    n = 2 ** 8
    i, j = gen.kronecker_edges(8, 16, 0.57, 0.19, 0.19,
                               np.random.default_rng(11))
    a = np.zeros((n, n), bool)
    a[i, j] = a[j, i] = True
    part = np.random.default_rng(4).integers(0, 4, n)
    words = sum(len(set(part[np.flatnonzero(a[:, c])].tolist())
                    - {int(part[c])}) for c in range(n))
    assert R.km1(hg, part) == words > 0
