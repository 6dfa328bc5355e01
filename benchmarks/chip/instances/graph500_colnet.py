"""Column-net hypergraph of a Graph 500 Kronecker matrix.

The matrix is the Graph 500 benchmark's graph: ``M = edgefactor · 2^scale``
edges drawn by the specification's Kronecker generator (initiator
probabilities A, B, C and D = 1 − A − B − C; at each of ``scale`` levels
the row bit is 1 with probability C + D, and the column bit is 1 with
probability C/(C + D) or B/(A + B) as the row bit is 1 or 0), then the
vertex ids permuted at random.  The graph is undirected, so the matrix is
the symmetric pattern of the edges with duplicates merged; the diagonal is
added, as the column-net model asks for when x and y are partitioned alike
(Catalyurek and Aykanat 1999).

In the column-net model row i is a vertex whose weight is its number of
nonzeros, and column j is a net whose pins are the rows with a nonzero in
it, of weight 1; the (lambda - 1) of a partition is then the words that
parallel y = Ax sends.  A net of one pin (a column whose only nonzero is
the diagonal) sends nothing under any partition and is left out.
"""
from __future__ import annotations

import numpy as np

from benchmarks.chip.reference import Hypergraph


def kronecker_edges(scale: int, edgefactor: int, a: float, b: float,
                    c: float, rng) -> tuple:
    """The Graph 500 generator's edge list (start, end), ids permuted."""
    n = 1 << scale
    m = edgefactor * n
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    i = np.zeros(m, np.int64)
    j = np.zeros(m, np.int64)
    for bit in range(scale):
        ii = rng.random(m) > ab
        jj = rng.random(m) > np.where(ii, c_norm, a_norm)
        i |= ii.astype(np.int64) << bit
        j |= jj.astype(np.int64) << bit
    perm = rng.permutation(n)
    return perm[i], perm[j]


def build(params: dict) -> Hypergraph:
    scale = int(params["scale"])
    n = 1 << scale
    rng = np.random.default_rng(int(params["seed"]))
    i, j = kronecker_edges(scale, int(params["edgefactor"]),
                           float(params["A"]), float(params["B"]),
                           float(params["C"]), rng)
    diag = np.arange(n, dtype=np.int64)
    row = np.concatenate([i, j, diag])
    col = np.concatenate([j, i, diag])
    key = np.unique(col * n + row)              # column-major, no duplicates
    col, row = key // n, key % n
    size = np.bincount(col, minlength=n)
    vwgt = np.bincount(row, minlength=n)        # nonzeros of each row
    keep = size[col] >= 2
    sizes = size[size >= 2]
    eptr = np.zeros(len(sizes) + 1, np.int64)
    np.cumsum(sizes, out=eptr[1:])
    return Hypergraph(n, len(sizes), eptr, row[keep], vwgt.astype(np.int64),
                      np.ones(len(sizes), np.int64))
