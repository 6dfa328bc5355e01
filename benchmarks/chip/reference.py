"""Plain host-numpy references that decide a run's ``correct``.

Nothing here imports the program under test.  The objective and balance
recounts are copied from ``chip_smoke.py``: the edge cut of a graph, the
(lambda - 1) of a hypergraph, and the weight by which the heaviest block
exceeds what the configuration's epsilon allows.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np


class Graph(NamedTuple):
    """Symmetric CSR graph: ``adjncy[xadj[v]:xadj[v+1]]`` are v's
    neighbours, ``adjwgt`` their edge weights."""
    n: int
    xadj: np.ndarray
    adjncy: np.ndarray
    vwgt: np.ndarray
    adjwgt: np.ndarray


class Hypergraph(NamedTuple):
    """hMETIS CSR hypergraph: ``eind[eptr[e]:eptr[e+1]]`` are e's pins."""
    n: int
    m: int
    eptr: np.ndarray
    eind: np.ndarray
    vwgt: np.ndarray
    ewgt: np.ndarray

    @property
    def pins(self) -> int:
        return int(self.eptr[-1])


def graph_from_edges(n: int, u, v, w=None, vwgt=None) -> Graph:
    """Undirected edges (each pair once) → symmetric CSR, rows sorted."""
    u = np.asarray(u, np.int64)
    v = np.asarray(v, np.int64)
    w = np.ones(len(u), np.int64) if w is None else np.asarray(w, np.int64)
    src = np.concatenate([u, v])
    dst = np.concatenate([v, u])
    ww = np.concatenate([w, w])
    order = np.lexsort((dst, src))
    xadj = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(src, minlength=n), out=xadj[1:])
    vw = np.ones(n, np.int64) if vwgt is None else np.asarray(vwgt, np.int64)
    return Graph(n, xadj, dst[order], vw, ww[order])


def hypergraph_from_pins(n: int, net, pin, ewgt=None, vwgt=None
                         ) -> Hypergraph:
    """(net, pin) pairs, nets numbered 0..m-1 with no gaps → CSR."""
    net = np.asarray(net, np.int64)
    pin = np.asarray(pin, np.int64)
    m = int(net.max()) + 1 if len(net) else 0
    order = np.argsort(net, kind="stable")
    eptr = np.zeros(m + 1, np.int64)
    np.cumsum(np.bincount(net, minlength=m), out=eptr[1:])
    ew = np.ones(m, np.int64) if ewgt is None else np.asarray(ewgt, np.int64)
    vw = np.ones(n, np.int64) if vwgt is None else np.asarray(vwgt, np.int64)
    return Hypergraph(n, m, eptr, pin[order], vw, ew)


# ---------------------------------------------------------------------------
# objective and balance
# ---------------------------------------------------------------------------

def edge_cut(g: Graph, part) -> int:
    """Σ of the weights of edges whose ends lie in different blocks."""
    part = np.asarray(part, np.int64)
    src = np.repeat(np.arange(g.n), np.diff(g.xadj))
    return int(g.adjwgt[part[src] != part[g.adjncy]].sum()) // 2


def km1(hg: Hypergraph, part) -> int:
    """Σ_e w(e)·(λ(e) − 1), λ(e) the number of blocks e touches."""
    part = np.asarray(part, np.int64)
    kk = int(part.max()) + 1
    net = np.repeat(np.arange(hg.m), np.diff(hg.eptr))
    pairs = np.unique(net * kk + part[hg.eind])
    lam = np.bincount(pairs // kk, minlength=hg.m)
    return int((hg.ewgt * np.maximum(lam - 1, 0)).sum())


def block_cap(vwgt, k: int, eps: float) -> float:
    """Most weight a block may hold: (1 + ε)·⌈W / k⌉."""
    return (1 + eps) * np.ceil(np.asarray(vwgt).sum() / k)


def overweight(vwgt, part, k: int, eps: float) -> float:
    """Weight by which the heaviest block exceeds ``block_cap``, or 0:
    the partition is balanced at ε iff this is 0."""
    bw = np.bincount(np.asarray(part, np.int64), weights=vwgt, minlength=k)
    return float(max(bw.max() - block_cap(vwgt, k, eps), 0.0))


def bad_labels(part, n: int, k: int) -> int:
    """Vertices with no label in [0, k) (a short array counts the missing
    ones)."""
    part = np.asarray(part).ravel()
    if part.dtype.kind not in "iu":
        return n
    return abs(n - len(part)) + int(((part < 0) | (part >= k)).sum())
