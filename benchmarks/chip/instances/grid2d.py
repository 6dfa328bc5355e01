"""2-D 5-point mesh: ``rows × cols`` cells, an edge between cells that
share a side, unit weights.  A copy of ``repro.io.generators.grid2d``
(without ``wrap``), so that a change to the program's generators cannot
move the benchmark's instance."""
from __future__ import annotations

import numpy as np

from benchmarks.chip.reference import graph_from_edges


def build(params: dict):
    rows, cols = int(params["rows"]), int(params["cols"])
    idx = np.arange(rows * cols).reshape(rows, cols)
    u = np.concatenate([idx[:, :-1].ravel(), idx[:-1, :].ravel()])
    v = np.concatenate([idx[:, 1:].ravel(), idx[1:, :].ravel()])
    return graph_from_edges(rows * cols, u, v)
