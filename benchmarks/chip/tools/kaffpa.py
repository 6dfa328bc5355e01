"""kaffpa through ``repro.core.interface.kaffpa``; the cut of each
partition is recounted on the host."""
from __future__ import annotations

from benchmarks.chip import reference as R


def prepare(g, traffic: dict) -> dict:
    """The entry point's arguments, built once in set-up."""
    from repro.core import interface
    return {"args": (g.n, g.vwgt, g.xadj, g.adjwgt, g.adjncy,
                     int(traffic["k"])),
            "mode": getattr(interface, traffic["preset"].upper())}


def solve(prepared: dict, eps: float, seed: int, report=None):
    """One partition job → (objective the entry point returned, labels)."""
    from repro.core import interface
    return interface.kaffpa(*prepared["args"], eps, seed=seed,
                            mode=prepared["mode"], report=report)


def objective(g, part) -> int:
    return R.edge_cut(g, part)
