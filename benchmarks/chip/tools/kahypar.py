"""kahypar through ``repro.core.interface.kahypar``; the (λ−1)
objective of each partition is recounted on the host."""
from __future__ import annotations

from benchmarks.chip import reference as R


def prepare(hg, traffic: dict) -> dict:
    """The entry point's arguments, built once in set-up."""
    from repro.core import interface
    return {"args": (hg.n, hg.m, hg.vwgt, hg.ewgt, hg.eptr, hg.eind,
                     int(traffic["k"])),
            "mode": getattr(interface, traffic["preset"].upper()),
            "objective": traffic["objective"]}


def solve(prepared: dict, eps: float, seed: int, report=None):
    """One partition job → (objective the entry point returned, labels)."""
    from repro.core import interface
    return interface.kahypar(*prepared["args"], eps, seed=seed,
                             mode=prepared["mode"],
                             objective=prepared["objective"], report=report)


def objective(hg, part) -> int:
    return R.km1(hg, part)
