"""Drive the partitioner's main path once on one TPU chip and check it.

    python chip_smoke.py              # one chip: kaffpa, kahypar,
                                      # node_separator, parhyp
    python chip_smoke.py --chips 4    # four chips: distributed parhyp only

Every phase goes through the library entry points (``repro.core.interface``
and the ``parhyp`` program) at deployment size and checks its result
against a plain host-side reference.  The refinement programs run the
compiled Pallas kernels: the script asserts that the kernels were placed
into them (``kernels/mosaic/*`` counters) and that each kernel, compiled
for the chip (``tpu_custom_call`` in its program), equals its pure-jnp
reference bit for bit on the phase's own instance.

Each phase prints one JSON line (platform, device kind, cold and warm
seconds, compiles, ``peak_bytes_in_use`` and the objective).  The last
line is ``{"ok": true, "device": {...}}``; any failed check raises, and the
script then exits non-zero without that line.  With no TPU the script
exits non-zero at once.  Everything runs in this one process.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

import numpy as np  # noqa: E402

from repro import obs  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402

EPS = 0.03
SEP_EPS = 0.20          # node_separator's default imbalance (paper §5.2)
PARHYP_SLACK = 1.05     # parhyp km1 ≤ 5% over sequential kahypar

#: phase sizes as run on the chip; tests run the one-chip phases smaller
FULL = {"kaffpa_grid": 1024, "kaffpa_k": 16, "hyper_scale": 17,
        "hyper_k": 8, "sep_grid": 512, "dist_scale": 20, "dist_k": 8}


class CheckFailed(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


def _device():
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _timed(fn, *args, **kwargs):
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    return out, time.perf_counter() - t0


#: engine spans whose warm seconds each phase reports (where present)
SPANS = ("hierarchy", "initial_tournament", "uncoarsen", "parhyp_coarsen",
         "parhyp_initial", "parhyp_level")


def _cold_warm(fn, *args, **kwargs):
    """Run ``fn`` twice (cold, then warm with the same arguments); returns
    (cold result, stats).  The stats hold both wall times, the compiles of
    each run, the warm run's seconds per engine span, the
    ``kernels/mosaic/*`` counts of the cold run (the traces that placed
    compiled kernels into its programs) and its counters."""
    from benchmarks.common import span_seconds
    rec = obs.Recorder("cold")
    out, cold = _timed(fn, *args, report=rec, **kwargs)
    counters = rec.counters()
    rec_w = obs.Recorder("warm")
    _, warm = _timed(fn, *args, report=rec_w, **kwargs)
    mosaic = {name.split("/")[-1]: int(v) for name, v in counters.items()
              if name.startswith("kernels/mosaic/")}
    spans = {name: span_seconds(rec_w.events, name) for name in SPANS}
    return out, {"cold_s": cold, "warm_s": warm,
                 "compiles": int(counters.get("jax/compiles", 0)),
                 "compile_s": counters.get("jax/compile_secs", 0.0),
                 "compiles_warm": rec_w.compile_count,
                 "warm_span_s": {k: v for k, v in spans.items() if v},
                 "mosaic": mosaic, "counters": counters}


# ---------------------------------------------------------------------------
# plain references (host numpy, independent of the code under test)
# ---------------------------------------------------------------------------

def ref_edge_cut(g, part) -> int:
    src = np.repeat(np.arange(g.n), np.diff(g.xadj))
    return int(g.adjwgt[part[src] != part[g.adjncy]].sum()) // 2


def ref_km1(hg, part) -> int:
    net = np.repeat(np.arange(hg.m), np.diff(hg.eptr))
    pairs = np.unique(net * np.int64(part.max() + 1) + part[hg.eind])
    lam = np.bincount(pairs // (part.max() + 1), minlength=hg.m)
    return int((hg.ewgt * np.maximum(lam - 1, 0)).sum())


def ref_balanced(vwgt, part, k: int, eps: float) -> bool:
    bw = np.bincount(part, weights=vwgt, minlength=k)
    return len(bw) == k and bw.max() <= (1 + eps) * np.ceil(
        vwgt.sum() / k) + 1e-9


def ref_separator_split(g, sep):
    """Components of G − S packed into two sides (largest first onto the
    lighter side) → (2-block labels, number of components)."""
    from scipy.sparse import csr_matrix
    from scipy.sparse.csgraph import connected_components
    keep = np.ones(g.n, bool)
    keep[sep] = False
    src = np.repeat(np.arange(g.n), np.diff(g.xadj))
    live = keep[src] & keep[g.adjncy]
    adj = csr_matrix((np.ones(int(live.sum())), (src[live], g.adjncy[live])),
                     shape=(g.n, g.n))
    ncomp, comp = connected_components(adj, directed=False)
    comp = np.where(keep, comp, -1)
    cw = np.bincount(comp[keep], weights=g.vwgt[keep], minlength=ncomp)
    side = np.zeros(ncomp, np.int64)
    load = [0.0, 0.0]
    for c in np.argsort(-cw, kind="stable"):
        if cw[c] == 0:
            continue
        s = int(load[1] < load[0])
        side[c] = s
        load[s] += cw[c]
    part = np.where(keep, side[np.maximum(comp, 0)], 0)
    return part, int((cw > 0).sum()), load


def _on_tpu() -> bool:
    import jax
    return jax.devices()[0].platform == "tpu"


def _emit(res: dict) -> None:
    print(json.dumps(res), flush=True)


def check_kernel(name: str, pallas, reference, args) -> None:
    """The Pallas op equals its pure-jnp reference bit for bit; on the chip
    its compiled program holds a Mosaic kernel."""
    import jax
    got = np.asarray(jax.block_until_ready(pallas(*args)))
    want = np.asarray(reference(*args))
    check(got.shape == want.shape and np.array_equal(got, want),
          f"{name}: Pallas kernel differs from its reference "
          f"({int(np.sum(got != want))} elements)")
    if _on_tpu():
        text = jax.jit(pallas).lower(*args).compile().as_text()
        check("tpu_custom_call" in text,
              f"{name}: compiled program holds no tpu_custom_call")


def _pad_labels(part, n_pad: int):
    import jax.numpy as jnp
    lab = np.zeros(n_pad, np.int32)
    lab[:len(part)] = part
    return jnp.asarray(lab)


# ---------------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------------

def phase_kaffpa(size: dict) -> dict:
    from repro.core import interface
    from repro.core.csr import to_ell
    from repro.core.partition import edge_cut, is_feasible
    from repro.io.generators import grid2d
    from repro.kernels import ops
    side, k = size["kaffpa_grid"], size["kaffpa_k"]
    g = grid2d(side, side)
    (cut, part), stats = _cold_warm(
        interface.kaffpa, g.n, g.vwgt, g.xadj, g.adjwgt, g.adjncy, k, EPS,
        mode=interface.FAST, seed=0)
    part = np.asarray(part, np.int64)
    check(cut == edge_cut(g, part) == ref_edge_cut(g, part),
          f"kaffpa: returned cut {cut} != recounted {ref_edge_cut(g, part)}")
    check(is_feasible(g, part, k, EPS) and ref_balanced(g.vwgt, part, k, EPS),
          "kaffpa: partition is not feasible at eps")
    ell = to_ell(g)
    lab = _pad_labels(part, ell.nbr.shape[0])
    check_kernel(
        "lp_affinity",
        lambda nbr, w, lb: ops.lp_affinity(nbr, w, lb, k),
        lambda nbr, w, lb: ops.lp_affinity(nbr, w, lb, k, use_pallas=False),
        (ell.nbr, ell.wgt, lab))
    return {"phase": "kaffpa", "instance": f"grid2d({side},{side})",
            "n": g.n, "k": k, "objective": "cut", "value": int(cut),
            **_public(stats)}


def phase_kahypar(size: dict, hg) -> dict:
    from repro.core import interface
    from repro.core.hypergraph import metrics as HM
    from repro.core.hypergraph.container import to_ell_h
    from repro.kernels import ops
    k = size["hyper_k"]
    (km1, part), stats = _cold_warm(
        interface.kahypar, hg.n, hg.m, hg.vwgt, hg.ewgt, hg.eptr, hg.eind,
        k, EPS, mode=interface.FAST, objective="km1", seed=0)
    part = np.asarray(part, np.int64)
    check(km1 == HM.connectivity(hg, part) == ref_km1(hg, part),
          f"kahypar: returned km1 {km1} != recounted {ref_km1(hg, part)}")
    check(HM.is_feasible(hg, part, k, EPS)
          and ref_balanced(hg.vwgt, part, k, EPS),
          "kahypar: partition is not feasible at eps")
    ell = to_ell_h(hg)
    lab = _pad_labels(part, ell.vnets.shape[0])
    check_kernel(
        "pin_affinity",
        lambda vn, p, pm, w, lb: ops.pin_affinity(vn, p, pm, w, lb, k),
        lambda vn, p, pm, w, lb: ops.pin_affinity(vn, p, pm, w, lb, k,
                                                  use_pallas=False),
        (ell.vnets, ell.pins, ell.pin_mask, ell.netw, lab))
    return {"phase": "kahypar", "instance": f"rmat_hypergraph("
            f"{size['hyper_scale']}, seed=3)", "n": hg.n, "m": hg.m,
            "pins": hg.pins, "k": k, "objective": "km1", "value": int(km1),
            **_public(stats)}


def phase_node_separator(size: dict) -> dict:
    from repro.core import interface
    from repro.core.csr import to_ell
    from repro.core.nodesep.refine import separator_caps
    from repro.core.separator import verify_separator
    from repro.io.generators import grid2d
    from repro.kernels import ops
    side = size["sep_grid"]
    g = grid2d(side, side)
    (nsep, sep), stats = _cold_warm(
        interface.node_separator, g.n, g.vwgt, g.xadj, g.adjwgt, g.adjncy, 2,
        SEP_EPS, mode=interface.FAST, seed=0)
    sep = np.asarray(sep, np.int64)
    check(nsep == len(sep) == len(np.unique(sep)) and 0 < nsep < g.n,
          f"node_separator: bad separator size {nsep}")
    part, ncomp, load = ref_separator_split(g, sep)
    check(ncomp >= 2, "node_separator: G - S is still connected")
    check(verify_separator(g, part, sep, 2),
          "node_separator: separator does not split G")
    cap = separator_caps(g, SEP_EPS)
    check(max(load) <= max(cap) + 1e-9,
          f"node_separator: sides {load} exceed capacity {list(cap)}")
    ell = to_ell(g)
    labels = np.where(part == 1, 1, 0)
    labels[sep] = 2
    lab = _pad_labels(labels, ell.nbr.shape[0])
    check_kernel(
        "sep_affinity",
        lambda nbr, w, vw, lb: ops.sep_affinity(nbr, w, vw, lb),
        lambda nbr, w, vw, lb: ops.sep_affinity(nbr, w, vw, lb,
                                                use_pallas=False),
        (ell.nbr, ell.wgt, ell.vwgt, lab))
    return {"phase": "node_separator", "instance": f"grid2d({side},{side})",
            "n": g.n, "objective": "separator_vertices", "value": int(nsep),
            "straight_line_separator": side, **_public(stats)}


def phase_parhyp(size: dict, hg, km1_seq: int) -> dict:
    import jax
    from jax.sharding import Mesh
    from repro.core.hypergraph.dist import parhyp
    k = size["hyper_k"]
    mesh = Mesh(np.array(jax.devices()[:1]), ("nets",))
    part, stats = _cold_warm(parhyp, hg, k, EPS, "fast", 0, mesh)
    part = np.asarray(part, np.int64)
    km1 = ref_km1(hg, part)
    levels = int(stats.pop("counters").get("parhyp/device_levels", 0))
    check(levels >= 2, "parhyp: the device-resident coarsening did not run")
    check(ref_balanced(hg.vwgt, part, k, EPS),
          "parhyp: partition is not feasible at eps")
    check(km1 <= PARHYP_SLACK * km1_seq,
          f"parhyp: km1 {km1} > {PARHYP_SLACK} x kahypar's {km1_seq}")
    return {"phase": "parhyp", "mesh": "1 device (nets,)", "n": hg.n,
            "k": k, "objective": "km1", "value": km1,
            "kahypar_km1": km1_seq, "device_levels": levels, **stats}


def _public(stats: dict) -> dict:
    return {key: v for key, v in stats.items() if key != "counters"}


def run_one_chip(size: dict) -> None:
    from repro.io.generators import rmat_hypergraph
    dev = _device()
    on_tpu = dev["platform"] == "tpu"

    def report(res, kernel=None):
        res.update(platform=dev["platform"], device_kind=dev["kind"],
                   peak_bytes_in_use=_peak_bytes())
        _emit(res)
        if kernel and on_tpu:
            check(res["mosaic"].get(kernel, 0) > 0,
                  f"{res['phase']}: refinement ran without the Pallas kernel")
        return res

    report(phase_kaffpa(size), "lp_affinity")
    report(phase_node_separator(size), "lp_affinity")
    hg = rmat_hypergraph(size["hyper_scale"], seed=3)
    res = report(phase_kahypar(size, hg), "pin_affinity")
    report(phase_parhyp(size, hg, res["value"]))


# ---------------------------------------------------------------------------
# four chips: distributed parhyp on two mesh layouts vs one chip
# ---------------------------------------------------------------------------

def run_four_chips(size: dict) -> None:
    """parhyp on a 4-device ("nets",) mesh and a (2, 2) ("nets", "verts")
    mesh against the same call on one device.  The distributed refinement
    of one fixed partition must agree bit for bit on the two four-chip
    layouts; its km1 and the labels where the one-device result differs
    are printed beside them.  The whole program's coarsening is
    layout-dependent by design (a cluster is arbitrated inside its vertex
    column by its net-row owner), so its km1 on four chips is held to
    ``PARHYP_SLACK`` × one chip's."""
    import jax
    from jax.sharding import Mesh
    from repro.core.hypergraph.dist import parhyp, parhyp_refine
    from repro.core.hypergraph.initial import random_partition
    from repro.io.generators import rmat_hypergraph
    devs = jax.devices()
    check(len(devs) == 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    k = size["dist_k"]
    hg = rmat_hypergraph(size["dist_scale"], seed=3)
    inst = {"instance": f"rmat_hypergraph({size['dist_scale']}, seed=3)",
            "n": hg.n, "m": hg.m, "pins": hg.pins, "k": k}
    meshes = {
        "1 device (nets,)": Mesh(np.array(devs[:1]), ("nets",)),
        "4 devices (nets,)": Mesh(np.array(devs), ("nets",)),
        "2x2 (nets, verts)": Mesh(np.array(devs).reshape(2, 2),
                                  ("nets", "verts")),
    }
    part0 = random_partition(hg, k, seed=1)
    refined, km1 = {}, {}
    for name, mesh in meshes.items():
        rec = obs.Recorder(name)
        out, dt = _timed(parhyp_refine, hg, part0, k, EPS, mesh=mesh,
                         rounds=8, seed=3)
        refined[name] = np.asarray(out, np.int64)
        _emit({"phase": "parhyp_refine", "mesh": name, **inst,
               "objective": "km1", "value": ref_km1(hg, refined[name]),
               "cold_s": dt, "compiles": rec.compile_count,
               "peak_bytes_in_use": _peak_bytes()})
    four, two_d = refined["4 devices (nets,)"], refined["2x2 (nets, verts)"]
    one = refined["1 device (nets,)"]
    _emit({"parhyp_refine_km1_1_chip": ref_km1(hg, one),
           "parhyp_refine_km1_4_chips": ref_km1(hg, four),
           "labels_differing_1_chip_vs_4_chips": int(np.sum(one != four))})
    check(np.array_equal(four, two_d),
          "parhyp_refine: the (4,) and (2, 2) layouts disagree")
    for name, mesh in meshes.items():
        rec = obs.Recorder(name)
        part, dt = _timed(parhyp, hg, k, EPS, "fast", 0, mesh, report=rec)
        part = np.asarray(part, np.int64)
        check(ref_balanced(hg.vwgt, part, k, EPS),
              f"parhyp {name}: partition is not feasible at eps")
        km1[name] = ref_km1(hg, part)
        _emit({"phase": "parhyp", "mesh": name, **inst, "objective": "km1",
               "value": km1[name], "cold_s": dt,
               "device_levels": int(rec.counters().get(
                   "parhyp/device_levels", 0)),
               "compiles": rec.compile_count,
               "compile_s": rec.counters().get("jax/compile_secs", 0.0),
               "peak_bytes_in_use": _peak_bytes()})
    one = km1["1 device (nets,)"]
    _emit({"parhyp_km1_1_chip": one,
           "parhyp_km1_4_chips": km1["4 devices (nets,)"],
           "parhyp_km1_4_chips_2x2": km1["2x2 (nets, verts)"],
           "bound": PARHYP_SLACK})
    for name in ("4 devices (nets,)", "2x2 (nets, verts)"):
        check(km1[name] <= PARHYP_SLACK * one,
              f"parhyp {name}: km1 {km1[name]} > {PARHYP_SLACK} x one "
              f"chip's {one}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--dist-scale", type=int, default=FULL["dist_scale"],
                    help="--chips 4: rmat_hypergraph scale (2^s vertices)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    obs.install_jax_compile_listener()
    dev = _device()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {dev['platform']!r})",
              file=sys.stderr)
        return 1
    if args.chips == 4:
        run_four_chips(dict(FULL, dist_scale=args.dist_scale))
    else:
        run_one_chip(FULL)
    print(json.dumps({"ok": True, "device": _device()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
