"""Entry-point registry: every jax-traceable program in the repo, with a
canonical (tiny, deterministic) shape spec the checkers trace it on.

The host drivers in `core/interface.py` are numpy orchestration loops; the
contracts live in the jitted inner programs they route through (the
one-compile engine programs of DESIGN.md §12, the shard_map rounds of §9,
the serve steps of §13).  So the registry registers those inner programs,
and `DRIVER_ENTRIES` maps every public driver to the entries that cover it
— the registry-hygiene lint fails when a public driver has no entry.

Each entry declares which checkers apply via `tags`, the dims that must be
pow2 buckets (`bucket_dims`), and — for entries with padded containers — a
`PaddingSpec`: a perturbation writing deterministic garbage into padding
slots only (per the masking contract in `kernels/ops.py`:
`PADDING_CONTRACT`) plus a projection selecting the *real* slots of the
outputs.  The padding-inertness checker requires the projected outputs to
be bit-identical under perturbation.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class PaddingSpec:
    """Noninterference spec: `perturb(args, rng)` returns args with garbage
    in padding slots only; `project(flat_outputs)` keeps the real slots."""
    perturb: Callable[[Tuple, np.random.Generator], Tuple]
    project: Callable[[Sequence], Sequence]


@dataclasses.dataclass(frozen=True)
class EntryPoint:
    name: str
    build: Callable[[], Tuple[Callable, Tuple]]   # -> (fn, args)
    tags: frozenset                               # checkers that apply
    bucket_dims: Optional[Callable[[Tuple], Dict[str, int]]] = None
    padding: Optional[PaddingSpec] = None
    allow_callbacks: Tuple[str, ...] = ()         # primitive names allowed
    drivers: Tuple[str, ...] = ()                 # interface.py publics


# ---------------------------------------------------------------------------
# canonical instances (host-side, deterministic)
# ---------------------------------------------------------------------------

def _ring_graph(n: int = 24, stride: int = 7):
    """Ring + chord graph: connected, irregular weights, tiny."""
    from repro.core.csr import Graph
    nbrs = [[] for _ in range(n)]
    for i in range(n):
        for j in ((i + 1) % n, (i + stride) % n):
            nbrs[i].append(j)
            nbrs[j].append(i)
    xadj = np.zeros(n + 1, dtype=np.int64)
    adjncy, adjwgt = [], []
    for i in range(n):
        xadj[i + 1] = xadj[i] + len(nbrs[i])
        adjncy.extend(nbrs[i])
        adjwgt.extend(1.0 + ((i + j) % 3) for j in nbrs[i])
    return Graph.from_arrays(xadj, np.asarray(adjncy, np.int64),
                             vwgt=1.0 + np.arange(n) % 2,
                             adjwgt=np.asarray(adjwgt, np.float64))


def _tiny_hypergraph(n: int = 20, m: int = 12):
    from repro.core.hypergraph.container import Hypergraph
    eptr = [0]
    eind = []
    for j in range(m):
        pins = {j % n, (j * 5 + 1) % n, (j * 3 + 7) % n, (j + n // 2) % n}
        eind.extend(sorted(pins))
        eptr.append(len(eind))
    return Hypergraph.from_arrays(
        n, np.asarray(eptr, np.int64), np.asarray(eind, np.int64),
        ewgt=1.0 + np.arange(m) % 2, vwgt=np.ones(n))


def _one_device_mesh(axis: str):
    import jax
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:1]), (axis,))


def _garble(idx: np.ndarray, where: np.ndarray, hi: int,
            rng: np.random.Generator) -> np.ndarray:
    """Copy of ``idx`` with slots selected by ``where`` replaced by random
    valid ids in [0, hi) — the padding garbage injection."""
    out = np.array(idx)
    k = int(np.count_nonzero(where))
    if k:
        out[np.asarray(where)] = rng.integers(0, hi, size=k, dtype=out.dtype)
    return out


def _np(x):
    return np.asarray(x)


# ---------------------------------------------------------------------------
# engine entries (graph / hypergraph / separator refinement, LP clustering)
# ---------------------------------------------------------------------------

def _build_kway(use_kernel: bool):
    import jax
    from repro.core import refine as R
    from repro.core.csr import to_coo, to_ell
    g = _ring_graph()
    coo = to_coo(g)
    k, rounds, b = 3, 4, 3
    b_pad = R.batch_bucket(b)
    labs = np.zeros((b, coo.n_pad), np.int32)
    for i in range(b):
        labs[i, :g.n] = (np.arange(g.n) * (i + 1)) % k
    labs = R._pad_rows(labs, b_pad)
    rkeys = np.stack([R._round_keys(jax.random.PRNGKey(i), rounds, rounds)
                      for i in range(b_pad)])
    cap = np.asarray(R._caps_for(g, k, 0.10), np.float32)
    nrounds = np.full(b_pad, rounds, np.int32)
    zero = np.zeros(b_pad, bool)
    force = np.zeros(b_pad, bool)
    active = np.ones((b_pad, coo.n_pad), bool)
    base = (coo, labs, cap, rkeys, nrounds, zero, force, active)
    if not use_kernel:
        def fn(coo, labs, cap, rkeys, nr, z, f, a):
            return R._refine_scan_batch(coo, labs, cap, rkeys, nr, z, f, a,
                                        k, rounds)
        return fn, base
    ell = to_ell(g, row_tile=coo.n_pad)

    def fnk(coo, labs, cap, rkeys, nr, z, f, a, ell):
        return R._refine_scan_batch(coo, labs, cap, rkeys, nr, z, f, a,
                                    k, rounds, ell=ell, use_kernel=True)
    return fnk, base + (ell,)


def _kway_bucket_dims(args):
    coo, labs = args[0], args[1]
    dims = {"n_pad": coo.n_pad, "e_pad": coo.e_pad, "batch": labs.shape[0]}
    if len(args) > 8:                      # kernel variant carries the ELL
        dims["ell_dmax"] = args[8].nbr.shape[1]
    return dims


def _perturb_coo(coo, rng):
    """Garbage in CooGraph padding slots: w==0 edges may point anywhere."""
    import dataclasses as dc
    import jax.numpy as jnp
    pad = _np(coo.w) == 0
    n_pad = coo.n_pad
    return dc.replace(
        coo,
        src=jnp.asarray(_garble(_np(coo.src), pad, n_pad, rng)),
        dst=jnp.asarray(_garble(_np(coo.dst), pad, n_pad, rng)))


def _perturb_kway(args, rng):
    coo = args[0]
    n = 24                                  # real vertices of _ring_graph()
    labs = np.array(args[1])
    k = 3
    labs[:, n:] = rng.integers(0, k, size=labs[:, n:].shape, dtype=labs.dtype)
    labs[3:] = rng.integers(0, k, size=labs[3:].shape, dtype=labs.dtype)
    return (_perturb_coo(coo, rng), labs) + tuple(args[2:])


def _project_kway(outs):
    labels, cuts = outs[0], outs[1]
    return [_np(labels)[:3, :24], _np(cuts)[:3]]


def _perturb_ell(ell, rng):
    import dataclasses as dc
    import jax.numpy as jnp
    pad = _np(ell.wgt) == 0
    return dc.replace(
        ell, nbr=jnp.asarray(_garble(_np(ell.nbr), pad, ell.nbr.shape[0],
                                     rng)))


def _perturb_kway_kernel(args, rng):
    out = _perturb_kway(args[:8], rng)
    return out + (_perturb_ell(args[8], rng),)


def _build_cluster_lp():
    import jax
    from repro.core import lp as L
    from repro.core.csr import to_coo
    g = _ring_graph()
    coo = to_coo(g)
    cap = np.full(coo.n_pad, 6.0 * g.n, np.float32)
    labs = np.arange(coo.n_pad, dtype=np.int32)
    key = np.asarray(jax.random.PRNGKey(7))

    def fn(coo, labs, cap, key):
        return L._cluster_lp(coo, labs, cap, key, 4)
    return fn, (coo, labs, cap, key)


def _perturb_cluster_lp(args, rng):
    coo, labs = args[0], np.array(args[1])
    labs[24:] = rng.integers(0, coo.n_pad, size=labs[24:].shape,
                             dtype=labs.dtype)
    return (_perturb_coo(coo, rng), labs) + tuple(args[2:])


def _project_cluster_lp(outs):
    return [_np(outs[0])[:24]]


def _build_hyper(objective: str):
    import jax
    from repro.core.hypergraph import refine as HR
    from repro.core.hypergraph.container import to_pincoo
    from repro.core.refine import _pad_rows
    hg = _tiny_hypergraph()
    hc = to_pincoo(hg)
    k, rounds, b = 3, 4, 2
    k_pad = HR.k_bucket(k)
    b_pad = 2
    labs = np.zeros((b, hc.n_pad), np.int32)
    for i in range(b):
        labs[i, :hg.n] = (np.arange(hg.n) + i) % k
    labs = _pad_rows(labs, b_pad)
    cap = np.zeros(k_pad, np.float32)
    cap[:k] = np.asarray(HR._caps_for(hg, k, 0.10), np.float32)
    keys = np.asarray(jax.random.split(jax.random.PRNGKey(3), b_pad))
    force = np.zeros(b_pad, bool)

    def fn(hc, labs, cap, keys, force):
        return HR._hyper_refine_scan_batch(hc, labs, cap, keys, force,
                                           k_pad, rounds, objective, False)
    return fn, (hc, labs, cap, keys, force)


def _hyper_bucket_dims(args):
    hc, labs, cap = args[0], args[1], args[2]
    return {"n_pad": hc.n_pad, "e_pad": hc.e_pad, "p_pad": hc.p_pad,
            "k_pad": cap.shape[0], "batch": labs.shape[0]}


def _perturb_pincoo(hc, rng):
    import dataclasses as dc
    import jax.numpy as jnp
    pad = _np(hc.mask) == 0
    return dc.replace(
        hc,
        pv=jnp.asarray(_garble(_np(hc.pv), pad, hc.n_pad, rng)),
        pe=jnp.asarray(_garble(_np(hc.pe), pad, hc.e_pad, rng)))


def _perturb_hyper(args, rng):
    hc = args[0]
    labs = np.array(args[1])
    labs[:, 20:] = rng.integers(0, 3, size=labs[:, 20:].shape,
                                dtype=labs.dtype)
    return (_perturb_pincoo(hc, rng), labs) + tuple(args[2:])


def _project_hyper(outs):
    return [_np(outs[0])[:, :20], _np(outs[1])]


def _build_sep():
    import jax
    from repro.core.nodesep import refine as SR
    from repro.core.csr import to_coo
    g = _ring_graph()
    coo = to_coo(g)
    rounds, b = 4, 2
    labs = np.full((b, coo.n_pad), 2, np.int32)     # everything separator
    labs[:, :g.n] = np.arange(g.n)[None, :] % 2
    labs[0, : g.n // 2] = 2
    cap = np.asarray(SR.separator_caps(g, 0.20), np.float32)
    keys = np.asarray(jax.random.split(jax.random.PRNGKey(5), b))
    force = np.zeros(b, bool)

    def fn(coo, labs, cap, keys, force):
        return SR._sep_refine_scan_batch(coo, labs, cap, keys, force, rounds)
    return fn, (coo, labs, cap, keys, force)


def _perturb_sep(args, rng):
    coo = args[0]
    labs = np.array(args[1])
    labs[:, 24:] = rng.integers(0, 3, size=labs[:, 24:].shape,
                                dtype=labs.dtype)
    return (_perturb_coo(coo, rng), labs) + tuple(args[2:])


def _project_sep(outs):
    return [_np(outs[0])[:, :24], _np(outs[1])]


# ---------------------------------------------------------------------------
# distributed / memetic entries (shard_map)
# ---------------------------------------------------------------------------

def _two_device_mesh_11():
    """1-device 2-D (nets, verts) mesh — the canonical 2-D layout spec."""
    import jax
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("nets", "verts"))


def _build_parhyp(two_d: bool = False):
    import jax
    from repro.core.hypergraph import dist as D
    from repro.core.hypergraph.refine import _caps_for, _pad_caps, k_bucket
    hg = _tiny_hypergraph()
    sh = D.shard_hypergraph(hg, (1, 1) if two_d else 1)
    mesh = _two_device_mesh_11() if two_d else _one_device_mesh("nets")
    k, rounds = 3, 4
    k_pad = k_bucket(k)
    cap = np.asarray(_pad_caps(_caps_for(hg, k, 0.10), k_pad), np.float32)
    labels0 = np.zeros(sh.n_pad, np.int32)
    labels0[:hg.n] = np.arange(hg.n) % k
    key = np.asarray(jax.random.PRNGKey(11))
    force = np.asarray(False)

    def fn(pv, pe, mask, netw, esize, vwgt, labels0, cap, key, force):
        return D._parhyp_refine_jit(mesh, pv, pe, mask, netw, esize, vwgt,
                                    labels0, cap, key, force, sh.rows_v,
                                    sh.n_col, sh.e_rows, k_pad, rounds,
                                    "km1")
    return fn, (sh.pv, sh.pe, sh.mask, sh.netw, sh.esize, sh.vwgt,
                labels0, cap, key, force)


def _parhyp_bucket_dims(args):
    pv, netw, vwgt = args[0], args[3], args[5]
    return {"p_shard": pv.shape[1], "e_pad": netw.shape[0],
            "n_pad": vwgt.shape[0], "k_pad": args[7].shape[0]}


def _perturb_parhyp(args, rng):
    pv, pe, mask = (np.array(a) for a in args[:3])
    n_pad, e_pad = args[5].shape[0], args[3].shape[0]
    pad = mask == 0
    pv = _garble(pv, pad, n_pad, rng)
    pe = _garble(pe, pad, e_pad, rng)
    labels0 = np.array(args[6])
    labels0[20:] = rng.integers(0, 3, size=labels0[20:].shape,
                                dtype=labels0.dtype)
    return (pv, pe, mask) + tuple(args[3:6]) + (labels0,) + tuple(args[7:])


def _project_parhyp(outs):
    return [_np(outs[0])[:20], _np(outs[1]), _np(outs[2])]


def _build_parhyp_cluster():
    from repro.core.hypergraph import dist as D
    hg = _tiny_hypergraph()
    sh = D.shard_hypergraph(hg, 1)
    mesh = _one_device_mesh("nets")
    labels0 = np.arange(sh.n_pad, dtype=np.int32)
    capv = np.full(sh.n_pad, 8.0, np.float32)
    parity0 = np.int32(0)

    def fn(pv, pe, mask, netw, esize, vwgt, labels0, capv, parity0):
        return D._parhyp_cluster_jit(mesh, pv, pe, mask, netw, esize, vwgt,
                                     labels0, capv, parity0, sh.rows_v,
                                     sh.n_col, sh.e_rows, 4)
    return fn, (sh.pv, sh.pe, sh.mask, sh.netw, sh.esize, sh.vwgt,
                labels0, capv, parity0)


def _cluster_bucket_dims(args):
    pv, netw, vwgt = args[0], args[3], args[5]
    return {"p_shard": pv.shape[1], "e_pad": netw.shape[0],
            "n_pad": vwgt.shape[0]}


def _perturb_parhyp_cluster(args, rng):
    pv, pe, mask = (np.array(a) for a in args[:3])
    n_pad, e_pad = args[5].shape[0], args[3].shape[0]
    pad = mask == 0
    pv = _garble(pv, pad, n_pad, rng)
    pe = _garble(pe, pad, e_pad, rng)
    # padding vertices (vwgt 0) may start in any singleton cluster
    labels0 = np.array(args[6])
    labels0[20:] = rng.integers(20, n_pad, size=labels0[20:].shape,
                                dtype=labels0.dtype)
    return (pv, pe, mask) + tuple(args[3:6]) + (labels0,) + tuple(args[7:])


def _project_parhyp_cluster(outs):
    return [_np(outs[0])[:20], _np(outs[1])]


def _build_parhyp_contract():
    from repro.core.hypergraph import dist as D
    hg = _tiny_hypergraph()
    sh = D.shard_hypergraph(hg, 1)
    mesh = _one_device_mesh("nets")
    labels = (np.arange(sh.n_pad, dtype=np.int32) // 2) * 2

    def fn(pv, pe, mask, netw, vwgt, labels):
        return D._parhyp_contract_jit(mesh, pv, pe, mask, netw, vwgt,
                                      labels, sh.n_col, sh.e_rows)
    return fn, (sh.pv, sh.pe, sh.mask, sh.netw, sh.vwgt, labels)


def _perturb_parhyp_contract(args, rng):
    pv, pe, mask = (np.array(a) for a in args[:3])
    n_pad, e_pad = args[4].shape[0], args[3].shape[0]
    pad = mask == 0
    pv = _garble(pv, pad, n_pad, rng)
    pe = _garble(pe, pad, e_pad, rng)
    labels = np.array(args[5])
    labels[20:] = rng.integers(20, n_pad, size=labels[20:].shape,
                               dtype=labels.dtype)
    return (pv, pe, mask) + tuple(args[3:5]) + (labels,)


def _project_parhyp_contract(outs):
    # coarse_of of padding vertices depends on their (free) input labels;
    # every other output is fully determined by the real slots
    pv2, pe2, mask2, netw2, esize2, cvw, coarse_of, nc, hi = outs
    return [_np(pv2), _np(pe2), _np(mask2), _np(netw2), _np(esize2),
            _np(cvw), _np(coarse_of)[:20], _np(nc), _np(hi)]


def _build_migrate():
    from repro.core.memetic import migrate as MG
    mesh = _one_device_mesh(MG.AXIS)
    parts = np.arange(4 * 32, dtype=np.int32).reshape(4, 32)

    def fn(parts):
        return MG._ring_roll_jit(mesh, parts, 1, 4, 1)
    return fn, (parts,)


# ---------------------------------------------------------------------------
# kernel entries (public Pallas wrappers)
# ---------------------------------------------------------------------------

def _build_lp_affinity():
    from repro.core.csr import to_ell
    from repro.kernels import ops
    g = _ring_graph()
    ell = to_ell(g)
    labels = np.arange(ell.nbr.shape[0], dtype=np.int32) % 4

    def fn(nbr, wgt, labels):
        return ops.lp_affinity(nbr, wgt, labels, 4)
    return fn, (ell.nbr, ell.wgt, labels)


def _perturb_lp_affinity(args, rng):
    nbr, wgt = _np(args[0]), _np(args[1])
    return (_garble(nbr, wgt == 0, nbr.shape[0], rng),) + tuple(args[1:])


def _build_sep_affinity():
    from repro.core.csr import to_ell
    from repro.kernels import ops
    g = _ring_graph()
    ell = to_ell(g)
    labels = np.arange(ell.nbr.shape[0], dtype=np.int32) % 3

    def fn(nbr, wgt, vwgt, labels):
        return ops.sep_affinity(nbr, wgt, vwgt, labels)
    return fn, (ell.nbr, ell.wgt, ell.vwgt, labels)


def _perturb_sep_affinity(args, rng):
    nbr, wgt = _np(args[0]), _np(args[1])
    return (_garble(nbr, wgt == 0, nbr.shape[0], rng),) + tuple(args[1:])


def _build_pin_count():
    from repro.core.hypergraph.container import to_ell_h
    from repro.kernels import ops
    eh = to_ell_h(_tiny_hypergraph())
    labels = np.arange(eh.n_pad, dtype=np.int32) % 4

    def fn(pins, pin_mask, netw, labels):
        return ops.pin_count(pins, pin_mask, netw, labels, 4)
    return fn, (eh.pins, eh.pin_mask, eh.netw, labels)


def _perturb_pin_count(args, rng):
    pins, mask = _np(args[0]), _np(args[1])
    n_pad = args[3].shape[0]
    return (_garble(pins, mask == 0, n_pad, rng),) + tuple(args[1:])


def _build_pin_affinity():
    from repro.core.hypergraph.container import to_ell_h
    from repro.kernels import ops
    eh = to_ell_h(_tiny_hypergraph())
    labels = np.arange(eh.n_pad, dtype=np.int32) % 4

    def fn(vnets, pins, pin_mask, netw, labels):
        return ops.pin_affinity(vnets, pins, pin_mask, netw, labels, 4)
    return fn, (eh.vnets, eh.pins, eh.pin_mask, eh.netw, labels)


def _perturb_pin_affinity(args, rng):
    vnets, pins, mask, netw = (_np(a) for a in args[:4])
    n_pad = args[4].shape[0]
    pins = _garble(pins, mask == 0, n_pad, rng)
    # vnets padding slots point at *a* zero-weight net (contract); move them
    # to a random other zero-weight net
    zero_nets = np.flatnonzero(netw == 0)
    vn = np.array(vnets)
    pad = np.isin(vn, zero_nets)
    k = int(np.count_nonzero(pad))
    vn[pad] = rng.choice(zero_nets, size=k)
    return (vn, pins) + tuple(args[2:])


def _build_ssd():
    from repro.kernels import ops
    rng = np.random.default_rng(0)
    bh, l, p, n = 2, 128, 4, 4
    x = rng.standard_normal((bh, l, p)).astype(np.float32)
    ld = -np.abs(rng.standard_normal((bh, l)).astype(np.float32))
    b = rng.standard_normal((bh, l, n)).astype(np.float32)
    c = rng.standard_normal((bh, l, n)).astype(np.float32)

    def fn(x, ld, b, c):
        return ops.ssd_scan(x, ld, b, c, chunk=64)
    return fn, (x, ld, b, c)


# ---------------------------------------------------------------------------
# serve entries
# ---------------------------------------------------------------------------

def _serve_setup(arch: str, slots: int):
    import jax
    from repro.configs.base import get_config
    from repro.models import transformer as T
    cfg = get_config(arch).reduced()
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    caches = T.init_caches(cfg, slots, 16)
    return cfg, params, caches


def _build_prefill_step1():
    from repro.serve import batching as B
    cfg, params, caches = _serve_setup("minicpm_2b", 1)

    def fn(params, tok, caches, pos):
        return B._step1(params, cfg, tok, caches, pos)
    return fn, (params, np.ones((1, 1), np.int32), caches,
                np.int32(0))


def _build_decode_slots():
    from repro.serve import batching as B
    cfg, params, caches = _serve_setup("minicpm_2b", 2)

    def fn(params, toks, pos, caches):
        return B._decode_slots(params, cfg, toks, pos, caches)
    return fn, (params, np.zeros(2, np.int32), np.zeros(2, np.int32),
                caches)


def _build_moe_gate_tap():
    from repro.models import moe
    from repro.serve import batching as B
    cfg, params, caches = _serve_setup("deepseek_v2_236b", 1)

    def fn(params, toks, pos, caches):
        # the allowlisted observability tap: observe_gates installs a
        # debug_callback inside the decoder layer scan at trace time
        with moe.observe_gates(lambda *_: None):
            return B._decode_slots(params, cfg, toks, pos, caches)
    return fn, (params, np.zeros(1, np.int32), np.zeros(1, np.int32),
                caches)


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------

_T = frozenset

ENTRIES: Tuple[EntryPoint, ...] = (
    EntryPoint(
        name="engine/kway_refine",
        build=functools.partial(_build_kway, False),
        tags=_T({"bucket", "padding", "hygiene"}),
        bucket_dims=_kway_bucket_dims,
        padding=PaddingSpec(_perturb_kway, _project_kway),
        drivers=("kaffpa", "kaffpa_balance_NE", "kaffpaE", "reduced_nd",
                 "fast_reduced_nd", "process_mapping"),
    ),
    EntryPoint(
        name="engine/kway_refine_kernel",
        build=functools.partial(_build_kway, True),
        tags=_T({"bucket", "padding", "hygiene"}),
        bucket_dims=_kway_bucket_dims,
        padding=PaddingSpec(_perturb_kway_kernel, _project_kway),
        drivers=("kaffpa",),
    ),
    EntryPoint(
        name="engine/cluster_lp",
        build=_build_cluster_lp,
        tags=_T({"bucket", "padding", "hygiene"}),
        bucket_dims=lambda args: {"n_pad": args[0].n_pad,
                                  "e_pad": args[0].e_pad},
        padding=PaddingSpec(_perturb_cluster_lp, _project_cluster_lp),
        drivers=("kaffpa", "kahypar", "node_separator"),
    ),
    EntryPoint(
        name="engine/hyper_refine_km1",
        build=functools.partial(_build_hyper, "km1"),
        tags=_T({"bucket", "padding", "hygiene"}),
        bucket_dims=_hyper_bucket_dims,
        padding=PaddingSpec(_perturb_hyper, _project_hyper),
        drivers=("kahypar", "kahyparE"),
    ),
    EntryPoint(
        name="engine/hyper_refine_cut",
        build=functools.partial(_build_hyper, "cut"),
        tags=_T({"bucket", "padding", "hygiene"}),
        bucket_dims=_hyper_bucket_dims,
        padding=PaddingSpec(_perturb_hyper, _project_hyper),
        drivers=("kahypar", "kahyparE"),
    ),
    EntryPoint(
        name="engine/sep_refine",
        build=_build_sep,
        tags=_T({"bucket", "padding", "hygiene"}),
        bucket_dims=lambda args: {"n_pad": args[0].n_pad,
                                  "e_pad": args[0].e_pad,
                                  "batch": args[1].shape[0]},
        padding=PaddingSpec(_perturb_sep, _project_sep),
        drivers=("node_separator", "reduced_nd", "fast_reduced_nd"),
    ),
    EntryPoint(
        name="dist/parhyp_round",
        build=_build_parhyp,
        tags=_T({"bucket", "padding", "spmd", "hygiene"}),
        bucket_dims=_parhyp_bucket_dims,
        padding=PaddingSpec(_perturb_parhyp, _project_parhyp),
        drivers=("parhyp",),
    ),
    EntryPoint(
        name="dist/parhyp_round_2d",
        build=functools.partial(_build_parhyp, True),
        tags=_T({"bucket", "padding", "spmd", "hygiene"}),
        bucket_dims=_parhyp_bucket_dims,
        padding=PaddingSpec(_perturb_parhyp, _project_parhyp),
        drivers=("parhyp",),
    ),
    EntryPoint(
        name="dist/cluster_round",
        build=_build_parhyp_cluster,
        tags=_T({"bucket", "padding", "spmd", "hygiene"}),
        bucket_dims=_cluster_bucket_dims,
        padding=PaddingSpec(_perturb_parhyp_cluster,
                            _project_parhyp_cluster),
        drivers=("parhyp",),
    ),
    EntryPoint(
        name="dist/contract",
        build=_build_parhyp_contract,
        tags=_T({"bucket", "padding", "spmd", "hygiene"}),
        bucket_dims=_cluster_bucket_dims,
        padding=PaddingSpec(_perturb_parhyp_contract,
                            _project_parhyp_contract),
        drivers=("parhyp",),
    ),
    EntryPoint(
        name="memetic/migrate_ring",
        build=_build_migrate,
        tags=_T({"spmd", "hygiene"}),
        drivers=("kaffpaE", "kahyparE"),
    ),
    EntryPoint(
        name="kernels/lp_affinity",
        build=_build_lp_affinity,
        tags=_T({"bucket", "padding", "hygiene"}),
        bucket_dims=lambda args: {"n_pad": args[0].shape[0],
                                  "dmax": args[0].shape[1]},
        padding=PaddingSpec(_perturb_lp_affinity,
                            lambda outs: [_np(outs[0])[:24]]),
    ),
    EntryPoint(
        name="kernels/sep_affinity",
        build=_build_sep_affinity,
        tags=_T({"bucket", "padding", "hygiene"}),
        bucket_dims=lambda args: {"n_pad": args[0].shape[0],
                                  "dmax": args[0].shape[1]},
        padding=PaddingSpec(_perturb_sep_affinity,
                            lambda outs: [_np(outs[0])[:24]]),
    ),
    EntryPoint(
        name="kernels/pin_count",
        build=_build_pin_count,
        tags=_T({"bucket", "padding", "hygiene"}),
        bucket_dims=lambda args: {"e_pad": args[0].shape[0],
                                  "pmax": args[0].shape[1]},
        padding=PaddingSpec(_perturb_pin_count,
                            lambda outs: [_np(outs[0])[:12],
                                          _np(outs[1])[:12]]),
    ),
    EntryPoint(
        name="kernels/pin_affinity",
        build=_build_pin_affinity,
        tags=_T({"bucket", "padding", "hygiene"}),
        bucket_dims=lambda args: {"n_pad": args[0].shape[0],
                                  "dvmax": args[0].shape[1],
                                  "e_pad": args[1].shape[0],
                                  "pmax": args[1].shape[1]},
        padding=PaddingSpec(_perturb_pin_affinity,
                            lambda outs: [_np(outs[0])[:20]]),
    ),
    EntryPoint(
        name="kernels/ssd_scan",
        build=_build_ssd,
        tags=_T({"bucket", "hygiene"}),
        bucket_dims=lambda args: {"seq": args[0].shape[1]},
    ),
    EntryPoint(
        name="serve/prefill_step1",
        build=_build_prefill_step1,
        tags=_T({"hygiene"}),
    ),
    EntryPoint(
        name="serve/decode_slots",
        build=_build_decode_slots,
        tags=_T({"hygiene"}),
    ),
    EntryPoint(
        name="serve/moe_gate_tap",
        build=_build_moe_gate_tap,
        tags=_T({"hygiene"}),
        allow_callbacks=("debug_callback",),
    ),
)


def default_registry() -> Dict[str, EntryPoint]:
    return {e.name: e for e in ENTRIES}


#: public driver (interface.py) -> entry names that cover its traced core;
#: the registry-hygiene lint fails when a driver is missing here or names
#: an unknown entry.
DRIVER_ENTRIES: Dict[str, Tuple[str, ...]] = {
    "kaffpa": ("engine/kway_refine", "engine/kway_refine_kernel",
               "engine/cluster_lp"),
    "kaffpa_balance_NE": ("engine/kway_refine",),
    "kaffpaE": ("engine/kway_refine", "memetic/migrate_ring"),
    "kahypar": ("engine/hyper_refine_km1", "engine/hyper_refine_cut",
                "engine/cluster_lp"),
    "kahyparE": ("engine/hyper_refine_km1", "memetic/migrate_ring"),
    "parhyp": ("dist/parhyp_round", "dist/parhyp_round_2d",
               "dist/cluster_round", "dist/contract"),
    "node_separator": ("engine/sep_refine", "engine/cluster_lp"),
    "reduced_nd": ("engine/sep_refine", "engine/kway_refine"),
    "fast_reduced_nd": ("engine/sep_refine", "engine/kway_refine"),
    "process_mapping": ("engine/kway_refine",),
}
