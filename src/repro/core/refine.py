"""Uncoarsening refinement (paper §2.1).

Three refiners, mirroring KaFFPa's arsenal under the batch-synchronous
adaptation documented in DESIGN.md §2:

  * ``refine_kway``      — round-based k-way gain refinement (the FM variant:
    all boundary nodes eligible, best-gain moves, balance-capped, undo to the
    best feasible cut seen).
  * ``multi_try_refine`` — the *multi-try FM* analogue: search is seeded from
    a random subset of boundary nodes and expands only through moved nodes'
    neighbourhoods (localized search escapes local optima, §2.1).
  * ``flow_refine``      — max-flow min-cut improvement on the boundary band
    of a block pair (host-side Dinic; the ``strong`` preset applies it on
    small/coarse levels, where KaHIP also concentrates its flow budget).
"""
from __future__ import annotations

import functools
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.csr import (Graph, CooGraph, EllGraph, neighbour_any,
                            to_coo, to_ell)
from repro.core.partition import edge_cut_device, edge_cut, is_feasible
from repro.core import lp as lp_mod


def default_use_kernel() -> bool:
    """Resolve ``use_kernel=None``: the Pallas affinity kernels are the
    default k-way refinement path on TPU; off-TPU they would run in
    interpret mode, so the COO scatter fallback/oracle is used instead."""
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# batched k-way gain refinement
#
# One jitted program per (bucket, k, rounds, batch bucket): the former
# allow_zero_gain / localized static flags are traced per batch row, and
# every entry point (single refine, multi-try, tournament) routes through
# the same vmapped scan — padded to the medium's pow2 batch bucket so
# hierarchy levels, V-cycles, islands and ND subproblems at the same shape
# share one compile (DESIGN.md §12).
# ---------------------------------------------------------------------------

def _refine_scan(g: CooGraph, labels0: jax.Array, cap: jax.Array,
                 rkeys: jax.Array, nrounds: jax.Array, k: int, rounds: int,
                 allow_zero_gain, force_balance,
                 active0: jax.Array,
                 ell: Optional[EllGraph] = None, use_kernel: bool = False):
    """One candidate's scan body (unjitted; vmapped by `_refine_scan_batch`).

    ``allow_zero_gain`` and ``force_balance`` are traced booleans; the
    localized-search reach expansion always runs (with ``active0`` all-ones
    it is the identity, bit-identical to an unmasked scan).  ``rkeys`` holds
    the per-round PRNG keys (``rounds``, 2) precomputed on the host, and
    ``nrounds`` (traced) masks trailing rounds to no-ops — a short search
    (e.g. multi-try's ``rounds//2``) keeps its exact ``split(key, r)`` key
    sequence while sharing the full-length compiled program.
    """
    n = g.n_pad
    vw = g.vwgt
    sizes0 = jnp.zeros((k,), jnp.float32).at[labels0].add(vw)
    cut0 = edge_cut_device(g, labels0)
    feas0 = jnp.max(sizes0 - cap) <= 1e-6
    best_cut0 = jnp.where(feas0, cut0, jnp.inf)
    affinity_fn = None
    if use_kernel and ell is not None:
        from repro.kernels import ops as kops
        affinity_fn = lambda _g, lab, kk: kops.lp_affinity(   # noqa: E731
            ell.nbr, ell.wgt, lab, kk)

    def body(carry, key_r):
        labels, sizes, active, best_cut, best_labels, parity = carry
        prop_labels, prop_sizes = lp_mod.kway_lp_round(
            g, labels, sizes, cap, key_r, k, parity,
            active, allow_zero_gain, force_balance,
            affinity_fn=affinity_fn)
        live = parity < nrounds
        new_labels = jnp.where(live, prop_labels, labels)
        new_sizes = jnp.where(live, prop_sizes, sizes)
        moved = new_labels != labels
        reach = neighbour_any(g, moved, ell)
        active = active | reach | moved
        cut = edge_cut_device(g, new_labels)
        feas = jnp.max(new_sizes - cap) <= 1e-6
        better = feas & (cut < best_cut)
        best_cut = jnp.where(better, cut, best_cut)
        best_labels = jnp.where(better, new_labels, best_labels)
        return (new_labels, new_sizes, active, best_cut, best_labels,
                parity + 1), cut

    (labels, sizes, _, best_cut, best_labels, _), cuts = jax.lax.scan(
        body, (labels0, sizes0, active0, best_cut0, labels0, jnp.int32(0)),
        rkeys)
    # undo-to-best (KaFFPa semantics): return best feasible if one was seen
    have_best = jnp.isfinite(best_cut)
    out = jnp.where(have_best, best_labels, labels)
    return out, jnp.where(have_best, best_cut, edge_cut_device(g, labels))


@functools.partial(jax.jit, static_argnames=("k", "rounds", "use_kernel"))
def _refine_scan_batch(g: CooGraph, labels0: jax.Array, cap: jax.Array,
                       rkeys: jax.Array, nrounds: jax.Array,
                       zero_gain: jax.Array, force: jax.Array,
                       active0: jax.Array, k: int, rounds: int,
                       ell: Optional[EllGraph] = None,
                       use_kernel: bool = False):
    """THE k-way refinement program: everything routes through here."""
    def one(lab0, rk, nr, z, f, a0):
        return _refine_scan(g, lab0, cap, rk, nr, k, rounds, z, f, a0,
                            ell=ell, use_kernel=use_kernel)
    return jax.vmap(one)(labels0, rkeys, nrounds, zero_gain, force, active0)


def _caps_for(g: Graph, k: int, eps: float,
              fractions: Optional[np.ndarray] = None) -> np.ndarray:
    total = g.total_vwgt()
    if fractions is None:
        lmax = np.ceil(total / k)
        return np.full(k, (1.0 + eps) * lmax)
    return (1.0 + eps) * np.asarray(fractions) * total


def _pad_labels(part: np.ndarray, n_pad: int) -> jnp.ndarray:
    lab = np.zeros(n_pad, dtype=np.int32)
    lab[:len(part)] = part
    return jnp.asarray(lab)


def batch_bucket(b: int, batch_floor: int = 1) -> int:
    """pow2 batch bucket shared by singles and tournaments at a floor."""
    from repro.core.csr import _pow2_pad
    return max(_pow2_pad(max(b, 1), 1), _pow2_pad(max(batch_floor, 1), 1))


def _pad_rows(arr: np.ndarray, b_pad: int) -> np.ndarray:
    """Pad the batch dim to ``b_pad`` by repeating row 0 (rows are
    independent under vmap, so padding rows never change real rows)."""
    b = arr.shape[0]
    if b == b_pad:
        return arr
    return np.concatenate([arr, np.broadcast_to(arr[:1],
                                                (b_pad - b,) + arr.shape[1:])])


def _round_keys(key, rounds: int, rounds_bucket: int) -> np.ndarray:
    """Host-side per-round key schedule (``rounds_bucket``, 2): the first
    ``rounds`` entries are exactly ``split(key, rounds)``; the padding tail
    feeds masked no-op rounds."""
    ks = np.asarray(jax.random.split(key, rounds))
    if rounds < rounds_bucket:
        ks = np.concatenate(
            [ks, np.broadcast_to(ks[:1], (rounds_bucket - rounds, 2))])
    return ks


def _run_scan_batch(coo, cap_np, labs, rkeys, nrounds, zero, force, active,
                    k, rounds_bucket, ell, use_kernel, batch_floor):
    """Shared batched-entry plumbing: pow2-pad the batch dim, count bucket
    pads and program-cache hits, run the one jitted program."""
    from repro.core import multilevel as ML
    b = labs.shape[0]
    b_pad = batch_bucket(b, batch_floor)
    ML.note_bucket_pad(b_pad - b)
    ML.note_program("kway", coo.n_pad, coo.e_pad, k, rounds_bucket, b_pad,
                    use_kernel)
    outs, _ = _refine_scan_batch(
        coo, jnp.asarray(_pad_rows(labs, b_pad)),
        jnp.asarray(np.asarray(cap_np, np.float32)),
        jnp.asarray(_pad_rows(rkeys, b_pad)),
        jnp.asarray(_pad_rows(np.asarray(nrounds, np.int32), b_pad)),
        jnp.asarray(_pad_rows(zero, b_pad)),
        jnp.asarray(_pad_rows(force, b_pad)),
        jnp.asarray(_pad_rows(active, b_pad)),
        k, rounds_bucket, ell=ell, use_kernel=use_kernel)
    return np.asarray(outs, dtype=np.int64)[:b]


def refine_kway(g: Graph, part: np.ndarray, k: int, eps: float = 0.03,
                rounds: int = 12, seed: int = 0,
                fractions: Optional[np.ndarray] = None,
                coo: Optional[CooGraph] = None,
                force_balance: bool = False,
                use_kernel: Optional[bool] = None,
                ell: Optional[EllGraph] = None,
                batch_floor: int = 1,
                rounds_bucket: Optional[int] = None) -> np.ndarray:
    """Polish ``part``; never returns a worse feasible cut (undo-to-best).

    ``use_kernel=None`` resolves to the backend default (Pallas on TPU, COO
    scatter elsewhere); ``coo``/``ell`` accept cached per-level views.
    ``batch_floor`` pads the batch dim up to the medium's bucket so this
    single call reuses the tournament's compiled program; ``rounds_bucket``
    likewise pads the round schedule (extra rounds are masked no-ops).
    """
    if k <= 1 or g.n == 0:
        return part
    use_kernel = default_use_kernel() if use_kernel is None else use_kernel
    coo = coo if coo is not None else to_coo(g)
    if use_kernel and ell is None:
        ell = to_ell(g, row_tile=coo.n_pad)   # same n_pad as the COO view
    rb = max(rounds, rounds_bucket or 0)
    labs = np.zeros((1, coo.n_pad), dtype=np.int32)
    labs[0, :g.n] = part
    rkeys = _round_keys(jax.random.PRNGKey(seed), rounds, rb)[None]
    outs = _run_scan_batch(coo, _caps_for(g, k, eps, fractions), labs, rkeys,
                           np.asarray([rounds]),
                           np.zeros(1, bool), np.asarray([force_balance]),
                           np.ones((1, coo.n_pad), bool), k, rb, ell,
                           use_kernel, batch_floor)
    out = outs[0][:g.n]
    # paranoia: keep the better of (in, out) among feasible options
    if edge_cut(g, out) <= edge_cut(g, part) or force_balance:
        return out
    return part


def refine_kway_batch(g: Graph, parts: list, k: int, eps: float = 0.03,
                      rounds: int = 12, seed: int = 0,
                      coo: Optional[CooGraph] = None,
                      ell: Optional[EllGraph] = None,
                      use_kernel: Optional[bool] = None,
                      keys: Optional[np.ndarray] = None,
                      batch_floor: int = 1,
                      rounds_bucket: Optional[int] = None) -> list:
    """Refine several candidate partitions in one vmapped device call.

    The initial-partition tournament uses this so all tries share a single
    compile; per-candidate force-balance rides along as a traced scalar.
    ``keys`` overrides the per-candidate PRNG keys (shape ``(b, 2)``) —
    the memetic sweep passes per-island keys so each island's trajectory
    is independent of how many islands are batched together.
    """
    if k <= 1 or g.n == 0 or not parts:
        return [np.asarray(p, dtype=np.int64) for p in parts]
    use_kernel = default_use_kernel() if use_kernel is None else use_kernel
    coo = coo if coo is not None else to_coo(g)
    if use_kernel and ell is None:
        ell = to_ell(g, row_tile=coo.n_pad)
    rb = max(rounds, rounds_bucket or 0)
    labs = np.zeros((len(parts), coo.n_pad), dtype=np.int32)
    for i, p in enumerate(parts):
        labs[i, :g.n] = p
    force = np.asarray([not is_feasible(g, p, k, eps) for p in parts])
    if keys is None:
        keys = np.asarray(jax.random.split(jax.random.PRNGKey(seed),
                                           len(parts)))
    rkeys = np.stack([_round_keys(kk, rounds, rb) for kk in np.asarray(keys)])
    outs = _run_scan_batch(coo, _caps_for(g, k, eps), labs, rkeys,
                           np.full(len(parts), rounds),
                           np.zeros(len(parts), bool),
                           force, np.ones((len(parts), coo.n_pad), bool),
                           k, rb, ell, use_kernel, batch_floor)
    outs = outs[:, :g.n]
    result = []
    for i, p in enumerate(parts):
        # same per-candidate paranoia as refine_kway
        if edge_cut(g, outs[i]) <= edge_cut(g, p) or force[i]:
            result.append(outs[i])
        else:
            result.append(np.asarray(p, dtype=np.int64))
    return result


def multi_try_refine(g: Graph, part: np.ndarray, k: int, eps: float = 0.03,
                     tries: int = 3, rounds: int = 8, seed: int = 0,
                     seed_frac: float = 0.05,
                     coo: Optional[CooGraph] = None,
                     batch_floor: int = 1,
                     rounds_bucket: Optional[int] = None) -> np.ndarray:
    """Multi-try FM analogue: several localized searches from random boundary
    seeds; keeps the best feasible result."""
    if k <= 1 or g.n == 0:
        return part
    coo = coo if coo is not None else to_coo(g)
    rb = max(rounds, rounds_bucket or 0)
    cap_np = _caps_for(g, k, eps)
    best = np.asarray(part, dtype=np.int64)
    best_cut = edge_cut(g, best)
    rng = np.random.default_rng(seed)
    src = g.edge_sources()
    for t in range(tries):
        labs = np.zeros((1, coo.n_pad), dtype=np.int32)
        labs[0, :g.n] = best
        bnd = np.unique(src[best[src] != best[g.adjncy]])
        if len(bnd) == 0:
            break
        nseed = max(1, int(len(bnd) * seed_frac))
        chosen = rng.choice(bnd, size=nseed, replace=False)
        active0 = np.zeros((1, coo.n_pad), dtype=bool)
        active0[0, chosen] = True
        rkeys = _round_keys(jax.random.PRNGKey(seed * 997 + t),
                            rounds, rb)[None]
        outs = _run_scan_batch(coo, cap_np, labs, rkeys,
                               np.asarray([rounds]),
                               np.ones(1, bool), np.zeros(1, bool),
                               active0, k, rb, None, False, batch_floor)
        out = outs[0][:g.n]
        c = edge_cut(g, out)
        if c < best_cut:
            best, best_cut = out, c
    return best


# ---------------------------------------------------------------------------
# flow-based refinement (host, 2 blocks, boundary band)
# ---------------------------------------------------------------------------

def _dinic(nv: int, edges: list, s: int, t: int):
    """Dinic max-flow. edges: list of [u, v, cap]; returns (flow, S-side set)."""
    graph = [[] for _ in range(nv)]
    for (u, v, c) in edges:
        graph[u].append([v, c, len(graph[v])])
        graph[v].append([u, 0, len(graph[u]) - 1])

    def bfs():
        level = [-1] * nv
        level[s] = 0
        q = [s]
        for u in q:
            for e in graph[u]:
                if e[1] > 0 and level[e[0]] < 0:
                    level[e[0]] = level[u] + 1
                    q.append(e[0])
        return level if level[t] >= 0 else None

    def dfs(u, f, level, it):
        if u == t:
            return f
        while it[u] < len(graph[u]):
            e = graph[u][it[u]]
            if e[1] > 0 and level[e[0]] == level[u] + 1:
                d = dfs(e[0], min(f, e[1]), level, it)
                if d > 0:
                    e[1] -= d
                    graph[e[0]][e[2]][1] += d
                    return d
            it[u] += 1
        return 0

    flow = 0
    while True:
        level = bfs()
        if level is None:
            break
        it = [0] * nv
        while True:
            f = dfs(s, float("inf"), level, it)
            if f == 0:
                break
            flow += f
    # S side of the min cut = reachable in residual
    seen = [False] * nv
    seen[s] = True
    q = [s]
    for u in q:
        for e in graph[u]:
            if e[1] > 0 and not seen[e[0]]:
                seen[e[0]] = True
                q.append(e[0])
    return flow, np.asarray(seen)


def flow_refine_pair(g: Graph, part: np.ndarray, a: int, b: int,
                     eps: float, band_depth: int = 2,
                     max_band: int = 4000) -> np.ndarray:
    """Max-flow min-cut improvement between blocks a and b (paper §2.1).

    Grows a band around the a|b boundary sized so that *any* s-t cut inside
    it keeps both blocks within the balance constraint, then replaces the
    boundary with the min cut.
    """
    part = np.asarray(part, dtype=np.int64)
    k = int(part.max()) + 1
    total = g.total_vwgt()
    lmax = (1.0 + eps) * np.ceil(total / k)
    in_pair = (part == a) | (part == b)
    src = g.edge_sources()
    # boundary nodes of the pair
    bmask = np.zeros(g.n, dtype=bool)
    cutedges = in_pair[src] & in_pair[g.adjncy] & (part[src] != part[g.adjncy])
    bmask[src[cutedges]] = True
    if not bmask.any():
        return part
    wa = int(g.vwgt[part == a].sum())
    wb = int(g.vwgt[part == b].sum())
    # budget: how much weight may cross either way
    slack_a = lmax - wa      # room in a
    slack_b = lmax - wb
    band = bmask.copy()
    # BFS out `band_depth` steps inside each block, capped by slack so every
    # cut in the band is feasible (moving whole band-side stays within lmax)
    for side, slack in ((a, slack_b), (b, slack_a)):
        depth_mask = bmask & (part == side)
        wsum = int(g.vwgt[depth_mask].sum())
        cur = depth_mask
        for _ in range(band_depth):
            nxt = np.zeros(g.n, dtype=bool)
            hits = cur[src] & (part[g.adjncy] == side) & ~band[g.adjncy] & ~cur[g.adjncy]
            nxt[g.adjncy[hits]] = True
            add_ids = np.flatnonzero(nxt)
            order = np.argsort(g.vwgt[add_ids])  # cheap nodes first
            for i in add_ids[order]:
                if wsum + int(g.vwgt[i]) > slack or band.sum() > max_band:
                    break
                band[i] = True
                wsum += int(g.vwgt[i])
            cur = nxt & band
            if not cur.any():
                break
    ids = np.flatnonzero(band)
    if len(ids) > max_band:
        return part
    remap = -np.ones(g.n, dtype=np.int64)
    remap[ids] = np.arange(len(ids))
    nv = len(ids) + 2
    S, T = len(ids), len(ids) + 1
    edges = []
    inside = band[src] & band[g.adjncy]
    fwd = inside & (src < g.adjncy)
    for e in np.flatnonzero(fwd):
        u, v, w = remap[src[e]], remap[g.adjncy[e]], int(g.adjwgt[e])
        edges.append([u, v, w])
        edges.append([v, u, w])
    big = int(g.adjwgt.sum()) + 1
    # attach S to band nodes adjacent to non-band a-side, T to b-side
    touch_a = band[src] & ~band[g.adjncy] & (part[g.adjncy] == a)
    touch_b = band[src] & ~band[g.adjncy] & (part[g.adjncy] == b)
    for u in np.unique(src[touch_a]):
        edges.append([S, remap[u], big])
    for u in np.unique(src[touch_b]):
        edges.append([remap[u], T, big])
    flow, sside = _dinic(nv, edges, S, T)
    new_part = part.copy()
    new_part[ids] = np.where(sside[:len(ids)], a, b)
    # accept only if feasible and not worse
    bw = np.zeros(k, dtype=np.int64)
    np.add.at(bw, new_part, g.vwgt)
    if bw.max() > lmax + 1e-9:
        return part
    if edge_cut(g, new_part) <= edge_cut(g, part):
        return new_part
    return part


def flow_refine_all_pairs(g: Graph, part: np.ndarray, k: int, eps: float,
                          max_n: int = 20000, seed: int = 0) -> np.ndarray:
    """Apply pairwise flow refinement over all adjacent block pairs."""
    if g.n > max_n:
        return part
    part = np.asarray(part, dtype=np.int64)
    src = g.edge_sources()
    for a in range(k):
        for b in range(a + 1, k):
            touching = np.any((part[src] == a) & (part[g.adjncy] == b))
            if touching:
                part = flow_refine_pair(g, part, a, b, eps)
    return part
