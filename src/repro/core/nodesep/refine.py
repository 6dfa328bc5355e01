"""Size-constrained separator refinement (DESIGN.md §8) — device side.

The 3-label state {A=0, B=1, S=2} is refined with the batch-synchronous LP
adaptation of FM for node separators: per round every separator vertex
computes its *pull-in cost* for leaving S into one side, a conflict-free
subset of moves is applied under the block-size caps, and the opposite-side
neighbours of every mover are pulled into S (the two-hop mask that keeps
the invariant "no A vertex adjacent to a B vertex" by construction).

The gain of moving v from S into block ``s`` is

    gain(v → s) = w(v) − Σ { w(u) : u ∈ N(v), label(u) = other(s) }

i.e. the separator sheds w(v) and absorbs the opposite-side neighbours.
The per-neighbour *vertex-weight* histogram aff[v, b] = Σ_{u∈N(v)} w(u)·
[label(u)=b] is exactly the lp_affinity contraction with k=3 and the edge
weights replaced by gathered neighbour vertex weights — so the existing
Pallas kernel (kernels/lp_affinity.py) is the TPU path and the COO scatter
here is the jnp fallback/oracle (bit-exact: integer-valued f32 sums).

Rounds alternate the target side (A on even parity, B on odd): with all
moves of a round going to one side, a mover can never become adjacent to
the opposite block — its opposite-side neighbours are pulled into S in the
same update.  Summed single-move gains are conservative (a pulled vertex
shared by two movers is counted twice but enters S once), and undo-to-best
over feasible states guards the objective like every other refiner here.
"""
from __future__ import annotations

import functools
from typing import List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.csr import (Graph, CooGraph, EllGraph, neighbour_any,
                            to_coo, to_ell)

SEP = 2                 # the separator label
_NEG = -1e30
_NOISE = 1e-4           # random tie-break amplitude
_GAIN_EPS = 1e-3        # strictly-positive-gain threshold (> noise)


# ---------------------------------------------------------------------------
# neighbour vertex-weight affinity: jnp oracle + Pallas kernel path
# ---------------------------------------------------------------------------

def sep_affinity_coo(g: CooGraph, labels: jax.Array) -> jax.Array:
    """aff[v, b] = total *vertex weight* of v's neighbours with label b.

    (n_pad, 3).  Padding edges carry w == 0 and are masked out explicitly:
    when n == n_pad the sentinel row is a real vertex with nonzero weight.
    """
    contrib = jnp.where(g.w > 0, g.vwgt[g.dst], 0.0)
    return jnp.zeros((g.n_pad, 3), jnp.float32).at[g.src, labels[g.dst]].add(
        contrib)


def sep_affinity_ell(ell: EllGraph, labels: jax.Array,
                     use_pallas: bool = True) -> jax.Array:
    """Kernel path: the ``sep_affinity`` op (kernels/ops.py) — lp_affinity
    with k=3 over neighbour vertex weights, ``wgt > 0`` invariant mask."""
    from repro.kernels import ops as kops
    return kops.sep_affinity(ell.nbr, ell.wgt, ell.vwgt, labels,
                             use_pallas=use_pallas)


# ---------------------------------------------------------------------------
# the separator LP/FM scan
# ---------------------------------------------------------------------------

def _sep_refine_scan(g: CooGraph, labels0: jax.Array, cap: jax.Array,
                     key: jax.Array, rounds: int, force_balance,
                     ell: Optional[EllGraph] = None,
                     use_kernel: bool = False):
    """``rounds`` one-side-per-round separator moves with undo-to-best.

    Unjitted scan body — vmapped by `_sep_refine_scan_batch` (shared graph)
    and `_sep_refine_scan_multi` (stacked sibling graphs, DESIGN.md §12);
    single refines ride the batched program at the medium's batch floor.

    ``cap`` is (2,) — the block-size caps for A and B; S is uncapped (its
    weight *is* the objective).  ``force_balance`` may be a Python bool or a
    traced scalar (the batched tournament vmaps candidates with mixed
    feasibility): overweight blocks push boundary vertices into S, capped at
    the overshoot so balance restoration inflates S minimally.
    """
    n = g.n_pad
    vw = g.vwgt
    from repro.core.lp import capped_accept

    if use_kernel and ell is not None:
        affinity = lambda lab: sep_affinity_ell(ell, lab)      # noqa: E731
    else:
        affinity = lambda lab: sep_affinity_coo(g, lab)        # noqa: E731

    def sizes_of(lab):
        return jnp.zeros((3,), jnp.float32).at[lab].add(vw)

    sizes0 = sizes_of(labels0)
    feas0 = (sizes0[0] <= cap[0] + 1e-6) & (sizes0[1] <= cap[1] + 1e-6)
    best_w0 = jnp.where(feas0, sizes0[SEP], jnp.inf)

    def body(carry, key_r):
        labels, sizes, best_w, best_labels, parity = carry
        side = (parity % 2).astype(labels.dtype)       # this round's target
        other = (1 - side).astype(labels.dtype)
        aff = affinity(labels)
        noise = jax.random.uniform(key_r, (n,), jnp.float32, 0.0, _NOISE)
        in_sep = labels == SEP
        # gain of leaving S into `side`: shed w(v), absorb other-side nbrs
        gain = vw - aff[jnp.arange(n), other] + noise
        # plateau rounds (every third) admit zero-gain moves: the separator
        # slides sideways to thinner regions; undo-to-best keeps it safe
        thresh = jnp.where(parity % 3 == 2, -_GAIN_EPS, _GAIN_EPS)
        want_move = in_sep & (gain > thresh)
        # forced balance: the most-overweight block pushes into S
        overshoot0 = sizes[0] - cap[0]
        overshoot1 = sizes[1] - cap[1]
        over_blk = jnp.where(overshoot0 >= overshoot1, 0, 1).astype(
            labels.dtype)
        overshoot = jnp.maximum(jnp.maximum(overshoot0, overshoot1), 0.0)
        forced = jnp.asarray(force_balance) & (overshoot > 0)
        want_push = forced & (labels == over_blk) & (vw > 0)
        # parity mask (avoid neighbouring-move oscillation)
        node_par = (jnp.arange(n) + parity) % 2 == 0
        want_move = want_move & node_par
        want_push = want_push & node_par
        proposal = jnp.where(want_move, side, labels)
        proposal = jnp.where(want_push, SEP, proposal)
        # pushes prefer boundary vertices (adjacent to S or the other side)
        pri = jnp.where(want_move, gain, _NEG)
        pri = jnp.where(want_push,
                        aff[jnp.arange(n), SEP] + aff[jnp.arange(n), other]
                        + noise, pri)
        # S admits at most the overshoot (padded by one vertex so integer
        # weights can actually cross it), so forced pushes stop at balance
        push_room = jnp.where(overshoot > 0, overshoot + jnp.max(vw), 0.0)
        cap3 = jnp.stack([cap[0], cap[1], sizes[SEP] + push_room])
        new_labels = capped_accept(labels, proposal, vw, sizes, cap3, pri)
        # two-hop pull-in: opposite-side neighbours of movers enter S
        moved = (new_labels != labels) & in_sep
        reach = neighbour_any(g, moved, ell)
        pulled = reach & (labels == other)
        new_labels = jnp.where(pulled, SEP, new_labels)
        new_sizes = sizes_of(new_labels)
        feas = ((new_sizes[0] <= cap[0] + 1e-6)
                & (new_sizes[1] <= cap[1] + 1e-6))
        better = feas & (new_sizes[SEP] < best_w)
        best_w = jnp.where(better, new_sizes[SEP], best_w)
        best_labels = jnp.where(better, new_labels, best_labels)
        return (new_labels, new_sizes, best_w, best_labels,
                parity + 1), new_sizes[SEP]

    keys = jax.random.split(key, rounds)
    (labels, sizes, best_w, best_labels, _), _ = jax.lax.scan(
        body, (labels0, sizes0, best_w0, labels0, jnp.int32(0)), keys)
    have_best = jnp.isfinite(best_w)
    out = jnp.where(have_best, best_labels, labels)
    return out, jnp.where(have_best, best_w, sizes[SEP])


@functools.partial(jax.jit, static_argnames=("rounds", "use_kernel"))
def _sep_refine_scan_batch(g: CooGraph, labels0: jax.Array, cap: jax.Array,
                           keys: jax.Array, force: jax.Array, rounds: int,
                           ell: Optional[EllGraph] = None,
                           use_kernel: bool = False):
    """THE separator refinement program (one graph, b candidates)."""
    def one(lab0, key, f):
        return _sep_refine_scan(g, lab0, cap, key, rounds, f, ell=ell,
                                use_kernel=use_kernel)
    return jax.vmap(one)(labels0, keys, force)


@functools.partial(jax.jit, static_argnames=("rounds", "use_kernel"))
def _sep_refine_scan_multi(gs: CooGraph, labels0: jax.Array, caps: jax.Array,
                           keys: jax.Array, force: jax.Array, rounds: int,
                           use_kernel: bool = False):
    """Batched tournament over *stacked sibling graphs* at one shape bucket
    (nested-dissection wave, DESIGN.md §12): ``gs`` is a CooGraph whose
    arrays carry a leading batch dim; row i refines candidate i on graph i
    under caps ``caps[i]`` (B, 2)."""
    def one(g, lab0, cap, key, f):
        return _sep_refine_scan(g, lab0, cap, key, rounds, f, ell=None,
                                use_kernel=use_kernel)
    return jax.vmap(one)(gs, labels0, caps, keys, force)


# ---------------------------------------------------------------------------
# host wrappers + metrics
# ---------------------------------------------------------------------------

def separator_caps(g: Graph, eps: float) -> np.ndarray:
    """Block caps: max(w(A), w(B)) ≤ (1+eps)·⌈w(V)/2⌉ (§2.8 constraint)."""
    lmax = np.ceil(g.total_vwgt() / 2.0)
    return np.full(2, (1.0 + eps) * lmax)


def separator_weight(g: Graph, labels: np.ndarray) -> int:
    return int(g.vwgt[np.asarray(labels) == SEP].sum())


def separator_is_feasible(g: Graph, labels: np.ndarray, eps: float) -> bool:
    labels = np.asarray(labels)
    cap = separator_caps(g, eps)
    wa = int(g.vwgt[labels == 0].sum())
    wb = int(g.vwgt[labels == 1].sum())
    return wa <= cap[0] + 1e-9 and wb <= cap[1] + 1e-9


def separator_invariant_ok(g: Graph, labels: np.ndarray) -> bool:
    """The structural invariant: no A vertex is adjacent to a B vertex."""
    labels = np.asarray(labels)
    src = g.edge_sources()
    a, b = labels[src], labels[g.adjncy]
    return not np.any(((a == 0) & (b == 1)) | ((a == 1) & (b == 0)))


def _pad_labels3(labels: np.ndarray, n_pad: int) -> jnp.ndarray:
    lab = np.zeros(n_pad, dtype=np.int32)
    lab[:len(labels)] = labels
    return jnp.asarray(lab)


def _run_sep_scan_batch(coo, cap_np, labs, keys, force, rounds,
                        ell, use_kernel, batch_floor):
    from repro.core import multilevel as ML
    from repro.core.refine import _pad_rows, batch_bucket
    b = labs.shape[0]
    b_pad = batch_bucket(b, batch_floor)
    ML.note_bucket_pad(b_pad - b)
    ML.note_program("sep", coo.n_pad, coo.e_pad, rounds, b_pad, use_kernel)
    outs, _ = _sep_refine_scan_batch(
        coo, jnp.asarray(_pad_rows(labs, b_pad)),
        jnp.asarray(np.asarray(cap_np, np.float32)),
        jnp.asarray(_pad_rows(keys, b_pad)),
        jnp.asarray(_pad_rows(force, b_pad)),
        rounds, ell=ell, use_kernel=use_kernel)
    return np.asarray(outs, dtype=np.int64)[:b]


def refine_separator(g: Graph, labels: np.ndarray, eps: float = 0.20,
                     rounds: int = 10, seed: int = 0,
                     coo: Optional[CooGraph] = None,
                     ell: Optional[EllGraph] = None,
                     use_kernel: Optional[bool] = None,
                     force_balance: bool = False,
                     batch_floor: int = 1) -> np.ndarray:
    """Polish a 3-label state; never worsens a feasible separator weight."""
    if g.n == 0:
        return np.asarray(labels, dtype=np.int64)
    from repro.core.refine import default_use_kernel
    use_kernel = default_use_kernel() if use_kernel is None else use_kernel
    coo = coo if coo is not None else to_coo(g)
    if use_kernel and ell is None:
        ell = to_ell(g, row_tile=coo.n_pad)
    labs = np.zeros((1, coo.n_pad), dtype=np.int32)
    labs[0, :g.n] = labels
    keys = np.asarray(jax.random.PRNGKey(seed))[None]
    outs = _run_sep_scan_batch(coo, separator_caps(g, eps), labs, keys,
                               np.asarray([force_balance]), rounds,
                               ell, use_kernel, batch_floor)
    out = outs[0][:g.n]
    # paranoia: keep the better of (in, out) among feasible options
    if force_balance:
        return out
    if (separator_weight(g, out) <= separator_weight(g, labels)
            or not separator_is_feasible(g, labels, eps)):
        return out
    return np.asarray(labels, dtype=np.int64)


def refine_separator_batch(g: Graph, cands: List[np.ndarray],
                           eps: float = 0.20, rounds: int = 10, seed: int = 0,
                           coo: Optional[CooGraph] = None,
                           ell: Optional[EllGraph] = None,
                           use_kernel: Optional[bool] = None,
                           keys: Optional[np.ndarray] = None,
                           batch_floor: int = 1) -> List[np.ndarray]:
    """Refine several 3-label candidates in one vmapped device call."""
    if g.n == 0 or not cands:
        return [np.asarray(c, dtype=np.int64) for c in cands]
    from repro.core.refine import default_use_kernel
    use_kernel = default_use_kernel() if use_kernel is None else use_kernel
    coo = coo if coo is not None else to_coo(g)
    if use_kernel and ell is None:
        ell = to_ell(g, row_tile=coo.n_pad)
    labs = np.zeros((len(cands), coo.n_pad), dtype=np.int32)
    for i, c in enumerate(cands):
        labs[i, :g.n] = c
    force = np.asarray([not separator_is_feasible(g, c, eps) for c in cands])
    if keys is None:
        keys = np.asarray(jax.random.split(jax.random.PRNGKey(seed),
                                           len(cands)))
    outs = _run_sep_scan_batch(coo, separator_caps(g, eps), labs,
                               np.asarray(keys), force, rounds,
                               ell, use_kernel, batch_floor)
    outs = outs[:, :g.n]
    result = []
    for i, c in enumerate(cands):
        if (separator_weight(g, outs[i]) <= separator_weight(g, c)
                or force[i]):
            result.append(outs[i])
        else:
            result.append(np.asarray(c, dtype=np.int64))
    return result


def refine_separator_multi(graphs: List[Graph],
                           cands_lists: List[List[np.ndarray]],
                           eps: float = 0.20, rounds: int = 10,
                           seeds: Optional[List[int]] = None,
                           coos: Optional[List[CooGraph]] = None
                           ) -> List[List[np.ndarray]]:
    """Refine the candidate tournaments of several *sibling graphs sharing
    one shape bucket* in a single vmapped device call (DESIGN.md §12).

    Per graph this is bit-identical to ``refine_separator_batch(graphs[i],
    cands_lists[i], seed=seeds[i])`` — rows carry per-graph keys
    ``split(PRNGKey(seeds[i]), len(cands_lists[i]))``, caps and arrays, so
    batching changes only which compiled program runs them.
    """
    if not graphs:
        return []
    seeds = seeds if seeds is not None else [0] * len(graphs)
    coos = coos if coos is not None else [to_coo(g) for g in graphs]
    n_pad = coos[0].n_pad
    e_pad = coos[0].e_pad
    assert all(c.n_pad == n_pad and c.e_pad == e_pad for c in coos), \
        "refine_separator_multi requires one shape bucket"
    rows_g, rows_lab, rows_cap, rows_key, rows_force = [], [], [], [], []
    owner = []
    for i, (g, cands) in enumerate(zip(graphs, cands_lists)):
        if not cands:
            continue
        cap = np.asarray(separator_caps(g, eps), np.float32)
        keys = np.asarray(jax.random.split(jax.random.PRNGKey(seeds[i]),
                                           len(cands)))
        for j, c in enumerate(cands):
            lab = np.zeros(n_pad, dtype=np.int32)
            lab[:g.n] = c
            rows_g.append(coos[i])
            rows_lab.append(lab)
            rows_cap.append(cap)
            rows_key.append(keys[j])
            rows_force.append(not separator_is_feasible(g, c, eps))
            owner.append((i, j))
    if not rows_g:
        return [[] for _ in graphs]
    from repro.core import multilevel as ML
    from repro.core.refine import batch_bucket
    b = len(rows_g)
    b_pad = batch_bucket(b, 1)
    ML.note_bucket_pad(b_pad - b)
    ML.note_program("sepmulti", n_pad, e_pad, rounds, b_pad, False)
    while len(rows_g) < b_pad:        # pad rows repeat row 0 (inert)
        rows_g.append(rows_g[0])
        rows_lab.append(rows_lab[0])
        rows_cap.append(rows_cap[0])
        rows_key.append(rows_key[0])
        rows_force.append(False)
    import jax.tree_util as jtu
    gs = jtu.tree_map(lambda *xs: jnp.stack(xs), *rows_g)
    outs, _ = _sep_refine_scan_multi(
        gs, jnp.asarray(np.stack(rows_lab)),
        jnp.asarray(np.stack(rows_cap)),
        jnp.asarray(np.stack(rows_key)),
        jnp.asarray(np.asarray(rows_force)), rounds, use_kernel=False)
    outs = np.asarray(outs, dtype=np.int64)
    result: List[List[np.ndarray]] = [[] for _ in graphs]
    for row, (i, j) in enumerate(owner):
        g, c = graphs[i], cands_lists[i][j]
        out = outs[row][:g.n]
        # same per-candidate paranoia as refine_separator_batch
        if (separator_weight(g, out) <= separator_weight(g, c)
                or not separator_is_feasible(g, c, eps)):
            result[i].append(out)
        else:
            result[i].append(np.asarray(c, dtype=np.int64))
    return result


# ---------------------------------------------------------------------------
# boundary → separator conversion and the vertex-cover polish (host)
# ---------------------------------------------------------------------------

def boundary_to_separator(g: Graph, part2: np.ndarray) -> np.ndarray:
    """Lift a bipartition to a 3-label state: the lighter boundary side
    becomes S (the paper's trivial separator, §2.8) — invariant holds by
    construction because non-boundary vertices have no cross-block edge."""
    part2 = np.asarray(part2, dtype=np.int64)
    labels = part2.copy()
    src = g.edge_sources()
    cut = part2[src] != part2[g.adjncy]
    b0 = np.unique(src[cut & (part2[src] == 0)])
    b1 = np.unique(src[cut & (part2[src] == 1)])
    w0 = int(g.vwgt[b0].sum())
    w1 = int(g.vwgt[b1].sum())
    labels[b0 if w0 <= w1 else b1] = SEP
    return labels


def flow_separator_polish(g: Graph, labels: np.ndarray, eps: float,
                          band_depth: int = 3,
                          max_band: int = 4000) -> np.ndarray:
    """Optimal separator within a band around S via node-capacitated max-flow
    (the §2.8 'advanced flow-based separator' idea that superseded the
    post-hoc construction).

    Every band vertex v is split into v_in → v_out with capacity w(v); band
    edges get infinite capacity, the source feeds band vertices adjacent to
    the retained A region and the sink drains those adjacent to retained B.
    The min s-t cut is then a *minimum-weight vertex set* separating A from
    B inside the band — the invariant holds structurally for the recut
    labels (an A'–B' adjacency would cross an uncut infinite edge).  Band
    growth into a side is capped by the opposite block's slack so any recut
    stays feasible; the result is adopted only if strictly lighter.
    """
    from repro.core.refine import _dinic
    labels = np.asarray(labels, dtype=np.int64)
    in_sep = labels == SEP
    if not in_sep.any() or int(in_sep.sum()) > max_band:
        return labels
    src = g.edge_sources()
    cap_blk = separator_caps(g, eps)
    w_blk = [int(g.vwgt[labels == 0].sum()), int(g.vwgt[labels == 1].sum())]
    w_sep = int(g.vwgt[in_sep].sum())
    band = in_sep.copy()
    # BFS band_depth steps into each side, budgeted by the other side's slack
    for side in (0, 1):
        budget = cap_blk[1 - side] - w_blk[1 - side] - w_sep
        cur = band.copy()
        wsum = 0
        for _ in range(band_depth):
            nxt = np.zeros(g.n, dtype=bool)
            hits = cur[src] & (labels[g.adjncy] == side) & ~band[g.adjncy]
            nxt[g.adjncy[hits]] = True
            add_ids = np.flatnonzero(nxt)
            order = np.argsort(g.vwgt[add_ids])          # cheap nodes first
            for i in add_ids[order]:
                if wsum + int(g.vwgt[i]) > budget or band.sum() >= max_band:
                    break
                band[i] = True
                wsum += int(g.vwgt[i])
            cur = nxt & band
            if not cur.any():
                break
    ids = np.flatnonzero(band)
    if len(ids) == 0 or len(ids) > max_band:
        return labels
    remap = -np.ones(g.n, dtype=np.int64)
    remap[ids] = np.arange(len(ids))
    nb = len(ids)
    S_node, T_node = 2 * nb, 2 * nb + 1
    big = int(g.vwgt.sum()) + 1
    edges = []
    for i, v in enumerate(ids):
        edges.append([2 * i, 2 * i + 1, int(g.vwgt[v])])   # v_in → v_out
    inside = band[src] & band[g.adjncy]
    for e in np.flatnonzero(inside):                       # directed edges
        u, v = remap[src[e]], remap[g.adjncy[e]]
        edges.append([2 * u + 1, 2 * v, big])              # u_out → v_in
    touch_a = band[src] & ~band[g.adjncy] & (labels[g.adjncy] == 0)
    touch_b = band[src] & ~band[g.adjncy] & (labels[g.adjncy] == 1)
    for u in np.unique(src[touch_a]):
        edges.append([S_node, 2 * remap[u], big])
    for u in np.unique(src[touch_b]):
        edges.append([2 * remap[u] + 1, T_node, big])
    _, reach = _dinic(2 * nb + 2, edges, S_node, T_node)
    in_r = reach[0:2 * nb:2]
    out_r = reach[1:2 * nb:2]
    new_labels = labels.copy()
    new_labels[ids] = np.where(in_r & out_r, 0,
                               np.where(in_r & ~out_r, SEP, 1))
    if (separator_weight(g, new_labels) < separator_weight(g, labels)
            and separator_is_feasible(g, new_labels, eps)
            and separator_invariant_ok(g, new_labels)):
        return new_labels
    return labels


def vertex_cover_polish(g: Graph, labels: np.ndarray,
                        eps: float) -> np.ndarray:
    """Replace S with a minimum vertex cover of a boundary bipartite graph.

    S is merged into one side, the resulting 2-way cut's König min-VC is
    extracted (the post-hoc construction, core/separator.py) and adopted iff
    it is lighter and feasible.  Both merge directions are tried.
    """
    from repro.core.separator import separator_from_partition_pair
    labels = np.asarray(labels, dtype=np.int64)
    best = labels
    best_w = separator_weight(g, labels)
    for side in (0, 1):
        part2 = np.where(labels == (1 - side), 1 - side, side)
        sep = separator_from_partition_pair(g, part2, 0, 1)
        cand = part2.copy()
        cand[sep] = SEP
        w = separator_weight(g, cand)
        if (w < best_w and separator_is_feasible(g, cand, eps)
                and separator_invariant_ok(g, cand)):
            best, best_w = cand, w
    return best
