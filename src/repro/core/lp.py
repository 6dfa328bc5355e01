"""Size-constrained label propagation (paper §2.4 / §4.10) — device side.

This is the batch-synchronous, TPU-native formulation of KaHIP's LP (see
DESIGN.md §2): per round every node computes its affinity to every candidate
label in parallel, then a conflict-free subset of moves is applied with a
hard size guarantee ("capped acceptance").

Two regimes:
  * clustering  — labels range over [0, n_pad) (coarsening;
    ``label_propagation`` program).  Affinity via lexsort+segment over edges.
  * k-way       — labels range over [0, k), k small (refinement).  Affinity is
    a dense (n_pad, k) histogram == A @ onehot(labels); the Pallas kernel
    (kernels/lp_affinity.py) implements exactly this product for the ELL
    layout; the COO scatter here is the jnp fallback/oracle.

All functions operate on pow2-padded arrays (see csr.CooGraph docstring), so
jit caches hit across multilevel levels.  Padding rows have zero vertex and
edge weight and never affect sizes, cuts, or gains.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.csr import CooGraph, Graph, to_coo
from repro.core.sorting import bit_width, lexsort, sort_free

_NEG = -1e30
_NOISE = 1e-4          # random tie-break amplitude
_GAIN_EPS = 1e-3       # strictly-positive-gain threshold (> noise)


# ---------------------------------------------------------------------------
# capped acceptance: apply proposed moves without exceeding target capacity
# ---------------------------------------------------------------------------

def _order_key(x: jax.Array) -> jax.Array:
    """float32 → uint32 preserving order (−0.0 ranks with 0.0, as in jnp's
    sorts); finite inputs only."""
    x = jnp.where(x == 0, jnp.zeros_like(x), x)
    b = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(1 << 31))


#: up to this many targets the bit search keeps one boundary per target
#: and sums inflows in one dense (targets × movers) reduction, with no
#: gather or scatter; above it (clustering: one target per node) each mover
#: carries its target's boundary, and a bit costs one scatter-add and one
#: gather.
_DENSE_TARGETS = 128


def accept_prefix(tgt: jax.Array, priority: jax.Array, vw: jax.Array,
                  idx: jax.Array, n_bits: int, sizes: jax.Array,
                  cap: jax.Array, total=lambda inflow: inflow) -> jax.Array:
    """Sort-free capped acceptance → (m,) bool, the accepted movers.

    Per target t the movers are ordered by (priority desc, ``idx`` asc)
    and the longest prefix with ``sizes[t] + inflow <= cap[t]`` is taken
    (``vw`` is 0 for non-movers).  The boundary of that prefix — a
    priority key L, then an index J among movers with key == L — is built
    bit by bit as the largest whose inflow still fits: one per-target sum
    per bit, in one scan.  ``idx`` holds distinct ints below 2**n_bits;
    ``total`` combines per-target partial sums when the movers are spread
    over several shards (a psum), so every shard takes the same decision.
    """
    k = sizes.shape[0]
    key = _order_key(-priority)

    if k <= _DENSE_TARGETS:
        t_ids = jnp.arange(k, dtype=tgt.dtype)[:, None]

        def below(lk, jk):                              # (k, m)
            lt, jt = lk[:, None], jk[:, None]
            return (tgt[None, :] == t_ids) & (
                (key[None, :] < lt) | ((key[None, :] == lt)
                                       & (idx[None, :] < jt)))

        def fits(lk, jk):
            inflow = jnp.sum(jnp.where(below(lk, jk), vw[None, :], 0.0),
                             axis=1)
            return sizes + total(inflow) <= cap

        def taken(lk, jk):
            return jnp.any(below(lk, jk), axis=0)

        shape = (k,)
    else:
        def taken(lt, jt):
            return (key < lt) | ((key == lt) & (idx < jt))

        def fits(lt, jt):
            inflow = jnp.zeros((k,), vw.dtype).at[tgt].add(
                jnp.where(taken(lt, jt), vw, 0.0))
            return (sizes + total(inflow) <= cap)[tgt]

        shape = tgt.shape

    def step(carry, s):
        lk, jk = carry
        on_key = s < 32
        bit_l = jnp.where(on_key, jnp.uint32(1) << (31 - s).astype(jnp.uint32),
                          jnp.uint32(0))
        bit_j = jnp.where(on_key, 0, jnp.int32(1) << jnp.maximum(
            n_bits - 1 - (s - 32), 0))
        cl, cj = lk | bit_l, jk | bit_j
        ok = fits(cl, cj)
        return (jnp.where(ok, cl, lk), jnp.where(ok, cj, jk)), None

    (lk, jk), _ = jax.lax.scan(
        step, (jnp.zeros(shape, jnp.uint32), jnp.zeros(shape, jnp.int32)),
        jnp.arange(32 + n_bits, dtype=jnp.int32))
    return taken(lk, jk)


def _accept_sorted(proposal, vw, sizes, cap, priority):
    """Sort-based capped acceptance → (n,) bool (see `capped_accept`)."""
    n = proposal.shape[0]
    # sort by (target, -priority): group per target, best first
    order = jnp.lexsort((-priority, proposal))
    t_s = proposal[order]
    vw_s = vw[order]
    cums = jnp.cumsum(vw_s)
    newrun = jnp.concatenate([jnp.array([True]), t_s[1:] != t_s[:-1]])
    base = jnp.where(newrun, cums - vw_s, -jnp.inf)
    base = jax.lax.cummax(base)
    inflow = cums - base                  # inclusive inflow within target run
    ok_s = sizes[t_s] + inflow <= cap[t_s]
    return jnp.zeros((n,), bool).at[order].set(ok_s)


def capped_accept(labels: jax.Array, proposal: jax.Array, vwgt: jax.Array,
                  sizes: jax.Array, cap: jax.Array,
                  priority: jax.Array) -> jax.Array:
    """Accept moves in priority order (desc) per target until capacity.

    Per target, movers are taken in (priority desc, index asc) order while
    ``size[t] + inclusive inflow <= cap[t]``.  Guarantee: for every target
    t, size[t] + accepted_inflow[t] <= cap[t] (outflow ignored →
    conservative).  Returns new labels.

    One sort orders the movers; where sorts are slow to compile
    (`sorting.sort_free`) the same prefix comes from `accept_prefix`.
    """
    n = labels.shape[0]
    moving = proposal != labels
    vw = jnp.where(moving, vwgt, 0.0)
    if sort_free():
        ok = accept_prefix(proposal.astype(jnp.int32), priority, vw,
                           jnp.arange(n, dtype=jnp.int32), n.bit_length(),
                           sizes, cap)
    else:
        ok = _accept_sorted(proposal, vw, sizes, cap, priority)
    return jnp.where(moving & ok, proposal, labels)


# ---------------------------------------------------------------------------
# k-way dense affinity (jnp oracle; Pallas kernel mirrors this on ELL)
# ---------------------------------------------------------------------------

def kway_affinity_coo(g: CooGraph, labels: jax.Array, k: int) -> jax.Array:
    """aff[v, b] = total weight of edges from v into block b.  (n_pad, k)."""
    tgt = labels[g.dst]
    return jnp.zeros((g.n_pad, k), jnp.float32).at[g.src, tgt].add(g.w)


def kway_lp_round(g: CooGraph, labels: jax.Array, sizes: jax.Array,
                  cap: jax.Array, key: jax.Array, k: int,
                  parity: jax.Array, active: Optional[jax.Array],
                  allow_zero_gain: bool, force_balance,
                  affinity_fn=None) -> tuple:
    """One batch-synchronous k-way LP/gain round; returns (labels, sizes).

    ``force_balance`` may be a Python bool or a traced boolean scalar (the
    batched tournament vmaps over it — candidates differ in feasibility).
    """
    n = g.n_pad
    aff = (affinity_fn or kway_affinity_coo)(g, labels, k)
    noise = jax.random.uniform(key, (n, k), jnp.float32, 0.0, _NOISE)
    own = jnp.take_along_axis(aff, labels[:, None].astype(jnp.int32), axis=1)[:, 0]
    gain = aff - own[:, None] + noise
    # own block is not a move target
    gain = gain.at[jnp.arange(n), labels].set(_NEG)
    # full targets are not candidates
    vw = g.vwgt
    room = sizes[None, :] + vw[:, None] <= cap[None, :]
    gain = jnp.where(room, gain, _NEG)
    best_gain = jnp.max(gain, axis=1)
    best_tgt = jnp.argmax(gain, axis=1).astype(labels.dtype)
    # traced flag (like force_balance): zero-gain admission rides the batch
    # dim instead of forking the compiled program per variant
    thresh = jnp.where(jnp.asarray(allow_zero_gain), -_GAIN_EPS, _GAIN_EPS)
    want = best_gain > thresh
    # overweight blocks push nodes out regardless of gain (when forced)
    over = sizes[labels] > cap[labels]
    want = want | (jnp.asarray(force_balance)
                   & over & (best_gain > _NEG / 2) & (vw > 0))
    # parity tie-break (avoid A<->B swap oscillation)
    node_par = (jnp.arange(n) + parity) % 2 == 0
    want = want & node_par
    if active is not None:
        want = want & active
    proposal = jnp.where(want, best_tgt, labels)
    new_labels = capped_accept(labels, proposal, vw, sizes, cap,
                               jnp.where(want, best_gain, _NEG))
    new_sizes = jnp.zeros((k,), sizes.dtype).at[new_labels].add(vw)
    return new_labels, new_sizes


# ---------------------------------------------------------------------------
# clustering LP (labels in [0, n_pad)) — lexsort+segment formulation
# ---------------------------------------------------------------------------

def _segment_affinity(g: CooGraph, labels: jax.Array, sizes: jax.Array,
                      cap: jax.Array, key: jax.Array):
    """Per node: best cluster among neighbours under the size constraint.

    Returns (best_label, best_aff, own_aff) arrays of length n_pad.
    """
    n = g.n_pad
    e = g.e_pad
    tgt = labels[g.dst]
    # sort live edges first and split runs on the live flag: real edges'
    # positions and run boundaries then depend on real edges alone — by the
    # masking contract (kernels/ops.py) padding (w == 0) edges may point
    # anywhere, and letting their placement shift the sort would leak into
    # the position-keyed tie-break noise below.  Padding edges land in
    # dead-only runs, which aff_eff masks to _NEG.
    dead = jnp.where(g.w > 0, 0, 1)
    nbits = bit_width(n - 1)
    order = lexsort((tgt, g.src, dead), (nbits, nbits, 1))  # (src, tgt) runs
    src_e = g.src[order]
    lab_e = tgt[order]
    ws = g.w[order]
    live = ws > 0
    newrun = jnp.concatenate(
        [jnp.array([True]),
         (src_e[1:] != src_e[:-1]) | (lab_e[1:] != lab_e[:-1])
         | (live[1:] != live[:-1])])
    seg = jnp.cumsum(newrun) - 1                       # (e,) run index
    segsum = jnp.zeros((e,), jnp.float32).at[seg].add(ws)
    aff_run = segsum[seg]                              # per edge: run's sum
    # random tie-break, consistent within a run
    noise = jax.random.uniform(key, (e,), jnp.float32, 0.0, _NOISE)
    noise = jnp.zeros((e,), jnp.float32).at[seg].max(noise)[seg]
    aff_run = aff_run + noise
    # size constraint: target must have room (own cluster always allowed)
    own = lab_e == labels[src_e]
    room = (sizes[lab_e] + g.vwgt[src_e] <= cap[lab_e]) | own
    aff_eff = jnp.where(room & live, aff_run, _NEG)
    best = jnp.full((n,), _NEG, jnp.float32).at[src_e].max(aff_eff)
    is_best = aff_eff >= best[src_e] - 1e-9
    cand = jnp.where(is_best, lab_e, n + 1)
    best_lab = jnp.full((n,), n + 1, jnp.int32).at[src_e].min(cand)
    own_best = jnp.zeros((n,), jnp.float32).at[src_e].max(
        jnp.where(own & live, aff_run, 0.0))
    return best_lab, best, own_best


@functools.partial(jax.jit, static_argnames=("iters",))
def _cluster_lp_round(g: CooGraph, labels: jax.Array, cap: jax.Array,
                      key: jax.Array, i: jax.Array, moved: jax.Array,
                      iters: int):
    """Round ``i`` of ``iters`` clustering LP rounds → (new labels,
    ``moved`` + the number of nodes it moved)."""
    n = g.n_pad
    vw = g.vwgt
    sizes = jnp.zeros((n,), jnp.float32).at[labels].add(vw)
    k1, _ = jax.random.split(jax.random.split(key, iters)[i])
    best_lab, best_aff, own_aff = _segment_affinity(g, labels, sizes, cap, k1)
    improve = (best_aff > own_aff + _GAIN_EPS) & (best_lab < n)
    node_par = (jnp.arange(n) + i) % 2 == 0
    want = improve & node_par
    proposal = jnp.where(want, best_lab, labels).astype(labels.dtype)
    pri = jnp.where(want, best_aff - own_aff, _NEG)
    new_labels = capped_accept(labels, proposal, vw, sizes, cap, pri)
    return new_labels, moved + jnp.sum((new_labels != labels)
                                       .astype(jnp.int32))


def _cluster_lp(g: CooGraph, labels0: jax.Array, cap: jax.Array,
                key: jax.Array, iters: int):
    """``iters`` clustering rounds → (labels, total node moves).

    The rounds are dispatched one compiled round at a time rather than as
    a device loop: the TPU compiler takes several times longer to build
    the round inside a loop than alone, and the dispatches are cheap."""
    labels, moved = labels0, jnp.int32(0)
    for i in range(iters):
        labels, moved = _cluster_lp_round(g, labels, cap, key, jnp.int32(i),
                                          moved, iters)
    return labels, moved


def size_constrained_lp(g: Graph, max_cluster_weight: float,
                        iters: int = 10, seed: int = 0,
                        coo: Optional[CooGraph] = None) -> np.ndarray:
    """The ``label_propagation`` program: returns a clustering (host ints)."""
    coo = coo if coo is not None else to_coo(g)
    n_pad = coo.n_pad
    # host-built constants: jnp.arange/jnp.full would each compile a
    # one-op program (iota / broadcast_in_dim) per shape
    labels0 = jnp.asarray(np.arange(n_pad, dtype=np.int32))
    cap = jnp.asarray(np.full(n_pad, max_cluster_weight, np.float32))
    labels, _ = _cluster_lp(coo, labels0, cap, jax.random.PRNGKey(seed),
                            iters)
    return np.asarray(labels)[:g.n]
