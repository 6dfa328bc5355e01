"""parhyp — distributed-memory multilevel hypergraph partitioning via
shard_map (DESIGN.md §9), the hypergraph sibling of core/parhip.py.

The MPI design of ParHIP carries over to hypergraphs with one twist: the
unit of distribution is the *net*, not the vertex.  Nets (and all their
pins) are block-distributed over the ``nets`` mesh axis as padded per-shard
pin-COO rows; on a 2-D ``(nets, verts)`` mesh each net row is additionally
split by the pin's *vertex column*, so the (n, k) gain/affinity scatters
shrink per device.  Vertex labels stay replicated (the ghost exchange is
the all-gather SPMD partitioning inserts).  Each refinement round:

  1. every shard scatters its local pins into a per-(net, block) pin-count
     partial; the partials ``psum`` over the ``verts`` axis first into the
     net-sharded histogram Φ(e_rows, b), then per-row objectives psum over
     ``nets``;
  2. exact (λ−1) / cut-net move gains are derived from Φ — the per-vertex
     affinity/removal partials are local scatters into the device's vertex
     *column*, psum'd over ``nets`` only (a net's pins for one column all
     live on one device, so its contribution is computed exactly once);
  3. moves are proposed with the same noise/parity split as the sequential
     refiner and accepted by the sequential refiner's rule: each shard
     searches the per-block prefix boundary of its owned vertex slice, the
     per-block partial inflows psum'd over the mesh at every step
     (`lp.accept_prefix`) — so the balance constraint holds globally and
     moves compete for room by priority across the mesh.

Coarsening is device-resident too: a distributed LP-clustering round
(deterministic min-label tie-breaks, integer fixed-point ratings so every
psum is order-independent) proposes column-local clusters, and a
contraction step rebuilds the coarser `ShardedHypergraph` in place — same
padded shapes at every level, so the whole hierarchy shares one compiled
program per (cluster, contract, refine) — without a host round-trip.  The
only host pull per level is the scalar coarse-vertex count.

With a 1-device mesh the refinement round is bit-identical to the
sequential COO oracle (`refine._hyper_refine_scan` with
``use_kernel=False``): same pin layout, same RNG stream, same scatter
orders, same capped acceptance — the regression test pins this.  The
cluster/contract bodies double as their own 1-device oracles: calling them
with ``ax_n=ax_v=None`` outside shard_map is the reference the shard_map
plumbing is tested against, and the host `coarsen.contract` is the
objective-preservation oracle for the device contraction.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro import obs
from repro.core.csr import _pow2_pad
from repro.core import lp as lp_mod
from repro.core.hypergraph.container import Hypergraph
from repro.core.hypergraph import metrics as M
from repro.core.hypergraph.coarsen import RATING_SCALE
from repro.core.sorting import bit_width, lexsort

def _psums_per_round(n_pad: int) -> int:
    """psums issued per distributed refinement round: the Φ(e,b) histogram,
    two gain partials (aff/rem for km1, joins/breaks for cut-net) and one
    per-block inflow per step of the acceptance's bit search."""
    return 3 + 32 + n_pad.bit_length()

_NEG = -1e30
_NOISE = 1e-4
_GAIN_EPS = 1e-3
_STALL = 0.95          # stop coarsening when a level shrinks less than this
_POLISH_N = 65536      # sequential polish cutoff on the device path
# Below this size the whole problem goes to the host-orchestrated path, as
# ParHIP gathers a small-enough subproblem onto one PE: data-parallel LP
# clustering pays a few percent cluster impurity that a tiny hierarchy has
# too few levels to refine away, while at scale the loss amortises.
_DEVICE_MIN_N = 8192


# ---------------------------------------------------------------------------
# host container: net/vertex-block-distributed pin COO
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ShardedHypergraph:
    """Host container: nets block-distributed over ``s_nets`` row groups and
    pins additionally split over ``s_verts`` vertex columns; each of the
    ``S = s_nets·s_verts`` shards holds one padded pin-COO row.  Net/vertex
    weight vectors are replicated.

    Shard ``ie·s_verts + jv`` owns the pins of net rows
    [ie·e_rows, (ie+1)·e_rows) whose vertex lies in column
    [jv·n_col, (jv+1)·n_col).  Padding pins are (net ``e_pad-1``, vertex
    ``n_pad-1``, mask 0) on a zero-weight net — the `PinCoo` convention, so
    with one shard the layout is exactly ``to_pincoo``'s (the bit-exactness
    anchor).
    """

    pv: np.ndarray      # (S, p_shard) int32 — pin's vertex (global id)
    pe: np.ndarray      # (S, p_shard) int32 — pin's net (global id)
    mask: np.ndarray    # (S, p_shard) f32   — 1 real, 0 padding
    netw: np.ndarray    # (e_pad,) f32 — net weights, 0 padding (replicated)
    esize: np.ndarray   # (e_pad,) f32 — pin counts, 0 padding (replicated)
    vwgt: np.ndarray    # (n_pad,) f32 — vertex weights, 0 pad (replicated)
    n: int
    m: int
    rows_v: int         # vertices owned per shard (n_pad == S * rows_v)
    s_nets: int = 1     # mesh extent over net rows
    s_verts: int = 1    # mesh extent over vertex columns

    @property
    def n_shards(self) -> int:
        return self.pv.shape[0]

    @property
    def p_shard(self) -> int:
        return self.pv.shape[1]

    @property
    def n_pad(self) -> int:
        return len(self.vwgt)

    @property
    def e_pad(self) -> int:
        return len(self.netw)

    @property
    def n_col(self) -> int:
        """Vertices per column (n_pad == s_verts · n_col)."""
        return self.n_pad // self.s_verts

    @property
    def e_rows(self) -> int:
        """Nets per row group (e_pad == s_nets · e_rows)."""
        return self.e_pad // self.s_nets


def shard_hypergraph(hg: Hypergraph, shards, p_mult: int = 256,
                     n_mult: int = 128, e_mult: int = 128
                     ) -> ShardedHypergraph:
    """Block-distribute ``hg`` over ``shards`` = S (1-D over nets) or
    ``(s_nets, s_verts)`` (2-D): net-row group ie owns the contiguous
    net-id range [ie·e_rows, (ie+1)·e_rows), vertex column jv the vertex
    range [jv·n_col, (jv+1)·n_col); shard ie·s_verts+jv holds their
    intersection's pins in global pin order."""
    if isinstance(shards, tuple):
        s_nets, s_verts = shards
    else:
        s_nets, s_verts = int(shards), 1
    S = s_nets * s_verts
    n, m, p = hg.n, hg.m, hg.pins
    n_pad = _pow2_pad(max(n, 1), n_mult)
    rows_v = -(-n_pad // S)
    n_pad = rows_v * S
    n_col = rows_v * s_nets
    e_pad = _pow2_pad(m + 1, e_mult)
    e_rows = -(-e_pad // s_nets)
    e_pad = e_rows * s_nets
    pe_h = hg.pin_sources()
    owner_e = np.minimum(pe_h // e_rows, s_nets - 1)
    col_v = np.minimum(hg.eind // n_col, s_verts - 1)
    owner = owner_e * s_verts + col_v
    pmax = int(np.bincount(owner, minlength=S).max()) if p else 1
    p_shard = _pow2_pad(max(pmax, 1), p_mult)
    pv = np.full((S, p_shard), n_pad - 1, dtype=np.int32)
    pe = np.full((S, p_shard), e_pad - 1, dtype=np.int32)
    mask = np.zeros((S, p_shard), dtype=np.float32)
    for s in range(S):
        ids = np.flatnonzero(owner == s)
        pv[s, :len(ids)] = hg.eind[ids]
        pe[s, :len(ids)] = pe_h[ids]
        mask[s, :len(ids)] = 1.0
    netw = np.zeros(e_pad, dtype=np.float32)
    netw[:m] = hg.ewgt
    esize = np.zeros(e_pad, dtype=np.float32)
    esize[:m] = hg.net_sizes()
    vwgt = np.zeros(n_pad, dtype=np.float32)
    vwgt[:n] = hg.vwgt
    return ShardedHypergraph(pv=pv, pe=pe, mask=mask, netw=netw,
                             esize=esize, vwgt=vwgt, n=n, m=m, rows_v=rows_v,
                             s_nets=s_nets, s_verts=s_verts)


# ---------------------------------------------------------------------------
# mesh plumbing: axis-optional collectives (ax=None ⇒ 1-extent identity,
# which makes every shard_map body its own sequential oracle)
# ---------------------------------------------------------------------------

def _mesh_axes(mesh: Mesh) -> Tuple[str, Optional[str]]:
    names = tuple(mesh.axis_names)
    if len(names) == 1:
        return names[0], None
    if len(names) == 2:
        return names[0], names[1]
    raise ValueError(f"parhyp mesh must be 1-D (nets) or 2-D (nets, verts); "
                     f"got axes {names}")


def _mesh_extents(mesh: Mesh) -> Tuple[int, int]:
    ax_n, ax_v = _mesh_axes(mesh)
    return mesh.shape[ax_n], (mesh.shape[ax_v] if ax_v else 1)


def _specs(ax_n, ax_v):
    """(pin-block, vertex-vector, replicated) PartitionSpecs for a mesh."""
    if ax_v is None:
        return P(ax_n, None), P(ax_n), P()
    # pins: nets-major over the leading shard dim; vertex vectors: the flat
    # owned block of device (ie, jv) is jv·s_nets + ie, i.e. column-major —
    # so its slice starts at jv·n_col + ie·rows_v
    return P((ax_n, ax_v), None), P((ax_v, ax_n)), P()


def _psum(x, ax):
    return jax.lax.psum(x, ax) if ax is not None else x


def _pmax(x, ax):
    return jax.lax.pmax(x, ax) if ax is not None else x


def _pmin(x, ax):
    return jax.lax.pmin(x, ax) if ax is not None else x


def _idx(ax):
    return jax.lax.axis_index(ax) if ax is not None else 0


# ---------------------------------------------------------------------------
# the distributed refinement round (shard_map body)
# ---------------------------------------------------------------------------

def _dist_obj_local(pv, pe, mask, netw, labels, k: int, e_rows: int,
                    ax_n, ax_v, objective: str):
    """Replicated objective from the verts-psum'd net-sharded Φ partial."""
    pv, pe, mask = (a.reshape(-1) for a in (pv, pe, mask))
    ie = _idx(ax_n)
    pe_loc = jnp.clip(pe - ie * e_rows, 0, e_rows - 1)
    cnt = _psum(jnp.zeros((e_rows, k), jnp.float32).at[
        pe_loc, labels[pv].astype(jnp.int32)].add(mask), ax_v)
    netw_row = jax.lax.dynamic_slice(netw, (ie * e_rows,), (e_rows,))
    obj_fn = M.km1_device if objective == "km1" else M.cut_net_device
    return _psum(obj_fn(cnt, netw_row), ax_n)


def _dist_wtot_local(pv, pe, mask, netw, vwgt, ax_n, ax_v):
    """Per-vertex total incident net weight W(v), psum'd over both axes —
    round-invariant, so it is computed once before the refinement scan."""
    pv, pe, mask = (a.reshape(-1) for a in (pv, pe, mask))
    w_pin = mask * netw[pe]
    n = vwgt.shape[0]
    return _psum(_psum(
        jnp.zeros((n,), jnp.float32).at[pv].add(w_pin), ax_v), ax_n)


def _dist_round_local(pv, pe, mask, netw, esize, vwgt, wtot, labels, sizes,
                      cap, key, parity, force, rows_v: int, n_col: int,
                      e_rows: int, k: int, ax_n, ax_v, objective: str):
    """One distributed LP round, run per shard under shard_map.

    ``labels`` is the full replicated vector; pin arrays arrive as (1, ·)
    local blocks.  Φ partials psum over ``verts`` into the net-sharded
    histogram; gain partials are scattered into the device's vertex column
    and psum over ``nets`` only.  Returns (new labels for the owned vertex
    slice, the pre-move objective) — gain math mirrors
    refine._hyper_refine_scan exactly so the 1-shard round is bit-identical
    to the sequential oracle.
    """
    pv, pe, mask = (a.reshape(-1) for a in (pv, pe, mask))
    ie = _idx(ax_n)
    jv = _idx(ax_v)
    n_pad = labels.shape[0]
    p_loc = pv.shape[0]
    lab_pin = labels[pv].astype(jnp.int32)
    # clamped local indices: padding pins (mask 0) may clamp anywhere —
    # every use below is mask-weighted (the kernels/ops.py masking contract)
    pe_loc = jnp.clip(pe - ie * e_rows, 0, e_rows - 1)
    pv_loc = jnp.clip(pv - jv * n_col, 0, n_col - 1)
    w_pin = mask * netw[pe]
    cnt = _psum(jnp.zeros((e_rows, k), jnp.float32).at[
        pe_loc, lab_pin].add(mask), ax_v)
    netw_row = jax.lax.dynamic_slice(netw, (ie * e_rows,), (e_rows,))
    obj_fn = M.km1_device if objective == "km1" else M.cut_net_device
    obj = _psum(obj_fn(cnt, netw_row), ax_n)
    # exact move gains from the net-sharded histogram (per-vertex partials
    # from local pins into this device's column, psum'd over nets — each
    # net's pins for one column all live on one device)
    cnt_e = cnt[pe_loc]                                   # (p_loc, k)
    cnt_own = cnt_e[jnp.arange(p_loc), lab_pin]
    wtot_col = jax.lax.dynamic_slice(wtot, (jv * n_col,), (n_col,))
    if objective == "km1":
        pres = (cnt_e > 0).astype(jnp.float32)
        aff = _psum(jnp.zeros((n_col, k), jnp.float32).at[pv_loc].add(
            w_pin[:, None] * pres), ax_n)
        rem = _psum(jnp.zeros((n_col,), jnp.float32).at[pv_loc].add(
            w_pin * (cnt_own == 1)), ax_n)
        gain = rem[:, None] - wtot_col[:, None] + aff
    else:
        makes = (cnt_e == (esize[pe] - 1.0)[:, None])
        joins = _psum(jnp.zeros((n_col, k), jnp.float32).at[pv_loc].add(
            w_pin[:, None] * makes.astype(jnp.float32)), ax_n)
        breaks = _psum(jnp.zeros((n_col,), jnp.float32).at[pv_loc].add(
            w_pin * (cnt_own == esize[pe])), ax_n)
        gain = joins - breaks[:, None]
    # full-width noise sliced to the column: identical values per vertex on
    # every mesh layout (the layout-parity anchor)
    noise = jax.random.uniform(key, (n_pad, k), jnp.float32, 0.0, _NOISE)
    gain = gain + jax.lax.dynamic_slice(noise, (jv * n_col, 0), (n_col, k))
    labels_col = jax.lax.dynamic_slice(labels, (jv * n_col,), (n_col,))
    vw_col = jax.lax.dynamic_slice(vwgt, (jv * n_col,), (n_col,))
    gain = gain.at[jnp.arange(n_col), labels_col].set(_NEG)
    room = sizes[None, :] + vw_col[:, None] <= cap[None, :]
    gain = jnp.where(room, gain, _NEG)
    best_gain = jnp.max(gain, axis=1)
    best_tgt = jnp.argmax(gain, axis=1).astype(labels.dtype)
    want = best_gain > _GAIN_EPS
    over = sizes[labels_col] > cap[labels_col]
    want = want | (jnp.asarray(force)
                   & over & (best_gain > _NEG / 2) & (vw_col > 0))
    node_par = (jv * n_col + jnp.arange(n_col) + parity) % 2 == 0
    want = want & node_par
    proposal = jnp.where(want, best_tgt, labels_col)
    pri = jnp.where(want, best_gain, _NEG)
    # Capped acceptance with the sequential decision: per block, movers
    # are taken by (priority desc, global index asc) while the block fits.
    # Each shard searches the prefix boundary on its owned vertex slice
    # and the per-block partial inflows are psum'd at every step, so all
    # shards reach the same global boundary without gathering proposals —
    # moves compete for a block's room across the whole mesh, whatever the
    # device count or layout.
    off = ie * rows_v
    lab_own = jax.lax.dynamic_slice(labels_col, (off,), (rows_v,))
    prop_own = jax.lax.dynamic_slice(proposal, (off,), (rows_v,))
    vw_own = jax.lax.dynamic_slice(vw_col, (off,), (rows_v,))
    pri_own = jax.lax.dynamic_slice(pri, (off,), (rows_v,))
    moving = prop_own != lab_own
    ok = lp_mod.accept_prefix(
        prop_own.astype(jnp.int32), pri_own, jnp.where(moving, vw_own, 0.0),
        jv * n_col + off + jnp.arange(rows_v, dtype=jnp.int32),
        n_pad.bit_length(), sizes, cap,
        total=lambda x: _psum(_psum(x, ax_n), ax_v))
    new_own = jnp.where(moving & ok, prop_own, lab_own)
    return new_own, obj


@functools.partial(jax.jit,
                   static_argnames=("rows_v", "n_col", "e_rows", "k",
                                    "rounds", "objective", "mesh"))
def _parhyp_refine_jit(mesh: Mesh, pv, pe, mask, netw, esize, vwgt,
                       labels0, cap, key, force, rows_v: int, n_col: int,
                       e_rows: int, k: int, rounds: int, objective: str):
    ax_n, ax_v = _mesh_axes(mesh)
    spec_p, spec_v, spec_r = _specs(ax_n, ax_v)
    round_fn = shard_map(
        functools.partial(_dist_round_local, rows_v=rows_v, n_col=n_col,
                          e_rows=e_rows, k=k, ax_n=ax_n, ax_v=ax_v,
                          objective=objective),
        mesh=mesh,
        in_specs=(spec_p, spec_p, spec_p, spec_r, spec_r, spec_r, spec_r,
                  spec_r, spec_r, spec_r, spec_r, spec_r, spec_r),
        out_specs=(spec_v, spec_r),
        check_vma=False,
    )
    obj_sm = shard_map(
        functools.partial(_dist_obj_local, k=k, e_rows=e_rows, ax_n=ax_n,
                          ax_v=ax_v, objective=objective),
        mesh=mesh,
        in_specs=(spec_p, spec_p, spec_p, spec_r, spec_r),
        out_specs=spec_r,
        check_vma=False,
    )
    wtot_fn = shard_map(
        functools.partial(_dist_wtot_local, ax_n=ax_n, ax_v=ax_v),
        mesh=mesh,
        in_specs=(spec_p, spec_p, spec_p, spec_r, spec_r),
        out_specs=spec_r,
        check_vma=False,
    )
    wtot = wtot_fn(pv, pe, mask, netw, vwgt)

    def body(carry, key_r):
        labels, sizes, best_obj, best_labels, parity = carry
        new_labels, obj = round_fn(pv, pe, mask, netw, esize, vwgt, wtot,
                                   labels, sizes, cap, key_r, parity, force)
        # undo-to-best: track the best feasible pre-move state
        feas = jnp.max(sizes - cap) <= 1e-6
        better = feas & (obj < best_obj)
        best_obj = jnp.where(better, obj, best_obj)
        best_labels = jnp.where(better, labels, best_labels)
        new_sizes = jnp.zeros((k,), jnp.float32).at[new_labels].add(vwgt)
        return (new_labels, new_sizes, best_obj, best_labels,
                parity + 1), obj

    sizes0 = jnp.zeros((k,), jnp.float32).at[labels0].add(vwgt)
    keys = jax.random.split(key, rounds)
    carry0 = (labels0, sizes0, jnp.float32(jnp.inf), labels0, jnp.int32(0))
    (labels, sizes, best_obj, best_labels, _), _ = jax.lax.scan(
        body, carry0, keys)
    # evaluate the final state too
    obj = obj_sm(pv, pe, mask, netw, labels)
    feas = jnp.max(sizes - cap) <= 1e-6
    better = feas & (obj < best_obj)
    best_obj = jnp.where(better, obj, best_obj)
    best_labels = jnp.where(better, labels, best_labels)
    have = jnp.isfinite(best_obj)
    out = jnp.where(have, best_labels, labels)
    out_sizes = jnp.zeros((k,), jnp.float32).at[out].add(vwgt)
    out_feas = jnp.max(out_sizes - cap) <= 1e-6
    return out, best_obj, out_feas


def parhyp_refine(hg: Hypergraph, part: np.ndarray, k: int,
                  eps: float = 0.03, mesh: Optional[Mesh] = None,
                  rounds: int = 12, seed: int = 0, objective: str = "km1",
                  force_balance: bool = False, axis: str = "nets",
                  sh: Optional[ShardedHypergraph] = None) -> np.ndarray:
    """Distributed k-way LP refinement of a hypergraph partition.

    Never returns a worse feasible objective than the input (the caller's
    better-of-in/out guard, as in refine_hypergraph); ``sh`` accepts a
    cached `ShardedHypergraph` matching the mesh layout.
    """
    if k <= 1 or hg.n == 0:
        return np.asarray(part, dtype=np.int64)
    if mesh is None:
        mesh = Mesh(np.array(jax.devices()), (axis,))
    s_nets, s_verts = _mesh_extents(mesh)
    rec = obs.current()
    if sh is None or sh.s_nets != s_nets or sh.s_verts != s_verts:
        sh = shard_hypergraph(hg, (s_nets, s_verts))
    from repro.core import multilevel as ML
    from repro.core.hypergraph.refine import _caps_for, _pad_caps, k_bucket
    k_pad = k_bucket(k)
    cap = jnp.asarray(_pad_caps(_caps_for(hg, k, eps), k_pad), jnp.float32)
    labels0 = np.zeros(sh.n_pad, dtype=np.int32)
    labels0[:hg.n] = part
    ML.note_program("parhyp", sh.n_pad, sh.e_pad, sh.p_shard, k_pad,
                    rounds, objective, s_nets, s_verts)
    with rec.span("parhyp_refine", n=hg.n, rounds=rounds,
                  shards=sh.n_shards):
        out, _, _ = _parhyp_refine_jit(mesh, jnp.asarray(sh.pv),
                                       jnp.asarray(sh.pe),
                                       jnp.asarray(sh.mask),
                                       jnp.asarray(sh.netw),
                                       jnp.asarray(sh.esize),
                                       jnp.asarray(sh.vwgt),
                                       jnp.asarray(labels0), cap,
                                       jax.random.PRNGKey(seed),
                                       jnp.asarray(force_balance),
                                       sh.rows_v, sh.n_col, sh.e_rows,
                                       k_pad, rounds, objective)
        out = np.asarray(out, dtype=np.int64)[:hg.n]
    rec.count("parhyp/dist_rounds", rounds)
    # per round: Φ, two gain partials, the acceptance's inflows; plus the
    # one-off wtot and final Φ
    rec.count("parhyp/psum_rounds", _psums_per_round(sh.n_pad) * rounds + 2)
    score = M.connectivity if objective == "km1" else M.cut_net
    if score(hg, out) <= score(hg, part) or force_balance:
        return out
    rec.count("parhyp/rounds_rejected")
    return np.asarray(part, dtype=np.int64)


# ---------------------------------------------------------------------------
# distributed LP-clustering coarsening (shard_map bodies)
# ---------------------------------------------------------------------------

def _cluster_round_local(pv, pe, mask, netw, esize, vwgt, labels, capv,
                         parity, rows_v: int, n_col: int, e_rows: int,
                         s_nets: int, s_verts: int, ax_n, ax_v):
    """One distributed LP-clustering round (per shard under shard_map).

    Affinities use integer fixed-point ratings r(e) = max(1,
    round(SCALE·w/(|e|−1))) computed in place from the replicated net
    vectors — linear in pins (no clique expansion), and integer-valued so
    every cross-device reduction is order-independent (exact).  Per net the
    two most frequent pin labels are found by a run-length lexsort + two
    masked scatter passes; each pin's candidate is the most frequent
    *other* label.  Tie-breaks are deterministic (min label), no RNG.
    Clusters are column-local by construction: candidates come from
    co-pins in the same vertex column, so a cluster never spans columns
    and contraction preserves the 2-D layout.
    """
    pv, pe, mask = (a.reshape(-1) for a in (pv, pe, mask))
    ie = _idx(ax_n)
    jv = _idx(ax_v)
    n_pad = labels.shape[0]
    p_loc = pv.shape[0]
    pe_loc = jnp.clip(pe - ie * e_rows, 0, e_rows - 1)
    pv_loc = jnp.clip(pv - jv * n_col, 0, n_col - 1)
    netw_row = jax.lax.dynamic_slice(netw, (ie * e_rows,), (e_rows,))
    esize_row = jax.lax.dynamic_slice(esize, (ie * e_rows,), (e_rows,))
    rate_row = jnp.where(
        (esize_row >= 2) & (netw_row > 0),
        jnp.maximum(1.0, jnp.round(
            RATING_SCALE * netw_row / jnp.maximum(esize_row - 1.0, 1.0))),
        0.0)
    r_pin = mask * rate_row[pe_loc]
    live = r_pin > 0
    dead = jnp.where(live, 0, 1)
    lab_p = jnp.where(live, labels[pv].astype(jnp.int32), n_pad)
    # pass 1: per-(net, label) run counts → per-net top-2 labels
    order = lexsort((lab_p, pe_loc, dead),
                    (bit_width(n_pad), bit_width(e_rows - 1), 1))
    pe_s = pe_loc[order]
    lab_s = lab_p[order]
    live_s = live[order]
    newrun = jnp.concatenate(
        [jnp.array([True]),
         (pe_s[1:] != pe_s[:-1]) | (lab_s[1:] != lab_s[:-1])
         | (live_s[1:] != live_s[:-1])])
    seg = jnp.cumsum(newrun) - 1
    rc = jnp.zeros((p_loc,), jnp.float32).at[seg].add(
        live_s.astype(jnp.float32))
    rc_eff = jnp.where(live_s, rc[seg], 0.0)
    t1c = jnp.zeros((e_rows,), jnp.float32).at[pe_s].max(rc_eff)
    is_t1 = live_s & (rc_eff == t1c[pe_s])
    t1l = jnp.full((e_rows,), n_pad, jnp.int32).at[pe_s].min(
        jnp.where(is_t1, lab_s, n_pad))
    not1 = live_s & (lab_s != t1l[pe_s])
    t2c = jnp.zeros((e_rows,), jnp.float32).at[pe_s].max(
        jnp.where(not1, rc_eff, 0.0))
    is_t2 = not1 & (rc_eff == t2c[pe_s])
    t2l = jnp.full((e_rows,), n_pad, jnp.int32).at[pe_s].min(
        jnp.where(is_t2, lab_s, n_pad))
    # back to pin order: own-run count, candidate label + its count
    rc_own = jnp.zeros((p_loc,), jnp.float32).at[order].set(rc_eff)
    own_is_t1 = lab_p == t1l[pe_loc]
    cand = jnp.where(own_is_t1, t2l[pe_loc], t1l[pe_loc])
    ccnt = jnp.where(own_is_t1, t2c[pe_loc], t1c[pe_loc])
    cand = jnp.where(live, cand, n_pad)
    own_aff = jnp.zeros((n_col,), jnp.float32).at[pv_loc].add(
        r_pin * jnp.maximum(rc_own - 1.0, 0.0))
    # pass 2: aggregate candidate affinity per (vertex, candidate)
    has_cand = live & (cand < n_pad)
    dead2 = jnp.where(has_cand, 0, 1)
    order2 = lexsort((cand, pv_loc, dead2),
                     (bit_width(n_pad), bit_width(n_col - 1), 1))
    pv2 = pv_loc[order2]
    cand_s = cand[order2]
    live2 = dead2[order2] == 0
    a_pin = jnp.where(has_cand, r_pin * ccnt, 0.0)[order2]
    newrun2 = jnp.concatenate(
        [jnp.array([True]),
         (pv2[1:] != pv2[:-1]) | (cand_s[1:] != cand_s[:-1])
         | (live2[1:] != live2[:-1])])
    seg2 = jnp.cumsum(newrun2) - 1
    aff_run = jnp.zeros((p_loc,), jnp.float32).at[seg2].add(a_pin)[seg2]
    # size-constrained best candidate per vertex, min-label tie-break
    sizes_cl = jnp.zeros((n_pad,), jnp.float32).at[labels].add(vwgt)
    cand_c = jnp.clip(cand_s, 0, n_pad - 1)
    vglob = jv * n_col + pv2
    room = sizes_cl[cand_c] + vwgt[vglob] <= capv[cand_c]
    g = aff_run - own_aff[pv2]
    g_eff = jnp.where(live2 & room, g, _NEG)
    g_v = jnp.full((n_col,), _NEG, jnp.float32).at[pv2].max(g_eff)
    is_best = live2 & (g_eff == g_v[pv2])
    cand_v = jnp.full((n_col,), n_pad, jnp.int32).at[pv2].min(
        jnp.where(is_best, cand_s, n_pad))
    # cross-row combine (exact: affinities are integer-valued f32)
    g2 = _pmax(g_v, ax_n)
    cand2 = _pmin(jnp.where((g_v == g2) & (cand_v < n_pad), cand_v, n_pad),
                  ax_n)
    labels_col = jax.lax.dynamic_slice(labels, (jv * n_col,), (n_col,))
    vw_col = jax.lax.dynamic_slice(vwgt, (jv * n_col,), (n_col,))
    improve = ((g2 > _GAIN_EPS) & (cand2 < n_pad) & (vw_col > 0)
               & (cand2 != labels_col))
    node_par = (jv * n_col + jnp.arange(n_col) + parity) % 2 == 0
    want = improve & node_par
    proposal = jnp.where(want, cand2, labels_col).astype(labels.dtype)
    pri = jnp.where(want, g2, _NEG)
    # contention-aware capped acceptance, as in the refinement round, with
    # per-cluster ownership: a cluster is arbitrated inside its own vertex
    # column by a rotating net-row owner
    vw_mov = jnp.where(proposal != labels_col, vw_col, 0.0)
    demand = _psum(jnp.zeros((n_pad,), jnp.float32).at[proposal].add(vw_mov),
                   ax_v)
    uncontended = demand <= capv - sizes_cl
    cid = jnp.arange(n_pad)
    owner = ((cid + parity) % s_nets == ie) & (cid // n_col == jv)
    cap_local = jnp.where(uncontended | owner, capv, sizes_cl)
    off = ie * rows_v
    lab_own = jax.lax.dynamic_slice(labels_col, (off,), (rows_v,))
    prop_own = jax.lax.dynamic_slice(proposal, (off,), (rows_v,))
    vw_own = jax.lax.dynamic_slice(vw_col, (off,), (rows_v,))
    pri_own = jax.lax.dynamic_slice(pri, (off,), (rows_v,))
    new_own = lp_mod.capped_accept(lab_own, prop_own, vw_own, sizes_cl,
                                   cap_local, pri_own)
    moved = _psum(_psum(
        jnp.sum((new_own != lab_own).astype(jnp.int32)), ax_n), ax_v)
    return new_own, moved


@functools.partial(jax.jit,
                   static_argnames=("rows_v", "n_col", "e_rows", "mesh"))
def _parhyp_cluster_round_jit(mesh: Mesh, pv, pe, mask, netw, esize, vwgt,
                              labels, capv, parity, moved, rows_v: int,
                              n_col: int, e_rows: int):
    """One distributed clustering round → (labels, ``moved`` + its moves)."""
    ax_n, ax_v = _mesh_axes(mesh)
    s_nets, s_verts = _mesh_extents(mesh)
    spec_p, spec_v, spec_r = _specs(ax_n, ax_v)
    round_fn = shard_map(
        functools.partial(_cluster_round_local, rows_v=rows_v, n_col=n_col,
                          e_rows=e_rows, s_nets=s_nets, s_verts=s_verts,
                          ax_n=ax_n, ax_v=ax_v),
        mesh=mesh,
        in_specs=(spec_p, spec_p, spec_p, spec_r, spec_r, spec_r, spec_r,
                  spec_r, spec_r),
        out_specs=(spec_v, spec_r),
        check_vma=False,
    )
    labels, m = round_fn(pv, pe, mask, netw, esize, vwgt, labels, capv,
                         parity)
    return labels, moved + m


def _parhyp_cluster_jit(mesh: Mesh, pv, pe, mask, netw, esize, vwgt,
                        labels0, capv, parity0, rows_v: int, n_col: int,
                        e_rows: int, iters: int):
    """``iters`` distributed clustering rounds → (labels, total moved).

    One compiled round is dispatched per iteration: the TPU compiler
    builds the round inside a device loop several times more slowly than
    alone (the clustering LP of core/lp.py does the same)."""
    labels, moved = labels0, jnp.int32(0)
    for i in range(iters):
        labels, moved = _parhyp_cluster_round_jit(
            mesh, pv, pe, mask, netw, esize, vwgt, labels, capv,
            jnp.int32(parity0 + i), moved, rows_v, n_col, e_rows)
    return labels, moved


def _compact_labels(labels, vwgt, n_col: int):
    """Replicated cluster-id compaction (plain jnp under jit).

    Coarse ids are assigned by a stable sort on (column, non-empty):
    within each vertex column, clusters with positive weight get the low
    contiguous ids — so the coarse level keeps the column structure (the
    recursive 2-D invariant) and the all-padding tail stays at the top.
    """
    n_pad = labels.shape[0]
    cvw_l = jnp.zeros((n_pad,), jnp.float32).at[labels].add(vwgt)
    pr = cvw_l > 0
    col = jnp.arange(n_pad) // n_col
    key = col * (2 * n_col) + jnp.where(pr, 0, n_col)
    perm = lexsort((key,), (bit_width(2 * n_pad),))
    newid = jnp.zeros((n_pad,), jnp.int32).at[perm].set(
        jnp.arange(n_pad, dtype=jnp.int32))
    coarse_of = newid[labels]
    cvw = jnp.zeros((n_pad,), jnp.float32).at[coarse_of].add(vwgt)
    nc = jnp.sum(pr.astype(jnp.int32))
    return coarse_of, cvw, nc


def _contract_pins_local(pv, pe, mask, netw, coarse_of, n_col: int,
                         e_rows: int, ax_n, ax_v):
    """Sharded pin rebuild for the coarse level (per shard).

    Pins are remapped to coarse vertices, duplicates within a net merged
    by a (net, coarse-vertex) lexsort (dead pins sort last, so live pins'
    positions are padding-inert), and dropped pins turned into sentinel
    padding.  Single-pin and empty nets get weight 0 (parallel nets are
    kept separate — objective-neutral).  Shapes are unchanged, so every
    level shares this one compiled program.
    """
    pv, pe, mask = (a.reshape(-1) for a in (pv, pe, mask))
    ie = _idx(ax_n)
    n_pad = coarse_of.shape[0]
    e_pad = netw.shape[0]
    live = mask > 0
    pvn = jnp.where(live, coarse_of[pv], n_pad - 1)
    pe_loc = jnp.clip(pe - ie * e_rows, 0, e_rows - 1)
    dead = jnp.where(live, 0, 1)
    order = lexsort((pvn, pe_loc, dead),
                    (bit_width(n_pad - 1), bit_width(e_rows - 1), 1))
    pe_s = pe_loc[order]
    pvn_s = pvn[order]
    live_s = live[order]
    dup = jnp.concatenate(
        [jnp.array([False]),
         (pe_s[1:] == pe_s[:-1]) & (pvn_s[1:] == pvn_s[:-1])
         & live_s[1:] & live_s[:-1]])
    keep = live_s & ~dup
    pv2 = jnp.where(keep, pvn_s, n_pad - 1).astype(jnp.int32)
    pe2 = jnp.where(keep, pe_s + ie * e_rows, e_pad - 1).astype(jnp.int32)
    mask2 = keep.astype(jnp.float32)
    esize_new = _psum(_psum(
        jnp.zeros((e_pad,), jnp.float32).at[pe2].add(mask2), ax_v), ax_n)
    netw2 = jnp.where(esize_new >= 2, netw, 0.0)
    esize2 = jnp.where(netw2 > 0, esize_new, 0.0)
    # every kept pin lives in the dead-last sort's live prefix, so the max
    # per-shard live count bounds the slice the host may compact pins to
    hi = _pmax(_pmax(jnp.sum(live.astype(jnp.int32)), ax_v), ax_n)
    return (pv2.reshape(1, -1), pe2.reshape(1, -1), mask2.reshape(1, -1),
            netw2, esize2, hi)


@functools.partial(jax.jit,
                   static_argnames=("n_col", "e_rows", "mesh"))
def _parhyp_contract_jit(mesh: Mesh, pv, pe, mask, netw, vwgt, labels,
                         n_col: int, e_rows: int):
    ax_n, ax_v = _mesh_axes(mesh)
    spec_p, spec_v, spec_r = _specs(ax_n, ax_v)
    coarse_of, cvw, nc = _compact_labels(labels, vwgt, n_col)
    pins_fn = shard_map(
        functools.partial(_contract_pins_local, n_col=n_col, e_rows=e_rows,
                          ax_n=ax_n, ax_v=ax_v),
        mesh=mesh,
        in_specs=(spec_p, spec_p, spec_p, spec_r, spec_r),
        out_specs=(spec_p, spec_p, spec_p, spec_r, spec_r, spec_r),
        check_vma=False,
    )
    pv2, pe2, mask2, netw2, esize2, hi = pins_fn(pv, pe, mask, netw,
                                                 coarse_of)
    return pv2, pe2, mask2, netw2, esize2, cvw, coarse_of, nc, hi


# ---------------------------------------------------------------------------
# device-resident hierarchy
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _DeviceLevel:
    """One hierarchy level held on device (constant shapes at every level)."""
    pv: jax.Array
    pe: jax.Array
    mask: jax.Array
    netw: jax.Array
    esize: jax.Array
    vwgt: jax.Array
    coarse_of: Optional[jax.Array] = None   # fine vertex → coarse id


def _device_hierarchy(sh: ShardedHypergraph, mesh: Mesh, cfg, k: int,
                      seed: int, rec) -> Tuple[List[_DeviceLevel], int]:
    """Coarsen on device until ~stop_n vertices remain (floored so level
    count — and with it the pin memory — stays bounded on million-scale
    inputs).  The only host round-trip per level is a pair of scalars
    (coarse-vertex count + live-pin bound); between levels the pin
    buffers are compacted to the next pow2 bucket of the live-pin bound
    — the dead-last contraction sort leaves every kept pin in a per-shard
    prefix — so level cost shrinks geometrically with the hypergraph
    while compile count stays bounded by the bucket count."""
    from repro.core import multilevel as ML
    stop_n = ML.coarsen_stop_n(cfg, k)
    stop_dev = max(stop_n, min(4096, sh.n // 8))
    levels = [_DeviceLevel(jnp.asarray(sh.pv), jnp.asarray(sh.pe),
                           jnp.asarray(sh.mask), jnp.asarray(sh.netw),
                           jnp.asarray(sh.esize), jnp.asarray(sh.vwgt))]
    total_w = float(np.sum(sh.vwgt))
    max_cw = max(1.0, total_w / (cfg.cluster_weight_factor * k))
    labels0 = jnp.asarray(np.arange(sh.n_pad, dtype=np.int32))
    capv = jnp.asarray(np.full(sh.n_pad, max_cw, np.float32))
    n_cur = sh.n
    lvl = 0
    while n_cur > stop_dev:
        L = levels[-1]
        p_cur = L.pv.shape[1]
        ML.note_program("parhyp_cluster", sh.n_pad, sh.e_pad, p_cur,
                        cfg.lp_iters, sh.s_nets, sh.s_verts)
        ML.note_program("parhyp_contract", sh.n_pad, sh.e_pad, p_cur,
                        sh.s_nets, sh.s_verts)
        with rec.span("parhyp_coarsen", level=lvl, n=n_cur):
            labels, _ = _parhyp_cluster_jit(
                mesh, L.pv, L.pe, L.mask, L.netw, L.esize, L.vwgt,
                labels0, capv, lvl, sh.rows_v, sh.n_col,
                sh.e_rows, cfg.lp_iters)
            (pv2, pe2, mask2, netw2, esize2, cvw, coarse_of,
             nc, hi) = _parhyp_contract_jit(mesh, L.pv, L.pe, L.mask,
                                            L.netw, L.vwgt, labels,
                                            sh.n_col, sh.e_rows)
            nc_i, hi_i = int(nc), int(hi)
        if nc_i >= n_cur * _STALL:
            break
        p_new = _pow2_pad(max(hi_i, 1), 256)
        if p_new < p_cur:
            pv2, pe2, mask2 = (a[:, :p_new] for a in (pv2, pe2, mask2))
        L.coarse_of = coarse_of
        levels.append(_DeviceLevel(pv2, pe2, mask2, netw2, esize2, cvw))
        n_cur = nc_i
        lvl += 1
    rec.count("parhyp/device_levels", len(levels))
    return levels, n_cur


def _extract_coarsest(L: _DeviceLevel) -> Tuple[Hypergraph, np.ndarray]:
    """Pull the coarsest device level to the host as a `Hypergraph`.

    Returns (hg, ids) where ids[c] is the device vertex id of host vertex
    c — the scatter map that seeds the device uncoarsening from the host
    initial partition."""
    pv = np.asarray(L.pv).reshape(-1)
    pe = np.asarray(L.pe).reshape(-1)
    mask = np.asarray(L.mask).reshape(-1)
    netw = np.asarray(L.netw)
    vwgt = np.asarray(L.vwgt)
    live = (mask > 0) & (netw[pe] > 0)
    real = vwgt > 0
    real[pv[live]] = True
    ids = np.flatnonzero(real)
    remap = np.full(len(vwgt), 0, np.int64)
    remap[ids] = np.arange(len(ids))
    pe_l = pe[live]
    pv_l = remap[pv[live]]
    order = np.argsort(pe_l, kind="stable")
    pe_s, pv_s = pe_l[order], pv_l[order]
    cnt = np.bincount(pe_s, minlength=len(netw))
    keepnet = (cnt >= 2) & (netw > 0)
    keep_pin = keepnet[pe_s]
    pv_s = pv_s[keep_pin]
    nid = np.flatnonzero(keepnet)
    eptr = np.concatenate([[0], np.cumsum(cnt[nid])]).astype(np.int64)
    hg = Hypergraph.from_arrays(len(ids), eptr, pv_s,
                                ewgt=netw[nid].astype(np.int64),
                                vwgt=np.maximum(vwgt[ids], 1).astype(
                                    np.int64))
    return hg, ids


# ---------------------------------------------------------------------------
# the parhyp program
# ---------------------------------------------------------------------------

PARHYP_PRESETS = {
    "ultrafast": dict(preset="fast", rounds=4),
    "fast":      dict(preset="fast", rounds=8),
    "eco":       dict(preset="eco", rounds=12),
}


def _parhyp_host(hg: Hypergraph, k: int, eps: float, cfg, rounds: int,
                 seed: int, mesh: Mesh, objective: str, rec) -> np.ndarray:
    """Host-orchestrated multilevel fallback (small inputs / stalled
    coarsening): hierarchy + initial-partition tournament from
    `HypergraphMedium`, the distributed LP round as the refinement engine
    at every level, the sequential force-balance refiner as the repair."""
    from repro.core import multilevel as ML
    from repro.core.hypergraph.coarsen import project
    from repro.core.hypergraph.driver import HypergraphMedium
    from repro.core.hypergraph.refine import refine_hypergraph
    levels = ML.build_hierarchy(HypergraphMedium(hg, cfg, objective),
                                k, seed)
    part = ML.initial_partition(levels[-1], k, eps, seed)

    def refine_level(hg_fine: Hypergraph, part: np.ndarray,
                     li: int) -> np.ndarray:
        part = parhyp_refine(hg_fine, part, k, eps, mesh, rounds=rounds,
                             seed=seed + li, objective=objective)
        if not M.is_feasible(hg_fine, part, k, eps):
            part = refine_hypergraph(hg_fine, part, k, eps, rounds=6,
                                     seed=seed + li, objective=objective,
                                     force_balance=True)
            rec.count("parhyp/repairs")
        return part

    score = M.connectivity if objective == "km1" else M.cut_net
    for li in range(len(levels) - 1, 0, -1):
        part = project(part, levels[li].cl)
        fine = levels[li - 1].medium.hg
        with rec.span("parhyp_level", level=li - 1, n=fine.n):
            part = refine_level(fine, part, li)
        if rec.enabled:
            rec.point("parhyp", level=li - 1,
                      objective=float(score(fine, part)))
    if len(levels) == 1:
        # single-level hierarchy: the loop above is empty — still refine
        # and repair at level 0 (the parhip bug PR 4 fixed)
        with rec.span("parhyp_level", level=0, n=hg.n):
            part = refine_level(hg, part, 0)
        if rec.enabled:
            rec.point("parhyp", level=0, objective=float(score(hg, part)))
    return part


def _parhyp_device(hg: Hypergraph, k: int, eps: float, cfg, rounds: int,
                   seed: int, mesh: Mesh, objective: str,
                   rec) -> Optional[np.ndarray]:
    """Device-resident V-cycle: coarsen → (host) initial partition on the
    coarsest → uncoarsen-refine, all level state staying on device.

    Returns None when coarsening stalls immediately (the caller falls back
    to the host-orchestrated path)."""
    from repro.core import multilevel as ML
    from repro.core.hypergraph.driver import HypergraphMedium
    from repro.core.hypergraph.refine import (_caps_for, _pad_caps,
                                              k_bucket, refine_hypergraph)
    s_nets, s_verts = _mesh_extents(mesh)
    sh = shard_hypergraph(hg, (s_nets, s_verts))
    levels, n_coarse = _device_hierarchy(sh, mesh, cfg, k, seed, rec)
    if len(levels) == 1:
        return None
    hg_c, ids = _extract_coarsest(levels[-1])
    with rec.span("parhyp_initial", n=hg_c.n, k=k):
        part_c = ML.multilevel(HypergraphMedium(hg_c, cfg, objective),
                               k, eps, seed)
    k_pad = k_bucket(k)
    cap = jnp.asarray(_pad_caps(_caps_for(hg, k, eps), k_pad), jnp.float32)
    lab_h = np.zeros(sh.n_pad, dtype=np.int32)
    lab_h[ids] = part_c
    labels = jnp.asarray(lab_h)
    score = M.connectivity if objective == "km1" else M.cut_net
    for li in range(len(levels) - 2, -1, -1):
        L = levels[li]
        ML.note_program("parhyp", sh.n_pad, sh.e_pad, L.pv.shape[1],
                        k_pad, rounds, objective, s_nets, s_verts)
        labels = jnp.take(labels, L.coarse_of)
        with rec.span("parhyp_level", level=li):
            out, obj, feas = _parhyp_refine_jit(
                mesh, L.pv, L.pe, L.mask, L.netw, L.esize, L.vwgt,
                labels, cap, jax.random.PRNGKey(seed + li),
                jnp.asarray(False), sh.rows_v, sh.n_col, sh.e_rows,
                k_pad, rounds, objective)
            rec.count("parhyp/dist_rounds", rounds)
            rec.count("parhyp/psum_rounds",
                      _psums_per_round(sh.n_pad) * rounds + 2)
            if not bool(feas):
                # forced-balance repair on the SAME device level views —
                # no re-sharding from the host container
                out, obj, feas = _parhyp_refine_jit(
                    mesh, L.pv, L.pe, L.mask, L.netw, L.esize, L.vwgt,
                    out, cap, jax.random.PRNGKey(seed + li + 7919),
                    jnp.asarray(True), sh.rows_v, sh.n_col, sh.e_rows,
                    k_pad, rounds, objective)
                rec.count("parhyp/repairs")
        labels = out
        if rec.enabled:
            rec.point("parhyp", level=li, objective=float(obj))
    part = np.asarray(labels, dtype=np.int64)[:hg.n]
    if not M.is_feasible(hg, part, k, eps):
        # last-resort host repair (forced balance never worsens feasibly)
        part = refine_hypergraph(hg, part, k, eps, rounds=6, seed=seed,
                                 objective=objective, force_balance=True)
        rec.count("parhyp/repairs")
    elif hg.n <= _POLISH_N:
        # small instances: one sequential polish pass (never-worse guard
        # inside) — quality insurance where its cost is negligible
        part = refine_hypergraph(hg, part, k, eps, rounds=6, seed=seed,
                                 objective=objective)
    if rec.enabled:
        rec.point("parhyp", level=0, objective=float(score(hg, part)))
    return part


def parhyp(hg: Hypergraph, k: int, eps: float = 0.03,
           preconfiguration: str = "fast", seed: int = 0,
           mesh: Optional[Mesh] = None, objective: str = "km1",
           report=None, device_min_n: Optional[int] = None) -> np.ndarray:
    """The ``parhyp`` program: distributed multilevel hypergraph
    partitioning (DESIGN.md §9).

    Device-resident V-cycle (distributed LP-clustering coarsening, host
    initial partition on the coarsest level only, distributed LP
    uncoarsening-refinement) for inputs above ``device_min_n`` (default
    ``_DEVICE_MIN_N``, the ParHIP gather-to-one-PE floor); the
    host-orchestrated multilevel on the shared engine remains the path
    for small inputs and the fallback for stalled coarsening.
    ``report`` is an optional ``obs.Recorder`` capturing the distributed
    rounds, psum counts, coarsening spans and per-level quality
    (DESIGN.md §11).
    """
    if objective not in ("km1", "cut"):
        raise ValueError(f"unknown objective {objective!r}")
    if k <= 1:
        return np.zeros(hg.n, dtype=np.int64)
    from repro.core import multilevel as ML
    from repro.core.hypergraph.driver import PRESETS
    pc = PARHYP_PRESETS[preconfiguration]
    cfg = PRESETS[pc["preset"]]
    rounds = pc["rounds"]
    if mesh is None:
        mesh = Mesh(np.array(jax.devices()), ("nets",))
    with obs.use(report):
        rec = obs.current()
        with rec.span("parhyp", n=hg.n, k=k,
                      preconfiguration=preconfiguration):
            part = None
            min_n = _DEVICE_MIN_N if device_min_n is None else device_min_n
            if hg.n > max(ML.coarsen_stop_n(cfg, k), min_n):
                part = _parhyp_device(hg, k, eps, cfg, rounds, seed, mesh,
                                      objective, rec)
            if part is None:
                part = _parhyp_host(hg, k, eps, cfg, rounds, seed, mesh,
                                    objective, rec)
    return part
