"""ParHIP — distributed-memory parallel partitioning via shard_map (§2.5).

The MPI design of ParHIP maps onto JAX collectives (DESIGN.md §2):

  * nodes (and their out-edges) are block-distributed over the mesh axis
    ``nodes`` — exactly ParHIP's vertex distribution;
  * each LP round reads the *replicated* label vector (the ghost-label
    exchange becomes one all-gather inserted by SPMD partitioning), computes
    new labels for owned nodes only, and enforces the size constraint with a
    per-shard slice of the *global* remaining capacity (psum'd histogram) —
    so the constraint holds globally without a sequential arbiter;
  * cluster-size histograms and cut values are ``psum`` reductions.

The same round function serves both phases: clustering (labels over [0, n))
for coarsening and k-way refinement during uncoarsening.  Preconfigurations
{ultrafast,fast,eco}×{mesh,social} select rounds/iterations (§4.3.1).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro import obs
from repro.core.csr import Graph, _pow2_pad
from repro.core import coarsen as C
from repro.core import kaffpa as K
from repro.core.partition import edge_cut, is_feasible

_NEG = -1e30
_NOISE = 1e-4
_GAIN_EPS = 1e-3


@dataclasses.dataclass
class ShardedGraph:
    """Host container: node-block-distributed COO (global ids)."""
    src: np.ndarray     # (S, emax) int32, padding points at row 0 w/ w=0
    dst: np.ndarray     # (S, emax) int32
    w: np.ndarray       # (S, emax) float32
    vwgt: np.ndarray    # (S, rows) float32
    n: int
    rows: int

    @property
    def n_shards(self) -> int:
        return self.src.shape[0]

    @property
    def n_pad(self) -> int:
        return self.n_shards * self.rows


def shard_graph(g: Graph, n_shards: int, row_mult: int = 8) -> ShardedGraph:
    n = g.n
    rows = _pow2_pad(max((n + n_shards - 1) // n_shards, 1), row_mult)
    n_pad = rows * n_shards
    src_h = g.edge_sources()
    owner = src_h // rows
    emax = int(np.bincount(owner, minlength=n_shards).max()) if len(src_h) else 1
    emax = _pow2_pad(max(emax, 1), 8)
    src = np.zeros((n_shards, emax), dtype=np.int32)
    dst = np.zeros((n_shards, emax), dtype=np.int32)
    w = np.zeros((n_shards, emax), dtype=np.float32)
    for s in range(n_shards):
        ids = np.flatnonzero(owner == s)
        src[s, :] = s * rows              # padding: own first row, w == 0
        dst[s, :] = s * rows
        src[s, :len(ids)] = src_h[ids]
        dst[s, :len(ids)] = g.adjncy[ids]
        w[s, :len(ids)] = g.adjwgt[ids]
    vw = np.zeros((n_shards, rows), dtype=np.float32)
    flat = np.zeros(n_pad, dtype=np.float32)
    flat[:n] = g.vwgt
    vw[:] = flat.reshape(n_shards, rows)
    return ShardedGraph(src, dst, w, vw, n, rows)


def _kway_round_local(src, dst, w, vwgt, labels, sizes_g, cap, key, parity,
                      rows: int, k: int, n_shards: int, axis: str):
    """Body run per shard under shard_map. labels: full replicated (n_pad,).

    Rank-2 inputs arrive as (1, ·) local blocks — flatten to local vectors.
    """
    src, dst, w, vwgt = (a.reshape(-1) for a in (src, dst, w, vwgt))
    me = jax.lax.axis_index(axis)
    off = me * rows
    lab_own = jax.lax.dynamic_slice(labels, (off,), (rows,))
    tgt = labels[dst]
    aff = jnp.zeros((rows, k), jnp.float32).at[src - off, tgt].add(w)
    noise = jax.random.uniform(jax.random.fold_in(key, me), (rows, k),
                               jnp.float32, 0.0, _NOISE)
    own = jnp.take_along_axis(aff, lab_own[:, None].astype(jnp.int32),
                              axis=1)[:, 0]
    gain = aff - own[:, None] + noise
    gain = gain.at[jnp.arange(rows), lab_own].set(_NEG)
    room = sizes_g[None, :] + vwgt[:, None] <= cap[None, :]
    gain = jnp.where(room, gain, _NEG)
    best_gain = jnp.max(gain, axis=1)
    best_tgt = jnp.argmax(gain, axis=1).astype(lab_own.dtype)
    gid = off + jnp.arange(rows)
    want = (best_gain > _GAIN_EPS) & ((gid + parity) % 2 == 0)
    proposal = jnp.where(want, best_tgt, lab_own)
    # local capped acceptance against this shard's slice of global capacity
    cap_local = sizes_g + (cap - sizes_g) / n_shards
    from repro.core.lp import capped_accept
    new_lab = capped_accept(lab_own, proposal, vwgt, sizes_g, cap_local,
                            jnp.where(want, best_gain, _NEG))
    return new_lab


@functools.partial(jax.jit,
                   static_argnames=("rows", "k", "rounds", "n_shards",
                                    "axis", "mesh"))
def _parhip_refine_jit(mesh: Mesh, src, dst, w, vwgt, labels0, cap, key,
                       rows: int, k: int, rounds: int, n_shards: int,
                       axis: str = "nodes"):
    spec_e = P(axis, None)
    spec_r = P()

    def sizes_of(labels):
        return jnp.zeros((k,), jnp.float32).at[labels].add(
            vwgt.reshape(-1))

    round_fn = shard_map(
        functools.partial(_kway_round_local, rows=rows, k=k,
                          n_shards=n_shards, axis=axis),
        mesh=mesh,
        in_specs=(spec_e, spec_e, spec_e, P(axis, None), spec_r, spec_r,
                  spec_r, spec_r, spec_r),
        out_specs=P(axis),
        check_vma=False,
    )

    def body(carry, key_r):
        labels, parity = carry
        sizes = sizes_of(labels)
        new_labels = round_fn(src, dst, w, vwgt, labels, sizes, cap, key_r,
                              parity)
        return (new_labels, parity + 1), jnp.int32(0)

    keys = jax.random.split(key, rounds)
    (labels, _), _ = jax.lax.scan(body, (labels0, jnp.int32(0)), keys)
    return labels


def parhip_refine(g: Graph, part: np.ndarray, k: int, eps: float,
                  mesh: Mesh, rounds: int = 8, seed: int = 0,
                  axis: str = "nodes") -> np.ndarray:
    """Distributed k-way LP refinement (never applied blindly: caller keeps
    the better of in/out)."""
    n_shards = int(np.prod([mesh.shape[a] for a in mesh.axis_names
                            if a == axis]))
    rec = obs.current()
    sg = shard_graph(g, n_shards)
    labels0 = np.zeros(sg.n_pad, dtype=np.int32)
    labels0[:g.n] = part
    total = g.total_vwgt()
    cap = jnp.full((k,), (1.0 + eps) * np.ceil(total / k), jnp.float32)
    # vwgt reshaped flat for rows owned by shards; padding rows weight 0
    with rec.span("parhip_refine", n=g.n, rounds=rounds, shards=n_shards):
        out = _parhip_refine_jit(mesh, jnp.asarray(sg.src),
                                 jnp.asarray(sg.dst),
                                 jnp.asarray(sg.w), jnp.asarray(sg.vwgt),
                                 jnp.asarray(labels0), cap,
                                 jax.random.PRNGKey(seed), sg.rows, k,
                                 rounds, n_shards, axis)
        cand = np.asarray(out)[:g.n].astype(np.int64)
    rec.count("parhip/dist_rounds", rounds)
    rec.count("parhip/psum_rounds", rounds)   # one sizes-histogram psum/round
    if (edge_cut(g, cand) <= edge_cut(g, part)
            and is_feasible(g, cand, k, eps)):
        return cand
    rec.count("parhip/rounds_rejected")
    return part


PARHIP_PRESETS = {
    "ultrafastmesh":   dict(preset="fast", rounds=4),
    "fastmesh":        dict(preset="fast", rounds=8),
    "ecomesh":         dict(preset="eco", rounds=12),
    "ultrafastsocial": dict(preset="fastsocial", rounds=4),
    "fastsocial":      dict(preset="fastsocial", rounds=8),
    "ecosocial":       dict(preset="ecosocial", rounds=12),
}


def parhip(g: Graph, k: int, eps: float = 0.03,
           preconfiguration: str = "fastmesh", seed: int = 0,
           mesh: Optional[Mesh] = None,
           vertex_degree_weights: bool = False, report=None) -> np.ndarray:
    """The ``parhip`` program (§4.3.1).

    Host-orchestrated multilevel with the distributed LP round as the
    refinement engine at every level; the coarsest graph is partitioned by
    the (evolutionary-grade) sequential path, as in the paper.  ``report``
    is an optional ``obs.Recorder`` (DESIGN.md §11).
    """
    if vertex_degree_weights:
        g = Graph(g.xadj, g.adjncy, 1 + g.degrees(), g.adjwgt)
    pc = PARHIP_PRESETS[preconfiguration]
    cfg = K.PRESETS[pc["preset"]]
    if mesh is None:
        mesh = Mesh(np.array(jax.devices()), ("nodes",))
    from repro.core import multilevel as ML
    with obs.use(report):
        rec = obs.current()
        with rec.span("parhip", n=g.n, k=k,
                      preconfiguration=preconfiguration):
            levels = ML.build_hierarchy(K.GraphMedium(g, cfg), k, seed)
            part = ML.initial_partition(levels[-1], k, eps, seed)

            def refine_level(g_fine: Graph, part: np.ndarray,
                             li: int) -> np.ndarray:
                part = parhip_refine(g_fine, part, k, eps, mesh,
                                     rounds=pc["rounds"], seed=seed + li)
                if not is_feasible(g_fine, part, k, eps):
                    from repro.core import refine as R
                    part = R.refine_kway(g_fine, part, k, eps, rounds=6,
                                         seed=seed + li, force_balance=True)
                    rec.count("parhip/repairs")
                return part

            for li in range(len(levels) - 1, 0, -1):
                part = C.project(part, levels[li].cl)
                fine = levels[li - 1].medium.g
                with rec.span("parhip_level", level=li - 1, n=fine.n):
                    part = refine_level(fine, part, li)
                if rec.enabled:
                    rec.point("parhip", level=li - 1,
                              objective=float(edge_cut(fine, part)))
            if len(levels) == 1:
                # single-level hierarchy (n <= stop_n): the loop above is
                # empty — still run the distributed refiner and the
                # feasibility repair at level 0 instead of returning the raw
                # initial partition
                with rec.span("parhip_level", level=0, n=g.n):
                    part = refine_level(g, part, 0)
                if rec.enabled:
                    rec.point("parhip", level=0,
                              objective=float(edge_cut(g, part)))
    return part
