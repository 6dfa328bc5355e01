"""Pallas TPU kernel: block-affinity histogram — the hot loop of every
LP-based phase (coarsening clustering, LP refinement, ParHIP rounds).

Computes  aff[v, b] = Σ_j  wgt[v, j] · [nbr_lab[v, j] == b]
i.e. ``A_ELL @ onehot(labels)`` — an (n × dmax × k) contraction.

TPU adaptation (DESIGN.md §2/§6): the irregular CSR gather (labels of
neighbours) is done by XLA outside the kernel (memory-bound, gather engine);
the one-hot contraction runs here on 128-row tiles resident in VMEM,
accumulating a (BN, BK) affinity tile on the VPU.  Each ELL column is one
lane-broadcast compare against the tile's block ids, so every vector op is
a plain 2-D (BN, BK) tile op: no 3-D compare cube, no value slicing at a
traced offset (Mosaic lowers neither).

Grid: (n_pad/BN, k_pad/BK, dmax/DCH).  The last axis walks dmax in chunks
of at most DCH columns, each chunk unrolled statically inside the kernel;
the output tile stays resident across it and accumulates (its block index
ignores the chunk), so VMEM use is bounded by (BN, DCH) input blocks at any
degree.  k_pad/BK is almost always 1 for partitioning workloads (k ≤ 128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BN = 128          # rows per tile (sublane-aligned)
BK = 128          # blocks per tile (lane-aligned)
DC = 8            # dmax padding granule
DCH = 128         # most ELL columns per grid step (one lane tile)

#: row tiles and block tiles are independent; the chunk axis accumulates
COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def padded_width(dmax: int) -> int:
    """ELL width the kernels accept: a multiple of DC, and of DCH above it."""
    d = -(-dmax // DC) * DC
    return d if d <= DCH else -(-d // DCH) * DCH


def grid_and_chunk(rows: int, k_pad: int, width: int):
    """(grid, chunk width) of a (rows, width) × k_pad affinity call."""
    dch = min(width, DCH)
    assert (rows % BN == 0 and k_pad % BK == 0 and width % DC == 0
            and width % dch == 0), (rows, k_pad, width)
    return (rows // BN, k_pad // BK, width // dch), dch


def accumulate_chunk(lab_ref, wgt_ref, acc_ref):
    """acc += Σ_{t in chunk} wgt[:, t] · [lab[:, t] == block id] on one
    (BN, BK) tile; zeroes the tile on the first chunk."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    lab = lab_ref[...]              # (BN, dch) int32
    wgt = wgt_ref[...]              # (BN, dch) f32
    kids = pl.program_id(1) * BK + jax.lax.broadcasted_iota(
        jnp.int32, (1, BK), 1)
    acc = acc_ref[...]
    for t in range(lab.shape[1]):   # static unroll: ≤ DCH columns
        acc = acc + jnp.where(lab[:, t:t + 1] == kids, wgt[:, t:t + 1],
                              jnp.float32(0))
    acc_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("k_pad", "interpret"))
def affinity_pallas(nbr_lab: jax.Array, wgt: jax.Array, k_pad: int,
                    interpret: bool = False) -> jax.Array:
    """(n_pad, dmax) neighbour labels/weights → (n_pad, k_pad) affinities.

    Requires n_pad % BN == 0, k_pad % BK == 0 and dmax == padded_width(dmax).
    """
    n_pad, dmax = nbr_lab.shape
    grid, dch = grid_and_chunk(n_pad, k_pad, dmax)
    in_spec = pl.BlockSpec((BN, dch), lambda i, j, c: (i, c))
    return pl.pallas_call(
        accumulate_chunk,
        grid=grid,
        in_specs=[in_spec, in_spec],
        out_specs=pl.BlockSpec((BN, BK), lambda i, j, c: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_pad, k_pad), jnp.float32),
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
        name="lp_affinity",
    )(nbr_lab, wgt)
