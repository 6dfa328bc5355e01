"""Pallas TPU kernel: per-net pin-count histogram — the hot loop of
hypergraph LP refinement and clustering (core/hypergraph/refine.py).

Computes, for every net e and block b over the net→pin ELL layout:

  cnt[e, b]   = Σ_j  mask[e, j] · [pin_lab[e, j] == b]      (pin count)
  score[e, b] = w(e) · cnt[e, b]                            (weighted)

The vertex-side pin affinity ``aff[v, b] = Σ_{e ∋ v} w(e)·|{u ∈ e :
lab[u] = b}|`` is then one XLA gather+sum of ``score`` rows over the
vertex→nets ELL (kernels/ops.py) — irregular gathers stay outside the
kernel exactly as in lp_affinity.py.

Same tiling and chunk walk as lp_affinity (its ``accumulate_chunk`` builds
the count tile); the differences are the per-row net-weight scaling fused
into the last chunk and the dual (cnt, score) output, which the refinement
gain formulas both need (λ−1 gains want raw counts, absorption affinities
want weighted scores).

Grid: (e_pad/BN, k_pad/BK, pmax/DCH); net weights ride along as a (BN, 1)
column.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.lp_affinity import (BK, BN, COMPILER_PARAMS,
                                       accumulate_chunk, grid_and_chunk)


def _pin_affinity_kernel(pin_lab_ref, mask_ref, netw_ref, cnt_ref,
                         score_ref):
    """One chunk of one (BN nets × BK blocks) tile of (cnt, score)."""
    accumulate_chunk(pin_lab_ref, mask_ref, cnt_ref)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _():
        score_ref[...] = cnt_ref[...] * netw_ref[...]


@functools.partial(jax.jit, static_argnames=("k_pad", "interpret"))
def pin_affinity_pallas(pin_lab: jax.Array, mask: jax.Array,
                        netw: jax.Array, k_pad: int,
                        interpret: bool = False):
    """(e_pad, pmax) pin labels/mask + (e_pad,) net weights →
    ((e_pad, k_pad) counts, (e_pad, k_pad) weighted scores).

    Requires e_pad % BN == 0, k_pad % BK == 0 and pmax == padded_width(pmax).
    """
    e_pad, pmax = pin_lab.shape
    grid, dch = grid_and_chunk(e_pad, k_pad, pmax)
    in_spec = pl.BlockSpec((BN, dch), lambda i, j, c: (i, c))
    out_spec = pl.BlockSpec((BN, BK), lambda i, j, c: (i, j))
    return pl.pallas_call(
        _pin_affinity_kernel,
        grid=grid,
        in_specs=[in_spec, in_spec,
                  pl.BlockSpec((BN, 1), lambda i, j, c: (i, 0))],
        out_specs=[out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((e_pad, k_pad), jnp.float32),
            jax.ShapeDtypeStruct((e_pad, k_pad), jnp.float32),
        ],
        compiler_params=COMPILER_PARAMS,
        interpret=interpret,
        name="pin_affinity",
    )(pin_lab, mask, netw.reshape(e_pad, 1))
