"""Public jit'd wrappers around the Pallas kernels.

On TPU the kernels compile natively; on CPU (this container) they run in
``interpret=True`` mode — the kernel body executes in Python with the same
block decomposition, validating tiling and semantics.

Masking contract (DESIGN.md §12): every input dimension may arrive padded
to its pow2 shape bucket, and tile correctness relies ONLY on weight masks
— ``wgt == 0`` for ELL slots, ``pin_mask == 0`` for pin slots, ``netw ==
0`` for padding nets, zero capacity for bucket-padding blocks (k_pad > k).
Index sentinels (slot id n_pad-1 etc.) are never trusted as masks: a
padded slot may alias a real row when a dim lands exactly on its bucket.
Affinity columns for capacity-zero padding blocks are computed but can
never win a gain comparison, so k-bucketed calls share one tile program
with the larger-k calls they pad up to.

Every trace that places a compiled (not interpreted) kernel into a program
counts ``kernels/mosaic/<kernel>`` in the ``repro.obs`` registry, so a run
can show that its refinement programs really hold the TPU kernels.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import lp_affinity as _lpk
from repro.kernels import pin_affinity as _pink
from repro.kernels import ssd_scan as _ssdk
from repro.kernels import ref as _ref
from repro.obs import metrics


#: Machine-readable form of the masking contract above, keyed by public op:
#: ``mask`` is the argument whose zeros mark padding slots, ``garbage`` the
#: index arguments whose padded slots are unconstrained (any valid id).  The
#: `repro.analysis` padding-inertness checker perturbs exactly the garbage
#: slots and requires bit-identical real outputs.
PADDING_CONTRACT = {
    "lp_affinity": {"mask": "wgt", "garbage": ("nbr",)},
    "sep_affinity": {"mask": "wgt", "garbage": ("nbr",)},
    "pin_count": {"mask": "pin_mask", "garbage": ("pins",)},
    "pin_affinity": {"mask": "pin_mask", "garbage": ("pins", "vnets")},
}


def _interpret(kernel: str) -> bool:
    """Interpret off TPU; on TPU count the compiled kernel's trace."""
    if jax.default_backend() != "tpu":
        return True
    metrics.inc(f"kernels/mosaic/{kernel}")
    return False


@jax.custom_batching.custom_vmap
def take(table: jax.Array, idx: jax.Array) -> jax.Array:
    """``table[idx]`` for a 1-D table, batching as one flat gather.

    Under ``vmap`` over the table, the TPU compiler can lower the batched
    gather with a temporary of ~512 bytes per index (8.6 GB for a
    2 × 2^17 label table gathered by 2^24 pin slots, which left kahypar's
    refinement unable to load on a 16 GB chip); offsetting the indices
    into the flattened table keeps it a plain gather."""
    return table[idx]


@take.def_vmap
def _take_vmap(axis_size, in_batched, table, idx):
    table_b, idx_b = in_batched
    if not table_b:
        return take(table, idx), idx_b
    n = table.shape[1]
    rows = jnp.arange(axis_size, dtype=idx.dtype) * n
    rows = rows.reshape((axis_size,) + (1,) * (idx.ndim - idx_b))
    return take(table.reshape(-1), idx + rows), True


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def lp_affinity(nbr: jax.Array, wgt: jax.Array, labels: jax.Array,
                k: int, use_pallas: bool = True) -> jax.Array:
    """ELL graph + labels → (n_pad, k) block affinities.

    The neighbour-label gather runs in XLA (memory-bound); the one-hot
    contraction runs in the Pallas kernel (compute-bound).  Padded ELL slots
    carry wgt == 0, so their (valid) gathered labels contribute nothing.
    """
    nbr_lab = labels[nbr]                         # XLA gather
    if not use_pallas:
        return _ref.affinity_ref(nbr_lab, wgt, k)
    n_pad, dmax = nbr.shape
    k_pad = _round_up(k, _lpk.BK)
    d_pad = _lpk.padded_width(dmax)
    if d_pad != dmax:
        pad = d_pad - dmax
        nbr_lab = jnp.pad(nbr_lab, ((0, 0), (0, pad)), constant_values=0)
        wgt = jnp.pad(wgt, ((0, 0), (0, pad)))
    aff = _lpk.affinity_pallas(nbr_lab, wgt, k_pad,
                               interpret=_interpret("lp_affinity"))
    return aff[:, :k]


def sep_affinity(nbr: jax.Array, wgt: jax.Array, vwgt: jax.Array,
                 labels: jax.Array, use_pallas: bool = True) -> jax.Array:
    """ELL graph + 3-labels → (n_pad, 3) neighbour *vertex-weight* histogram
    — the separator-gain contraction (DESIGN.md §8).

    Same kernel as ``lp_affinity`` with k=3 and the edge weights replaced by
    gathered neighbour vertex weights; ``wgt > 0`` is the invariant mask (a
    padded ELL slot may alias a real vertex when n == n_pad, so the edge
    weight — zero exactly on padding — gates the gather, not the slot id).
    """
    vw_nbr = jnp.where(wgt > 0, vwgt[nbr], 0.0)
    return lp_affinity(nbr, vw_nbr, labels, 3, use_pallas=use_pallas)


def pin_count(pins: jax.Array, pin_mask: jax.Array, netw: jax.Array,
              labels: jax.Array, k: int, use_pallas: bool = True):
    """Net→pin ELL + labels → ((e_pad, k) pin counts, weighted scores).

    The pin-label gather runs in XLA (memory-bound); the one-hot contraction
    and net-weight scaling run in the Pallas kernel (compute-bound).  Padded
    pin slots carry pin_mask == 0 and contribute nothing.
    """
    pin_lab = take(labels, pins)                  # XLA gather
    if not use_pallas:
        cnt, score = _ref.pin_count_ref(pin_lab, pin_mask, netw, k)
        return cnt, score
    e_pad, pmax = pins.shape
    k_pad = _round_up(k, _lpk.BK)
    p_pad = _lpk.padded_width(pmax)
    if p_pad != pmax:
        pad = p_pad - pmax
        pin_lab = jnp.pad(pin_lab, ((0, 0), (0, pad)), constant_values=0)
        pin_mask = jnp.pad(pin_mask, ((0, 0), (0, pad)))
    cnt, score = _pink.pin_affinity_pallas(pin_lab, pin_mask, netw, k_pad,
                                           interpret=_interpret(
                                               "pin_affinity"))
    return cnt[:, :k], score[:, :k]


def pin_affinity(vnets: jax.Array, pins: jax.Array, pin_mask: jax.Array,
                 netw: jax.Array, labels: jax.Array, k: int,
                 use_pallas: bool = True) -> jax.Array:
    """Dual-ELL hypergraph + labels → (n_pad, k) pin affinities:

        aff[v, b] = Σ_{e ∋ v} w(e) · |{pins of e with label b}|

    Per-net scores come from the Pallas kernel; the irregular vertex-side
    accumulation is an XLA gather+sum over ``vnets`` rows (padding slots
    point at a zero-weight net)."""
    _, score = pin_count(pins, pin_mask, netw, labels, k,
                         use_pallas=use_pallas)
    return jnp.sum(score[vnets], axis=1)


def ssd_scan(x: jax.Array, logdecay: jax.Array, b: jax.Array, c: jax.Array,
             chunk: int = 128, use_pallas: bool = True) -> jax.Array:
    """Mamba2 SSD scan: (BH, L, P) × (BH, L) × (BH, L, N)² → (BH, L, P)."""
    if not use_pallas:
        return _ref.ssd_scan_ref(x, logdecay, b, c)
    l = x.shape[1]
    if l % chunk != 0:
        pad = _round_up(l, chunk) - l
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        logdecay = jnp.pad(logdecay, ((0, 0), (0, pad)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
    y = _ssdk.ssd_scan_pallas(x, logdecay, b, c, chunk=chunk,
                              interpret=_interpret("ssd_scan"))
    return y[:, :l]
