"""Rumor-spreading migration as a collective (DESIGN.md §10).

Every migration round each island pushes its best individual's partition
vector one ring step of ``shift`` islands: island i receives from island
(i - shift) mod I.  A seeded random shift per round is the randomized
rumor-spreading exchange of the paper's MPI formulation, restated as a
*static* permutation so it maps onto ``jax.lax.ppermute`` when the islands
are laid out as shards on a device mesh.

The stacked best-parts matrix (I, n) is sharded along the islands axis;
a global ring roll of island rows decomposes into at most two
``ppermute`` block exchanges plus an intra-shard reorder: with
``ipd = I / S`` islands per device and ``shift = q·ipd + r``, destination
device d needs rows from source devices (d-q) and (d-q-1) — block A
shifted q devices forward supplies local rows r.., block B shifted q+1
supplies rows ..r.  With one device both permutes are the identity and
the reorder is exactly the host ``np.roll`` — the mesh round is
bit-identical to the host-loop fallback (pinned by a regression test),
which also serves meshes whose device count does not divide the island
count.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

AXIS = "islands"


def islands_mesh(devices=None) -> Mesh:
    """A 1-D ``islands`` mesh over the given (default: all local) devices."""
    devs = np.asarray(jax.devices() if devices is None else devices)
    return Mesh(devs.reshape(-1), (AXIS,))


def ring_roll_host(parts: np.ndarray, shift: int) -> np.ndarray:
    """Host fallback: out[i] = parts[(i - shift) mod I]."""
    parts = np.asarray(parts)
    return np.roll(parts, shift % len(parts), axis=0)


@functools.partial(jax.jit, static_argnames=("mesh", "shift", "ipd", "n_sh"))
def _ring_roll_jit(mesh: Mesh, parts, shift: int, ipd: int, n_sh: int):
    q, r = divmod(shift, ipd)

    def local(block):
        a = jax.lax.ppermute(block, AXIS,
                             [(s, (s + q) % n_sh) for s in range(n_sh)])
        if r == 0:
            return a
        b = jax.lax.ppermute(block, AXIS,
                             [(s, (s + q + 1) % n_sh) for s in range(n_sh)])
        return jnp.concatenate([b[ipd - r:], a[:ipd - r]], axis=0)

    fn = shard_map(local, mesh=mesh, in_specs=P(AXIS, None),
                   out_specs=P(AXIS, None), check_vma=False)
    return fn(parts)


def ring_roll(parts: np.ndarray, shift: int, mesh=None) -> np.ndarray:
    """Ring-migrate the (I, n) best-parts matrix by ``shift`` islands.

    With a mesh whose device count divides I the roll runs as ppermute
    block exchanges on the ``islands`` sharding; otherwise (or with
    ``mesh=None``) the host fallback computes the identical result.
    """
    parts = np.asarray(parts, dtype=np.int32)
    n_isl = parts.shape[0]
    shift %= n_isl
    if shift == 0:
        return parts.copy()
    if mesh is None:
        return ring_roll_host(parts, shift)
    devs = np.asarray(mesh.devices).reshape(-1)
    if n_isl % len(devs) != 0:
        return ring_roll_host(parts, shift)
    m = Mesh(devs, (AXIS,))
    out = _ring_roll_jit(m, jnp.asarray(parts), shift, n_isl // len(devs),
                         len(devs))
    return np.asarray(out)
