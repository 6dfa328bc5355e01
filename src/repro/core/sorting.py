"""Stable multi-key argsort, and where the device's sorts are avoided.

``jnp.lexsort`` lowers to one XLA sort.  The TPU compiler takes tens of
seconds to build each program that holds a sort (at 2^12 elements as at
2^20), which a multilevel run with one program per level bucket cannot
afford; elsewhere sorts compile in well under a second and run fast.
`sort_free` names the backends whose device code avoids sorts.  There
`lexsort` is an LSD radix sort over non-negative integer keys of known
bit width: each pass ranks one 4-bit digit stably with a one-hot
cumulative count and scatters the permutation, and all passes run as one
`lax.scan`, so the pass body is compiled once.  Either way the result is
the permutation of ``jnp.lexsort`` (the stable order is unique).
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

DIGIT = 4                   # bits ranked per pass
_BUCKETS = 1 << DIGIT


def sort_free() -> bool:
    """Does device code avoid sorts here (TPU), or sort (elsewhere)?"""
    return jax.default_backend() == "tpu"


def lexsort(keys: Sequence[jax.Array], bits: Sequence[int]) -> jax.Array:
    """Stable argsort by ``keys[-1]``, then ``keys[-2]``, … (jnp.lexsort
    order).  ``keys[i]`` holds non-negative ints below ``2**bits[i]``.
    Its ops carry the ``lexsort`` scope in the program's metadata."""
    with jax.named_scope("lexsort"):
        if not sort_free():
            return jnp.lexsort(tuple(keys))
        return radix_lexsort(keys, bits)


def radix_lexsort(keys: Sequence[jax.Array],
                  bits: Sequence[int]) -> jax.Array:
    """`lexsort` without a sort: 4-bit LSD radix passes in one scan."""
    n = keys[0].shape[0]
    digits, shifts = [], []
    for i, b in enumerate(bits):
        for s in range(0, max(int(b), 1), DIGIT):
            digits.append(i)
            shifts.append(s)
    stacked = jnp.stack([k.astype(jnp.int32) for k in keys])
    buckets = jnp.arange(_BUCKETS, dtype=jnp.int32)

    def one_pass(perm, ks):
        key_i, shift = ks
        d = (stacked[key_i][perm] >> shift) & (_BUCKETS - 1)
        seen = jnp.cumsum((d[:, None] == buckets[None, :]).astype(jnp.int32),
                          axis=0)
        count = seen[-1]
        start = jnp.cumsum(count) - count
        dest = start[d] + jnp.take_along_axis(seen, d[:, None], axis=1)[:, 0]
        return jnp.zeros_like(perm).at[dest - 1].set(
            perm, unique_indices=True), None

    perm, _ = jax.lax.scan(one_pass, jnp.arange(n, dtype=jnp.int32),
                           (jnp.asarray(digits, jnp.int32),
                            jnp.asarray(shifts, jnp.int32)))
    return perm


def bit_width(bound: int) -> int:
    """Bits of the non-negative ints ``0 … bound`` (static)."""
    return max(int(bound).bit_length(), 1)
