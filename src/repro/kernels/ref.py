"""Pure-jnp oracles for the Pallas kernels (tests assert_allclose vs these)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

#: full-f32 contraction: the TPU's default matmul precision rounds f32
#: operands to bf16, which would make weights above 256 inexact
_EXACT = jax.lax.Precision.HIGHEST


def affinity_ref(nbr_lab: jax.Array, wgt: jax.Array, k_pad: int) -> jax.Array:
    """aff[v, b] = Σ_j wgt[v, j] · [nbr_lab[v, j] == b]  — (n_pad, k_pad)."""
    hit = jax.nn.one_hot(nbr_lab, k_pad, dtype=jnp.float32)   # (n, d, k)
    return jnp.einsum("nd,ndk->nk", wgt.astype(jnp.float32), hit,
                      precision=_EXACT)


def pin_count_ref(pin_lab: jax.Array, mask: jax.Array, netw: jax.Array,
                  k_pad: int):
    """(cnt, score) oracle for the pin-affinity kernel.

    cnt[e, b] = Σ_j mask[e, j]·[pin_lab[e, j] == b];  score = netw·cnt.
    Counts are small integers in f32, so sums are exact and both outputs
    match the Pallas kernel bit-for-bit (for integer-valued net weights).
    """
    hit = jax.nn.one_hot(pin_lab, k_pad, dtype=jnp.float32)   # (e, p, k)
    cnt = jnp.einsum("ep,epk->ek", mask.astype(jnp.float32), hit,
                     precision=_EXACT)
    return cnt, cnt * netw[:, None]


def pin_affinity_ref(vnets: jax.Array, pin_lab: jax.Array, mask: jax.Array,
                     netw: jax.Array, k_pad: int) -> jax.Array:
    """aff[v, b] = Σ_{e ∈ vnets[v]} netw[e] · cnt[e, b]  — (n_pad, k_pad).

    Padding slots of ``vnets`` point at a padding net (netw == 0)."""
    _, score = pin_count_ref(pin_lab, mask, netw, k_pad)
    return jnp.sum(score[vnets], axis=1)


def ssd_scan_ref(x: jax.Array, logdecay: jax.Array, b: jax.Array,
                 c: jax.Array) -> jax.Array:
    """Exact sequential SSD recurrence.

    h_t = exp(logdecay_t) · h_{t-1} + b_t ⊗ x_t ;  y_t = h_tᵀ c_t
    x: (BH, L, P), logdecay: (BH, L), b/c: (BH, L, N) → y: (BH, L, P)
    """
    bh, l, p = x.shape
    n = b.shape[-1]

    def step(h, inp):
        xt, ldt, bt, ct = inp
        h = jnp.exp(ldt)[:, None, None] * h + bt[:, :, None] * xt[:, None, :]
        y = jnp.einsum("znp,zn->zp", h, ct)
        return h, y

    h0 = jnp.zeros((bh, n, p), jnp.float32)
    xs = (jnp.swapaxes(x, 0, 1), jnp.swapaxes(logdecay, 0, 1),
          jnp.swapaxes(b, 0, 1), jnp.swapaxes(c, 0, 1))
    _, ys = jax.lax.scan(step, h0, xs)
    return jnp.swapaxes(ys, 0, 1)
