"""Device primitives vs plain host references: capped acceptance
(core/lp.py) in its sorted and sort-free forms, and the radix lexsort
(core/sorting.py)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import lp, sorting
from repro.core.lp import capped_accept
from repro.core.sorting import bit_width, lexsort, radix_lexsort


def _capped_accept_ref(labels, proposal, vwgt, sizes, cap, priority):
    """Per target, movers in (priority desc, index asc) order are accepted
    while size + inclusive inflow fits the capacity."""
    out = labels.copy()
    moving = proposal != labels
    for t in np.unique(proposal[moving]):
        idx = np.flatnonzero(moving & (proposal == t))
        # stable sort on -priority keeps index order among equal priorities
        # (-0.0 and 0.0 compare equal, as in the device order key)
        idx = idx[np.argsort(-priority[idx], kind="stable")]
        inflow = np.cumsum(vwgt[idx], dtype=np.float64)
        ok = sizes[t] + inflow <= cap[t]
        out[idx[ok]] = t
    return out


@pytest.mark.parametrize("sort_free", [False, True])
@pytest.mark.parametrize("clustering", [False, True])
def test_capped_accept_matches_sorted_prefix_reference(monkeypatch,
                                                       clustering,
                                                       sort_free):
    monkeypatch.setattr(lp, "sort_free", lambda: sort_free)
    rng = np.random.default_rng(3 + clustering)
    moved = 0
    for trial in range(60):
        n = int(rng.integers(1, 200))
        k = n if clustering else int(rng.integers(1, 9))
        labels = rng.integers(0, k, n).astype(np.int32)
        proposal = np.where(rng.random(n) < 0.6, rng.integers(0, k, n),
                            labels).astype(np.int32)
        vwgt = rng.integers(0, 4, n).astype(np.float32)
        sizes = np.bincount(labels, weights=vwgt,
                            minlength=k).astype(np.float32)
        cap = (sizes + rng.integers(-3, 15, k)).astype(np.float32)
        if trial % 2:
            priority = rng.choice(np.array(
                [-1e30, -0.0, 0.0, 1.5, -2.25, 3e-5, 7.0, 2.0], np.float32), n)
        else:
            priority = rng.standard_normal(n).astype(np.float32)
        got = np.asarray(jax.jit(capped_accept)(
            *(jnp.asarray(a) for a in (labels, proposal, vwgt, sizes, cap,
                                       priority))))
        want = _capped_accept_ref(labels, proposal, vwgt, sizes, cap,
                                  priority)
        np.testing.assert_array_equal(got, want)
        moved += int((got != labels).sum())
    assert moved > 0


@pytest.mark.parametrize("bounds", [(2, 50, 9), (1, 1, 1), (70000, 3, 2),
                                    (5,)])
def test_radix_lexsort_matches_jnp_lexsort(bounds):
    rng = np.random.default_rng(len(bounds) + bounds[0])
    for n in (1, 2, 17, 1000):
        keys = [jnp.asarray(rng.integers(0, b + 1, n).astype(np.int32))
                for b in bounds]
        got = jax.jit(lambda *ks: radix_lexsort(
            ks, [bit_width(b) for b in bounds]))(*keys)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(jnp.lexsort(tuple(keys))))


@pytest.mark.parametrize("backend,radix", [("cpu", False), ("tpu", True)])
def test_sort_free_follows_backend(monkeypatch, backend, radix):
    """Sorts stay where they compile fast; TPU device code avoids them."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert sorting.sort_free() is radix
    calls = []
    monkeypatch.setattr(sorting, "radix_lexsort",
                        lambda ks, bits: calls.append(bits) or jnp.lexsort(
                            tuple(ks)))
    keys = (jnp.asarray([3, 1, 2]), jnp.asarray([0, 0, 1]))
    np.testing.assert_array_equal(np.asarray(lexsort(keys, (2, 1))),
                                  [1, 0, 2])
    assert bool(calls) is radix
