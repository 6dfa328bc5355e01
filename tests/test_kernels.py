"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (deliverable c)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.kernels import ops, ref


@pytest.mark.parametrize("n_pad,dmax,k", [
    (128, 8, 2), (256, 24, 5), (128, 16, 130), (384, 40, 17),
    (128, 300, 7)])  # dmax > DCH → several accumulated column chunks
@pytest.mark.parametrize("dtype", [np.float32])
def test_lp_affinity_sweep(n_pad, dmax, k, dtype):
    rng = np.random.default_rng(n_pad + dmax + k)
    nbr = rng.integers(0, n_pad, (n_pad, dmax)).astype(np.int32)
    wgt = (rng.random((n_pad, dmax)) *
           (rng.random((n_pad, dmax)) > 0.3)).astype(dtype)
    labels = rng.integers(0, k, (n_pad,)).astype(np.int32)
    got = ops.lp_affinity(jnp.asarray(nbr), jnp.asarray(wgt),
                          jnp.asarray(labels), k)
    want = ref.affinity_ref(jnp.asarray(labels)[jnp.asarray(nbr)],
                            jnp.asarray(wgt), k)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("e_pad,pmax,k", [(128, 8, 3), (256, 260, 9)])
def test_pin_count_chunks_bit_exact(e_pad, pmax, k):
    """Integer net weights: counts and scores equal the oracle bit for bit,
    also when the pin walk spans several grid chunks."""
    rng = np.random.default_rng(e_pad + pmax)
    pins = rng.integers(0, e_pad, (e_pad, pmax)).astype(np.int32)
    mask = (rng.random((e_pad, pmax)) > 0.4).astype(np.float32)
    netw = rng.integers(0, 1000, (e_pad,)).astype(np.float32)
    labels = jnp.asarray(rng.integers(0, k, (e_pad,)).astype(np.int32))
    cnt, score = ops.pin_count(jnp.asarray(pins), jnp.asarray(mask),
                               jnp.asarray(netw), labels, k)
    cnt_r, score_r = ref.pin_count_ref(labels[jnp.asarray(pins)],
                                       jnp.asarray(mask), jnp.asarray(netw),
                                       k)
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(cnt_r))
    np.testing.assert_array_equal(np.asarray(score), np.asarray(score_r))


@pytest.mark.parametrize("bh,l,p,n,chunk", [
    (2, 128, 8, 4, 64), (3, 256, 16, 8, 128), (1, 64, 32, 16, 32),
    (2, 200, 8, 8, 64)])  # l not divisible by chunk → padding path
def test_ssd_scan_sweep(bh, l, p, n, chunk):
    rng = np.random.default_rng(bh * l + p)
    x = rng.standard_normal((bh, l, p)).astype(np.float32)
    ld = (-0.05 - 0.5 * rng.random((bh, l))).astype(np.float32)
    b = (rng.standard_normal((bh, l, n)) * 0.3).astype(np.float32)
    c = (rng.standard_normal((bh, l, n)) * 0.3).astype(np.float32)
    got = ops.ssd_scan(jnp.asarray(x), jnp.asarray(ld), jnp.asarray(b),
                       jnp.asarray(c), chunk=chunk)
    want = ref.ssd_scan_ref(jnp.asarray(x), jnp.asarray(ld), jnp.asarray(b),
                            jnp.asarray(c))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-4, atol=3e-4)


def test_ssd_chunked_jnp_matches_ref():
    from repro.models.mamba2 import ssd_chunked
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((4, 192, 16)), jnp.float32)
    ld = jnp.asarray(-0.1 - 0.4 * rng.random((4, 192)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((4, 192, 8)) * 0.3, jnp.float32)
    c = jnp.asarray(rng.standard_normal((4, 192, 8)) * 0.3, jnp.float32)
    got = ssd_chunked(x, ld, b, c, chunk=64)
    want = ref.ssd_scan_ref(x, ld, b, c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-4)


def test_kernel_integrated_refinement_matches_jnp():
    """The Pallas affinity kernel plugged into k-way refinement must be
    bit-identical to the COO scatter path (same RNG stream)."""
    from repro.io.generators import grid2d
    from repro.core.refine import refine_kway
    from repro.core.initial import random_partition
    from repro.core.partition import edge_cut
    g = grid2d(12, 12)
    p0 = random_partition(g, 3, seed=0)
    a = refine_kway(g, p0, 3, rounds=5, seed=2, use_kernel=False)
    b = refine_kway(g, p0, 3, rounds=5, seed=2, use_kernel=True)
    assert edge_cut(g, a) == edge_cut(g, b)


def test_online_attention_matches_dense():
    from repro.models.attention import _sdpa, _sdpa_online
    from repro.models.layers import causal_mask
    rng = np.random.default_rng(1)
    b, sq, h, hd, kvh = 2, 96, 4, 16, 2
    q = jnp.asarray(rng.standard_normal((b, sq, h, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, sq, kvh, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, sq, kvh, hd)), jnp.float32)
    dense = _sdpa(q, k, v, causal_mask(sq, sq), None, 0.25)
    online = _sdpa_online(q, k, v, None, 0.25, q_offset=0, window=None,
                          is_causal=True)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(online),
                               rtol=2e-4, atol=2e-4)
    # with window + softcap
    dense_w = _sdpa(q, k, v, causal_mask(sq, sq, window=24), 30.0, 0.25)
    online_w = _sdpa_online(q, k, v, 30.0, 0.25, q_offset=0, window=24,
                            is_causal=True)
    np.testing.assert_allclose(np.asarray(dense_w), np.asarray(online_w),
                               rtol=2e-4, atol=2e-4)


def test_take_batches_as_flat_gather():
    """ops.take under vmap (batched table, shared or batched indices,
    nested) equals plain indexing."""
    rng = np.random.default_rng(5)
    t = jnp.asarray(rng.integers(0, 100, (3, 4, 50)).astype(np.int32))
    i = jnp.asarray(rng.integers(0, 50, (4, 7, 5)).astype(np.int32))
    np.testing.assert_array_equal(ops.take(t[0, 0], i[0]), t[0, 0][i[0]])
    np.testing.assert_array_equal(jax.vmap(ops.take, (0, None))(t[0], i[0]),
                                  t[0][:, i[0]])
    np.testing.assert_array_equal(
        jax.jit(jax.vmap(ops.take))(t[0], i),
        jnp.stack([t[0, j][i[j]] for j in range(4)]))
    np.testing.assert_array_equal(jax.vmap(ops.take, (None, 0))(t[0, 0], i),
                                  t[0, 0][i])
    np.testing.assert_array_equal(
        jax.vmap(jax.vmap(ops.take, (0, None)), (0, None))(t, i[0]),
        t[:, :, i[0]])
