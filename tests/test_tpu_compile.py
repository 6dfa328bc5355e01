"""Compile the main path's Pallas kernels for a described TPU v5e chip.

Nothing runs: each test lowers and compiles at deployment widths against a
v5e topology described by the installed TPU compiler, and asserts that the
program holds the Mosaic kernel (``tpu_custom_call``).  This catches what
interpret mode cannot: lowering rules Mosaic lacks, misaligned tiles and
VMEM overruns.  The topology is described inside a module fixture only, so
collection never loads the TPU library; where it cannot be described the
tests skip.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import lp_affinity as lpk
from repro.kernels import ops
from repro.kernels import pin_affinity as pink

#: one v5e chip's HBM (Google Cloud documentation, "TPU v5e")
V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler for this platform here
        mp.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    mp.undo()


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Off the chip the ops interpret their kernels and the device code
    sorts; compile the kernels and the chip's sort-free forms instead."""
    from repro.core import lp, sorting
    monkeypatch.setattr(ops, "_interpret", lambda kernel: False)
    monkeypatch.setattr(lp, "sort_free", lambda: True)
    monkeypatch.setattr(sorting, "sort_free", lambda: True)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *specs):
    return jax.jit(fn).lower(*specs).compile()


@pytest.mark.parametrize("n,dmax", [(2**20, 8), (2**16, 1024)])
def test_affinity_pallas_compiles(one_chip, n, dmax):
    c = _compile(lambda lab, w: lpk.affinity_pallas(lab, w, lpk.BK),
                 _spec((n, dmax), jnp.int32, one_chip),
                 _spec((n, dmax), jnp.float32, one_chip))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("e,pmax", [(2**20, 16), (2**17, 64)])
def test_pin_affinity_pallas_compiles(one_chip, e, pmax):
    c = _compile(lambda lab, m, w: pink.pin_affinity_pallas(lab, m, w,
                                                            lpk.BK),
                 _spec((e, pmax), jnp.int32, one_chip),
                 _spec((e, pmax), jnp.float32, one_chip),
                 _spec((e,), jnp.float32, one_chip))
    assert "tpu_custom_call" in c.as_text()


def test_kaffpa_refine_scan_compiles(one_chip, compiled_kernels):
    """The batched k-way refinement program of kaffpa's finest level on
    grid2d(1024, 1024) (fast preset: batch 2, 6 rounds), k = 16, with the
    affinity kernel inside; it must fit one chip's HBM."""
    from repro.core.csr import CooGraph, EllGraph
    from repro.core.refine import _refine_scan_batch
    n, e, dmax, b, rounds, k = 2**20, 2**22, 8, 2, 6, 16
    coo = CooGraph(src=_spec((e,), jnp.int32, one_chip),
                   dst=_spec((e,), jnp.int32, one_chip),
                   w=_spec((e,), jnp.float32, one_chip),
                   vwgt=_spec((n,), jnp.float32, one_chip))
    ell = EllGraph(nbr=_spec((n, dmax), jnp.int32, one_chip),
                   wgt=_spec((n, dmax), jnp.float32, one_chip),
                   vwgt=_spec((n,), jnp.float32, one_chip))
    c = _refine_scan_batch.lower(
        coo, _spec((b, n), jnp.int32, one_chip),
        _spec((k,), jnp.float32, one_chip),
        _spec((b, rounds, 2), jnp.uint32, one_chip),
        _spec((b,), jnp.int32, one_chip), _spec((b,), jnp.bool_, one_chip),
        _spec((b,), jnp.bool_, one_chip), _spec((b, n), jnp.bool_, one_chip),
        k, rounds, ell=ell, use_kernel=True).compile()
    assert "tpu_custom_call" in c.as_text()
    mem = c.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < V5E_HBM_BYTES, used
