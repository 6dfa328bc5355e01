"""Kernel operation and byte counts, and the table of peaks."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import devtrace as D  # noqa: E402
from benchmarks.chip.harness import HERE, load_module  # noqa: E402


def _shape(a):
    dt = {np.dtype(np.int32): "s32", np.dtype(np.float32): "f32"}[a.dtype]
    return dt, tuple(a.shape)


def test_lp_affinity_bytes_are_its_arrays():
    """Bytes counted from the shapes equal the sizes of the arrays the
    kernel reads and writes: gathered labels, weights and affinities."""
    cost = load_module(HERE / "kernels" / "lp_affinity.py")
    lab = np.zeros((384, 16), np.int32)
    wgt = np.ones((384, 16), np.float32)
    out = np.zeros((384, 128), np.float32)
    moved = cost.cost([_shape(out)], [_shape(lab), _shape(wgt)])
    assert moved == lab.nbytes + wgt.nbytes + out.nbytes
    batched, out2 = np.stack([lab, lab]), np.stack([out, out])
    moved2 = cost.cost([_shape(out2)], [_shape(batched), _shape(wgt)])
    assert moved2 == batched.nbytes + wgt.nbytes + out2.nbytes


def test_pin_affinity_bytes_are_its_arrays():
    cost = load_module(HERE / "kernels" / "pin_affinity.py")
    lab = np.zeros((256, 8), np.int32)
    mask = np.ones((256, 8), np.float32)
    col = np.ones((256, 1), np.float32)
    cnt = np.zeros((256, 128), np.float32)
    moved = cost.cost([_shape(cnt), _shape(cnt)],
                      [_shape(lab), _shape(mask), _shape(col)])
    assert moved == lab.nbytes + mask.nbytes + col.nbytes + 2 * cnt.nbytes


def test_roofline_share_is_bytes_over_bandwidth():
    """Two calls of 8 MB each in 40 us of device time at 819 GB/s."""
    cost = load_module(HERE / "kernels" / "lp_affinity.py")
    shape = [("f32", (1024, 1024))]
    trace = {"kernels": [{"name": "lp_affinity", "calls": 2,
                          "seconds": 40e-6, "results": shape,
                          "operands": shape}]}
    peaks = D.load_peaks("TPU v5 lite")
    want = 100.0 * 2 * 8 * 2 ** 20 / 819e9 / 40e-6
    assert D.roofline_share(trace, "lp_affinity", cost, peaks) == \
        pytest.approx(want)
    assert D.roofline_share(trace, "pin_affinity", cost, peaks) is None


def test_peaks_by_device_kind():
    p = D.load_peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        D.load_peaks("TPU v9 imaginary")


def test_nbytes_and_shapes():
    assert D.shapes("f32[2,3]{1,0} x, s32[] y, pred[4]") == [
        ("f32", (2, 3)), ("s32", ()), ("pred", (4,))]
    assert D.nbytes(("bf16", (4, 8))) == 64
