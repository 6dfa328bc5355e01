"""The trace reduction, on interval arithmetic and on small traces that
one TPU v5e recorded of the two tools through the harness (tiny cells;
``tests/data``).  Reading a trace needs ``jax.profiler.ProfileData`` only,
which loads no TPU library."""
import gzip
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from benchmarks.chip import devtrace as D  # noqa: E402
from benchmarks.chip.harness import HERE, load_module  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
SPANS = ["run", "multilevel", "hierarchy", "coarsen", "initial_tournament",
         "uncoarsen", "refine"]
TRACES = {"tiny_mesh.kaffpa_fast_k4": "lp_affinity",
          "tiny_rmat.kahypar_fast_k4": "pin_affinity"}
V5E = "TPU v5 lite"


def reduced(cell):
    from jax.profiler import ProfileData
    raw = gzip.decompress((DATA / f"{cell}.xplane.pb.gz").read_bytes())
    return D.reduce_profile(ProfileData.from_serialized_xspace(raw), SPANS)


def test_union_and_gaps():
    iv = [(0, 10), (5, 20), (30, 40), (35, 36), (50, 60)]
    assert D.union_seconds(iv) == pytest.approx(40e-9)
    assert D.gaps(iv, 0, 70) == [(20, 30), (40, 50), (60, 70)]
    assert D.gaps(iv, 8, 33) == [(20, 30)]
    assert D.union_seconds([]) == 0.0


def test_gaps_labelled_by_innermost_span():
    spans = [(0, 100, "solve"), (10, 60, "uncoarsen"), (20, 30, "refine")]
    got = D._label_gaps([(22, 28), (40, 50), (70, 90), (120, 130)], spans)
    assert got == pytest.approx({"refine": 6e-9, "uncoarsen": 10e-9,
                                 "solve": 20e-9, "none": 10e-9})


def test_parse_kernel_from_hlo_text():
    op = ('%pin_affinity.14 = (f32[2,1024,128]{2,1,0:T(8,128)S(1)}, '
          'f32[2,1024,128]{2,1,0:T(8,128)}) custom-call(s32[2,1024,32]'
          '{2,1,0:T(8,128)S(1)} %reshape.538, f32[1024,32]{1,0:T(8,128)S(1)}'
          ' %copy-done.27, f32[1024,1]{1,0:T(8,128)S(1)} %copy-done.13), '
          'custom_call_target="tpu_custom_call", operand_layout_constraints='
          '{s32[2,1024,32]{2,1,0}, f32[1024,32]{1,0}, f32[1024,1]{1,0}}')
    name, res, opd = D.parse_kernel(op)
    assert name == "pin_affinity"
    assert res == [("f32", (2, 1024, 128))] * 2
    assert opd == [("s32", (2, 1024, 32)), ("f32", (1024, 32)),
                   ("f32", (1024, 1))]
    assert D.parse_kernel("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %a)") \
        is None


@pytest.mark.parametrize("cell", sorted(TRACES))
def test_reduce_recorded_trace(cell):
    kernel = TRACES[cell]
    r = reduced(cell)
    assert r["chips"] == 1
    assert 0 < r["busy_s"] <= r["window_s"]
    bd = r["breakdown"]
    assert 0 < len(bd["device_ops"]) <= D.TOP
    assert 0 < len(bd["idle_gaps"]) <= D.TOP
    assert f"kernel:{kernel}" in {name for name, _ in bd["device_ops"]}
    assert any(name.startswith("program:jit_") for name, _ in
               bd["device_ops"])
    idle = sum(s for _, s in bd["idle_gaps"])
    assert idle <= r["window_s"] - r["busy_s"] + 1e-9
    assert set(n for n, _ in bd["idle_gaps"]) <= set(SPANS) | {"solve",
                                                              "none"}
    calls = [k for k in r["kernels"] if k["name"] == kernel]
    assert calls and all(k["calls"] > 0 and k["seconds"] > 0 for k in calls)
    share = D.roofline_share(r, kernel, load_module(
        HERE / "kernels" / f"{kernel}.py"), D.load_peaks(V5E))
    assert 0 < share <= 100
    assert json.loads(json.dumps(r)) == r


def test_no_call_no_share():
    r = reduced("tiny_mesh.kaffpa_fast_k4")
    cost = load_module(HERE / "kernels" / "pin_affinity.py")
    assert D.roofline_share(r, "pin_affinity", cost, D.load_peaks(V5E)) \
        is None
    assert D.roofline_share(None, "pin_affinity", cost,
                            D.load_peaks(V5E)) is None
