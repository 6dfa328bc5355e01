"""Cells, configurations, traffic and per-layer metrics are found by name:
adding one is adding files.  ``run.py`` refuses to run off the chip."""
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(Path(__file__).parent)]

from benchmarks.chip import harness  # noqa: E402
from chipbench_tiny import cpu_device, tiny_data  # noqa: E402

CELL = "tiny_mesh.kaffpa_fast_k4"


def test_new_files_are_found_by_name_and_run(tmp_path):
    bench, data = tiny_data(tmp_path)
    (data / "metrics" / "solves_seen.py").write_text(
        "def read(ctx):\n    return float(ctx.n_solves)\n")
    (data / "metrics" / "nothing_to_read.py").write_text(
        "def read(ctx):\n    return None\n")
    for name in ("solves_seen", "nothing_to_read"):
        bench["per_layer"].append({
            "name": name, "unit": "count", "better": "higher",
            "source": "program_counter", "layer": "test",
            "moves": "solve_s.graph", "workloads": [CELL]})
    cell = harness.load_cell(CELL, bench, data)
    assert cell.config["instance"]["rows"] == 16 and cell.k == 4
    assert {m["name"] for m in cell.per_layer} == {
        "hierarchy_s.graph", "uncoarsen_s.graph", "window_compiles.graph",
        "fresh_job_compiles.graph", "fresh_job_compile_s.graph",
        "solves_seen", "nothing_to_read"}

    out = harness.run_cell(cell, 2 ** 33 + 1, 0.2, False,
                           time.perf_counter(), cpu_device)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"solve_s.graph", "objective", "setup_s"}
    assert list(out)[-1] == "checks"

    def fake_trace(tdir, window):
        return {"busy_s": 0.5, "window_s": window.seconds,
                "breakdown": {"device_ops": [], "idle_gaps": []}}
    out = harness.run_cell(cell, 5, 0.2, True, time.perf_counter(),
                           cpu_device, reduce_trace=fake_trace)
    assert out["correct"], out["checks"]
    got = out["metrics"]
    # the traced run checks one more solve, with a seed outside the pool
    assert got["solves_seen"]["value"] == out["attempted"] - 1
    assert got["fresh_job_compiles.graph"]["value"] >= 0
    assert got["fresh_job_compile_s.graph"]["value"] >= 0
    assert "nothing_to_read" not in got
    assert got["hierarchy_s.graph"]["value"] > 0
    assert got["uncoarsen_s.graph"]["value"] > 0
    assert out["device"]["busy_s"] == 0.5 and "breakdown" in out


def test_order_drawn_from_the_seed():
    traffic = {"pool": [5, 6, 7, 8]}
    big = 2 ** 40 + 3
    assert harness.solve_order(traffic, big) == harness.solve_order(traffic,
                                                                    big)
    assert sorted(harness.solve_order(traffic, big)) == [5, 6, 7, 8]
    orders = {tuple(harness.solve_order(traffic, s)) for s in range(40)}
    assert len(orders) > 1
    assert sorted(harness.solve_order(traffic, -1)) == [5, 6, 7, 8]


def _run_py(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "mesh2d_1m.kaffpa_fast_k16", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_run_py_refuses_without_tpu():
    r = _run_py(ROOT)
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "TPU" in r.stderr


def test_run_py_fails_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p)
    r = _run_py(tmp_path)
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "repro" in r.stderr


def test_fresh_job_lies_outside_the_pool(tmp_path):
    """The traced run's extra job: a seed outside the pool, and a new
    instance where the generator takes a seed; its partition is checked."""
    bench, data = tiny_data(tmp_path)
    for name, new_instance in (("tiny_mesh.kaffpa_fast_k4", False),
                               ("tiny_rmat.kahypar_fast_k4", True)):
        cell = harness.load_cell(name, bench, data)
        fresh = harness.fresh_solve(cell, 2 ** 34 + 3)
        assert fresh["seed"] not in cell.traffic["pool"]
        assert fresh["seed"] == harness.fresh_seeds(cell.traffic,
                                                    2 ** 34 + 3, 1)[0]
        same = fresh["inst"] is cell.instance()
        assert same != new_instance
        checks = harness.check_window(cell, cell.instance(), [fresh])
        assert harness.checks_pass(checks), checks
        assert fresh["compiles"] >= 0 and fresh["compile_s"] >= 0
