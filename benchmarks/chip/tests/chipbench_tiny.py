"""Tiny cells for the benchmark's CPU tests: a copy of the benchmark's data
directories with new configurations and traffic written as files."""
import json
import shutil
from pathlib import Path

from benchmarks.chip.harness import HERE, ROOT, read_json

TINY_CONFIGS = {
    "tiny_mesh": {"instance": {"generator": "grid2d", "rows": 16,
                               "cols": 16},
                  "tool": "kaffpa", "control": {"eps": 0.05}},
    "tiny_rmat": {"instance": {"generator": "graph500_colnet", "scale": 8,
                               "edgefactor": 16, "A": 0.57, "B": 0.19,
                               "C": 0.19, "seed": 3},
                  "tool": "kahypar", "control": {"eps": 0.05}},
}
TINY_TRAFFIC = {
    "kaffpa_fast_k4": {"preset": "fast", "k": 4, "loop": "closed",
                       "clients": 1, "pool": [1, 2]},
    "kahypar_fast_k4": {"preset": "fast", "k": 4, "objective": "km1",
                        "loop": "closed", "clients": 1, "pool": [1, 2]},
}
TINY_METRICS = ("solve_s", "hierarchy_s", "uncoarsen_s", "window_compiles",
                "fresh_job_compiles", "fresh_job_compile_s")
CELLS = {"tiny_mesh.kaffpa_fast_k4": ("tiny_mesh", "kaffpa_fast_k4"),
         "tiny_rmat.kahypar_fast_k4": ("tiny_rmat", "kahypar_fast_k4")}


def tiny_data(tmp: Path):
    """→ (benchmark dict, data dir): the committed benchmark plus the tiny
    cells, each of their files new in a copy of the data directories."""
    data = tmp / "chip"
    shutil.copytree(HERE, data, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    for name, cfg in TINY_CONFIGS.items():
        full = dict(cfg, name=name, guarantees={"eps": 0.03}, reduced=[])
        (data / "configs" / f"{name}.json").write_text(json.dumps(full))
    for name, tr in TINY_TRAFFIC.items():
        (data / "traffic" / f"{name}.json").write_text(json.dumps(tr))
    bench = read_json(ROOT / "BENCHMARK.json")
    for name, (config, traffic) in CELLS.items():
        bench["workloads"].append({"name": name, "config": config,
                                   "traffic": traffic, "chips": 1,
                                   "why": "CPU test"})
        kind = {"kaffpa": "graph", "kahypar": "hypergraph"}[
            TINY_CONFIGS[config]["tool"]]
        for m in bench["per_layer"] + bench["end_to_end"]:
            if "workloads" in m and m["name"] in {
                    f"{q}.{kind}" for q in TINY_METRICS}:
                m["workloads"].append(name)
    return bench, data


def cpu_device() -> dict:
    return {"platform": "cpu", "kind": "cpu", "count": 1,
            "memory_peak_bytes": 0}
