"""Benchmark driver (deliverable d): one section per paper table/use-case.

Prints ``name,us_per_call,derived`` CSV rows.  Roofline tables (deliverable
g) are produced by ``benchmarks/roofline.py`` from the dry-run artifacts.

``python benchmarks/run.py --smoke`` runs the end-to-end engine benchmark,
the node-separator benchmark, the distributed-hypergraph smoke, the
memetic smoke and the serve-telemetry smoke, writing ``BENCH_engine.json``,
``BENCH_nodesep.json``, ``BENCH_parhyp.json``, ``BENCH_memetic.json`` and
``BENCH_serve_obs.json`` (+ ``BENCH_serve_trace.json``, the Perfetto
serve timeline) — the CI perf-trajectory records.

``--scale`` / ``--scale-smoke`` run the million-vertex (resp. ~130k CI)
``parhyp_scale`` cells.  Host/runtime flags for scale runs are set up
*before* jax is imported:

* ``--devices N`` → ``--xla_force_host_platform_device_count=N`` (SPMD
  over N fake CPU devices);
* ``JAX_ENABLE_X64=0`` / ``JAX_DEFAULT_DTYPE_BITS=32`` defaulted (the
  engine is f32/int32 end to end);
* the persistent compilation cache is turned on before the first compile
  (``repro.launch.cache``: ``$JAX_COMPILATION_CACHE_DIR`` if set, else
  ``.jax_cache/`` in the checkout);
* ``--tcmalloc`` → re-exec with ``LD_PRELOAD=libtcmalloc`` and a high
  ``TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD`` — glibc malloc returns the
  multi-GB coarsening arenas to the OS poorly at 1M+ vertices.
"""
from __future__ import annotations

import os
import sys

# `python benchmarks/run.py` puts benchmarks/ (not the repo root) on
# sys.path — pin the root so `from benchmarks import …` always resolves
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

_TCMALLOC = "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4"


def _setup_env(argv: list) -> list:
    """Consume env-shaping flags; must run before any jax import."""
    args = list(argv)
    if "--devices" in args:
        i = args.index("--devices")
        n = int(args[i + 1])
        del args[i:i + 2]
        flags = os.environ.get("XLA_FLAGS", "")
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}".strip())
    os.environ.setdefault("JAX_ENABLE_X64", "0")
    os.environ.setdefault("JAX_DEFAULT_DTYPE_BITS", "32")
    if "--tcmalloc" in args:
        args.remove("--tcmalloc")
        if (not os.environ.get("_REPRO_TCMALLOC")
                and os.path.exists(_TCMALLOC)):
            env = dict(os.environ, LD_PRELOAD=_TCMALLOC,
                       TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD="60000000000",
                       _REPRO_TCMALLOC="1")
            os.execve(sys.executable, [sys.executable] + sys.argv, env)
    return args


def scale(smoke_run: bool) -> None:
    from benchmarks import bench_parhyp
    bench_parhyp.scale_main(smoke=smoke_run)


def smoke() -> None:
    from benchmarks import (bench_engine, bench_memetic, bench_nodesep,
                            bench_parhyp, bench_serve_obs)
    eng = bench_engine.main()
    # compile-count columns (DESIGN.md §12): per cell, cold-run backend
    # compiles plus the shape-bucket registry's padding/sharing counters
    print("cell,compile_count,bucket_pads,compile_cache_hits,s")
    for name, cell in eng["engine"].items():
        print(f"{name},{cell['compile_count']},{cell['bucket_pads']},"
              f"{cell['compile_cache_hits']},{cell['s']}")
    bench_nodesep.main()
    bench_parhyp.main()
    bench_memetic.main()
    bench_serve_obs.main()


def main() -> None:
    from benchmarks import (bench_partitioning, bench_tools, bench_kernels,
                            bench_hypergraph)
    print("name,us_per_call,derived")
    print("# --- kaffpa presets / kabape / kaffpaE / parhip (paper §2.1-2.5)")
    bench_partitioning.main()
    print("# --- separators / edge partitioning / ordering / mapping / ILP "
          "(paper §2.6-2.10)")
    bench_tools.main()
    print("# --- multilevel node separators vs post-hoc baseline (§2.8)")
    from benchmarks import bench_nodesep
    bench_nodesep.main()
    print("# --- hypergraph partitioning (kahypar vs star-expansion baseline)")
    bench_hypergraph.main()
    print("# --- distributed hypergraph partitioning (parhyp vs kahypar)")
    from benchmarks import bench_parhyp
    bench_parhyp.main()
    print("# --- memetic engine (kahyparE/kaffpaE vs single runs)")
    from benchmarks import bench_memetic
    bench_memetic.main()
    print("# --- kernels (DESIGN.md §6)")
    bench_kernels.main()
    print("# --- roofline (from dry-run artifacts, if present)")
    try:
        from benchmarks import roofline
        cells = roofline.load_cells()
        if cells:
            md, _ = roofline.render(cells)
            for ln in md.splitlines():
                print("#", ln)
        else:
            print("# (no dry-run artifacts; run python -m repro.launch.dryrun"
                  " --all)")
    except Exception as e:  # pragma: no cover
        print(f"# roofline unavailable: {e}")


if __name__ == "__main__":
    _args = _setup_env(sys.argv[1:])
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    if "--scale-smoke" in _args:
        scale(smoke_run=True)
    elif "--scale" in _args:
        scale(smoke_run=False)
    elif "--smoke" in _args:
        smoke()
    else:
        main()
