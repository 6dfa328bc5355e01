"""Run one cell of the chip benchmark and print its result line.

    python benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

One process on the chip the cell asks for: it refuses to run without a TPU
(or with fewer chips than the cell's), turns on the persistent compilation
cache inside the checkout, builds the cell's instance, solves each
partitioner seed of the traffic's pool once (set-up), then runs partition
jobs back to back for ``--seconds`` through the library's entry point, and
checks every partition on the host after the window.  With ``--trace 0``
the last line of standard output holds the cell's end-to-end metrics; with
``--trace 1`` the window is traced, one more job outside the pool counts
what a new job compiles, and the line holds the per-layer metrics, the
device's busy time and a breakdown.  Each number the check compared is
printed beside its limit on standard error and under ``checks`` in the
result line.
"""
from __future__ import annotations

import time

CLOCK0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_stamp() -> dict:
    import jax
    devs = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(max(peaks))}


def enable_cache() -> None:
    """The program's persistent compilation cache, kept in the checkout;
    every program is written to it, however quickly it compiled."""
    import jax
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    args = parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import repro  # noqa: F401  (the program under test; absent, no run)
    from benchmarks.chip import harness
    from benchmarks.chip.devtrace import load_peaks, reduce_trace
    cell = harness.load_cell(args.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"run.py: needs {cell.chips} TPU chip(s), JAX has {len(devs)} "
              f"{devs[0].platform} device(s)", file=sys.stderr)
        return 1
    peaks = load_peaks(devs[0].device_kind)
    enable_cache()
    result = harness.run_cell(
        cell, args.seed, args.seconds, bool(args.trace), CLOCK0,
        device_stamp, reduce_trace=reduce_trace, peaks=peaks)
    print(f"correct {result['correct']}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
