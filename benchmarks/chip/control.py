"""Readings of the check that decides ``correct``: the program's, and the
control's, on the same jobs.

    python benchmarks/chip/control.py --workload <cell> --seeds 11 12 13

After one set-up, as ``run.py`` makes it, each seed draws as many
partitioner seeds outside the pool as the pool holds, and each of those
jobs is solved twice through the window's entry point at the cell's size:
at the ε that the configuration states (the program: the lower reading)
and at the looser ε of the configuration's ``control`` (the control: the
program with its balance guarantee loosened, the step a change could take
to reach a better objective).  Both are checked against the
configuration's ε.  One JSON line per seed; the benchmark's own runs
never run the control.  Needs the chip, like run.py.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def readings(cell, inst, prepared, seed: int) -> dict:
    from benchmarks.chip import harness
    jobs = harness.fresh_seeds(cell.traffic, seed, len(cell.traffic["pool"]))
    out = {"seed": seed, "jobs": jobs}
    for side, eps in (("program", cell.eps),
                      ("control", float(cell.config["control"]["eps"]))):
        solves = [harness.timed_solve(cell, prepared, eps, j) for j in jobs]
        checks = harness.check_window(cell, inst, solves)
        out[side] = {k: v["value"] for k, v in checks.items()}
        out[side + "_correct"] = harness.checks_pass(checks)
        out[side + "_objective"] = [s["recount"] for s in solves]
        out[side + "_s"] = [s["seconds"] for s in solves]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from benchmarks.chip import harness, run
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control.py: no TPU", file=sys.stderr)
        return 1
    run.enable_cache()
    cell = harness.load_cell(args.workload)
    t0 = time.perf_counter()
    inst, prepared = harness.setup(cell)
    print(json.dumps({"setup_s": time.perf_counter() - t0}), flush=True)
    for seed in args.seeds:
        print(json.dumps(readings(cell, inst, prepared, seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
