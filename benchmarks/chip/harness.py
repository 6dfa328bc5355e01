"""The benchmark's cells, found by name, and one measured run of a cell.

Everything a cell needs is data found by name:

- ``BENCHMARK.json`` (the checkout's root) lists each cell's configuration,
  traffic and metrics;
- ``configs/<config>.json`` names the instance generator
  (``instances/<generator>.py``), its parameters, the tool
  (``tools/<tool>.py``) and the guarantees, ε among them;
- ``traffic/<traffic>.json`` holds the job: preset, k, objective, the
  closed loop and the seed rule;
- ``metrics/<metric>.py`` reads one per-layer metric from the window;
- ``kernels/<kernel>.py`` counts the bytes one kernel call moves.

A run (``run_cell``) builds the instance, solves each partitioner seed of
the traffic's pool once (set-up), then runs partition jobs back to back,
one client, starting another while less than ``seconds`` have passed; the
window closes when the last returns.  A traced run then makes one more
job outside the pool (a new partitioner seed, and a new instance where the
generator takes a seed) and counts what it compiles.
After the window every partition is checked on the host
(``check_window``).
"""
from __future__ import annotations

import importlib.util
import json
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType, SimpleNamespace
from typing import Callable, Dict, List, Optional

import numpy as np

from benchmarks.chip import reference as R

HERE = Path(__file__).resolve().parent          # benchmarks/chip
ROOT = HERE.parents[1]                          # the checkout

def load_module(path: Path) -> ModuleType:
    """Import one file of the benchmark by its path."""
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file not found: {path}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"benchmark file not found: {path}")
    return json.loads(path.read_text())


@dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    generator: ModuleType
    tool: ModuleType
    chips: int
    end_to_end: List[dict]
    per_layer: List[dict]
    data: Path = HERE
    _instance: object = field(default=None, repr=False)

    @property
    def eps(self) -> float:
        return float(self.config["guarantees"]["eps"])

    @property
    def k(self) -> int:
        return int(self.traffic["k"])

    def instance(self):
        if self._instance is None:
            self._instance = self.generator.build(self.config["instance"])
        return self._instance

    def metric_reader(self, name: str) -> Callable:
        return load_module(self.data / "metrics" / f"{name}.py").read


def _for_cell(metrics: List[dict], cell: str) -> List[dict]:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def load_cell(name: str, benchmark: Optional[dict] = None,
              data: Path = HERE) -> Cell:
    """The cell ``name`` of ``benchmark`` (default: the checkout's
    ``BENCHMARK.json``), its files looked up under ``data``."""
    if benchmark is None:
        benchmark = read_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in benchmark["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    config = read_json(data / "configs" / f"{w['config']}.json")
    traffic = read_json(data / "traffic" / f"{w['traffic']}.json")
    return Cell(
        name=name, config=config, traffic=traffic,
        generator=load_module(
            data / "instances" / f"{config['instance']['generator']}.py"),
        tool=load_module(data / "tools" / f"{config['tool']}.py"),
        chips=int(w["chips"]),
        end_to_end=_for_cell(benchmark["end_to_end"], name),
        per_layer=_for_cell(benchmark["per_layer"], name), data=data)


def solve_order(traffic: dict, seed: int) -> List[int]:
    """The pool's partitioner seeds in the order drawn from ``seed`` (any
    whole number); the window cycles through this order."""
    pool = [int(s) for s in traffic["pool"]]
    rng = np.random.default_rng(np.random.SeedSequence(int(seed) % 2 ** 64))
    return [pool[i] for i in rng.permutation(len(pool))]


def _block(out):
    """Wait for any device work still behind ``out``."""
    import jax
    return jax.block_until_ready(out)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

@dataclass
class Window:
    """What the measured window produced."""
    seconds: float
    solves: List[dict]
    compiles: int
    events: list
    trace: Optional[dict] = None
    fresh: Optional[dict] = None


def compiles_so_far() -> int:
    from repro.obs import metrics
    return int(metrics.get("jax/compiles", 0))


def compile_seconds_so_far() -> float:
    """Seconds in JAX's compile-or-load of new programs: a program the
    persistent cache holds is loaded, and counted as a compile too."""
    from repro.obs import metrics
    return float(metrics.get("jax/compile_secs", 0.0))


def cache_loads_so_far() -> int:
    from repro.obs import metrics
    return int(metrics.get("jax/compile_cache_hits", 0))


def fresh_seeds(traffic: dict, seed: int, count: int) -> List[int]:
    """``count`` partitioner seeds drawn from ``seed``, none in the pool."""
    pool = {int(s) for s in traffic["pool"]}
    rng = np.random.default_rng(
        np.random.SeedSequence([int(seed) % 2 ** 64, 1]))
    out: List[int] = []
    while len(out) < count:
        s = int(rng.integers(1, 2 ** 31))
        if s not in pool and s not in out:
            out.append(s)
    return out


def timed_solve(cell: Cell, prepared, eps: float, seed: int,
                report=None) -> dict:
    """One job through the entry point, waited for → a solve record with
    its wall seconds and the process's CPU seconds in it."""
    ts, cs = time.perf_counter(), time.process_time()
    obj, part = _block(cell.tool.solve(prepared, eps, seed, report))
    return {"seed": seed, "objective": obj, "part": part,
            "seconds": time.perf_counter() - ts,
            "cpu_s": time.process_time() - cs}


def fresh_solve(cell: Cell, seed: int) -> dict:
    """One job outside the pool, drawn from ``seed``, as a user who brings
    a new one pays it: a partitioner seed outside the pool and, where the
    configuration's generator takes a seed, an instance made from that
    seed.  → its solve record (with its ``inst``), the programs it
    compiled (``compiles``: not found in the persistent cache), those it
    loaded from the cache (``loads``), and the seconds spent on both."""
    job = fresh_seeds(cell.traffic, seed, 1)[0]
    params = cell.config["instance"]
    inst = cell.generator.build(dict(params, seed=job)) \
        if "seed" in params else cell.instance()
    c0, s0, h0 = compiles_so_far(), compile_seconds_so_far(), \
        cache_loads_so_far()
    prepared = cell.tool.prepare(inst, cell.traffic)
    out = timed_solve(cell, prepared, cell.eps, job)
    loads = cache_loads_so_far() - h0
    out.update(inst=inst, compiles=compiles_so_far() - c0 - loads,
               loads=loads, compile_s=compile_seconds_so_far() - s0)
    return out


def setup(cell: Cell):
    """Instance, entry-point arguments, and one warm-up solve of every
    partitioner seed of the pool: a seed decides the sizes of the coarse
    levels, so each compiles programs of its own."""
    from repro import obs
    obs.install_jax_compile_listener()
    inst = cell.instance()
    prepared = cell.tool.prepare(inst, cell.traffic)
    for s in cell.traffic["pool"]:
        _block(cell.tool.solve(prepared, cell.eps, int(s)))
    return inst, prepared


def measure(cell: Cell, prepared, seed: int, seconds: float, trace: bool,
            trace_dir: Optional[str] = None) -> Window:
    """The closed loop: solves back to back while under ``seconds``.

    With ``trace`` the engine's spans are recorded (and written into the
    profiler trace as annotations) and the profiler traces the window."""
    import jax
    from repro import obs
    rec = obs.Recorder(cell.name, annotate_xprof=True) if trace else None
    order = solve_order(cell.traffic, seed)
    c0 = compiles_so_far()
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # no Python function events
        opts.host_tracer_level = 1        # the annotations, not the runtime
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    solves = []
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        s = order[len(solves) % len(order)]
        with jax.profiler.TraceAnnotation("solve"):
            solves.append(timed_solve(cell, prepared, cell.eps, s, rec))
    window_s = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    return Window(window_s, solves, compiles_so_far() - c0,
                  rec.events if rec else [])


def check_window(cell: Cell, inst, solves: List[dict]) -> Dict[str, dict]:
    """Every partition of ``solves`` against the host reference, at the
    configuration's ε → {check: {value, limit}}.  A solve is of ``inst``
    unless it carries its own.  Each solve gets its host recount
    (``recount``, None where its labels are bad) and its verdict (``ok``).
    """
    tool, k, eps = cell.tool, cell.k, cell.eps
    gaps, over, bad = [], [], 0
    for s in solves:
        g = s.get("inst", inst)
        part = np.asarray(s["part"])
        s["recount"], s["ok"] = None, False
        if R.bad_labels(part, len(g.vwgt), k):
            bad += R.bad_labels(part, len(g.vwgt), k)
            continue
        s["recount"] = tool.objective(g, part)
        gaps.append(abs(int(s["objective"]) - s["recount"]))
        over.append(R.overweight(g.vwgt, part, k, eps))
        s["ok"] = gaps[-1] == 0 and over[-1] == 0
    return {
        "solves": {"value": len(solves), "limit": 1},
        "bad_labels": {"value": int(bad), "limit": 0},
        "objective_gap": {"value": max(gaps, default=0), "limit": 0},
        "overweight": {"value": max(over, default=0.0), "limit": 0.0},
    }


def checks_pass(checks: Dict[str, dict]) -> bool:
    """``solves`` is a floor; every other check is a ceiling."""
    ok = checks["solves"]["value"] >= checks["solves"]["limit"]
    return ok and all(c["value"] <= c["limit"] for name, c in checks.items()
                      if name != "solves")


def end_to_end(cell: Cell, window: Window, setup_s: float) -> dict:
    """The cell's end-to-end metrics from a checked, untraced window: the
    objective is each job's recount averaged over the window's jobs.  A
    metric's name up to its first dot says which quantity it is, so cells
    that need bounds of their own report ``solve_s.<kind>``."""
    per_job: Dict[int, List[int]] = {}
    for s in window.solves:
        if s["recount"] is not None:
            per_job.setdefault(s["seed"], []).append(s["recount"])
    values = {
        "solve_s": window.seconds / max(len(window.solves), 1),
        "objective": float(np.mean([np.mean(v) for v in per_job.values()]))
        if per_job else None,
        "setup_s": setup_s,
    }
    out = {}
    for m in cell.end_to_end:
        v = values.get(m["name"].split(".")[0])
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def per_layer(cell: Cell, window: Window, peaks: Optional[dict]) -> dict:
    """The cell's per-layer metrics: each reader by its name; a reader
    that finds nothing returns None and its metric is left out."""
    ctx = SimpleNamespace(events=window.events,
                          n_solves=max(len(window.solves), 1),
                          compiles=window.compiles, fresh=window.fresh,
                          trace=window.trace, peaks=peaks,
                          kernel_cost=lambda name: load_module(
                              cell.data / "kernels" / f"{name}.py"))
    out = {}
    for m in cell.per_layer:
        v = cell.metric_reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             clock0: float, device: Callable[[], dict],
             reduce_trace: Optional[Callable] = None,
             peaks: Optional[dict] = None, log=sys.stderr) -> dict:
    """One run → the result line's object (without printing it).

    ``clock0`` is when the process started (``time.perf_counter``);
    ``device()`` stamps the device and its peak bytes; ``reduce_trace``
    turns the profiler's directory into the trace summary that the
    per-layer readers take."""
    inst, prepared = setup(cell)
    setup_s = time.perf_counter() - clock0
    print(f"setup_s {setup_s!r} compiles {compiles_so_far()} cache_loads "
          f"{cache_loads_so_far()} compile_s {compile_seconds_so_far()!r}",
          file=log)
    with tempfile.TemporaryDirectory(prefix="chipbench_trace_") as tdir:
        window = measure(cell, prepared, seed, seconds, trace, tdir)
        dev = device()
        if trace:
            window.trace = reduce_trace(tdir, window) if reduce_trace \
                else None
    for j, s in enumerate(window.solves):
        print(f"solve {j} seed {s['seed']} s {s['seconds']!r} "
              f"cpu_s {s['cpu_s']!r} objective {s['objective']}", file=log)
    print(f"window_s {window.seconds!r} solves {len(window.solves)} "
          f"compiles_in_window {window.compiles}", file=log)
    del prepared
    checked = list(window.solves)
    if trace:
        window.fresh = fresh_solve(cell, seed)
        checked.append(window.fresh)
        print(f"fresh job {window.fresh['seed']} s "
              f"{window.fresh['seconds']!r} compiles "
              f"{window.fresh['compiles']} cache_loads "
              f"{window.fresh['loads']} compile_s "
              f"{window.fresh['compile_s']!r}", file=log)
    checks = check_window(cell, inst, checked)
    if trace:
        metrics = per_layer(cell, window, peaks)
        dev.update(busy_s=window.trace["busy_s"],
                   window_s=window.trace["window_s"])
    else:
        metrics = end_to_end(cell, window, setup_s)
    out = {"correct": checks_pass(checks), "attempted": len(checked),
           "failed": sum(not s["ok"] for s in checked),
           "metrics": metrics, "device": dev}
    if trace:
        out["breakdown"] = window.trace["breakdown"]
    out["checks"] = checks
    return out

