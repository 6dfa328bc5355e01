"""The profiler's trace of a window, reduced to the numbers the per-layer
metrics and the breakdown read.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes; it is read
with ``jax.profiler.ProfileData`` alone.  On a TPU each chip is a plane
``/device:TPU:<i>`` whose line ``XLA Ops`` holds one event per operation
run (the event's name is the HLO instruction's text, shapes included) and
whose line ``XLA Modules`` holds one event per program run.  The host's
plane ``/host:CPU`` holds the annotations the benchmark and the engine's
spans wrote (``TraceAnnotation``).  All share one clock in nanoseconds.

- the window runs from the first ``solve`` annotation's start to the last
  one's end;
- busy time is the union of the intervals of a chip's ``XLA Ops`` events
  inside the window, averaged over the chips;
- each Pallas kernel (a ``tpu_custom_call``) is kept with its operand and
  result shapes, its calls and its summed device time;
- each idle gap of chip 0 is labelled by the innermost host span open at
  its middle.
"""
from __future__ import annotations

import bisect
import json
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

HERE = Path(__file__).resolve().parent

#: annotation the benchmark writes around each solve of the window
SOLVE = "solve"
TOP = 10

_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
             "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
             "u64": 8}
_SHAPE = re.compile(r"\b(pred|[fsu]\d+|bf16)\[([\d,]*)\]")
_CUSTOM = re.compile(r"^%([A-Za-z_][\w\-]*?)(?:\.\d+)? = (.*?) "
                     r"custom-call\((.*)\), custom_call_target="
                     r"\"tpu_custom_call\"")
_PROGRAM = re.compile(r"^(.*?)(\(\d+\))?$")

Shape = Tuple[str, Tuple[int, ...]]


def load_peaks(device_kind: str, path: Path = HERE / "peaks.json") -> dict:
    """The published peaks of ``device_kind``; a chip not in the table is
    an error, never a default."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in {path} (have {sorted(table)})")
    return table[device_kind]


def shapes(text: str) -> List[Shape]:
    """Every array shape written in a piece of HLO text, in order."""
    return [(dt, tuple(int(d) for d in dims.split(",") if d))
            for dt, dims in _SHAPE.findall(text)]


def nbytes(shape: Shape) -> int:
    n = _ITEMSIZE[shape[0]]
    for d in shape[1]:
        n *= d
    return n


def parse_kernel(op: str) -> Optional[Tuple[str, List[Shape], List[Shape]]]:
    """``%lp_affinity.10 = f32[..] custom-call(s32[..] %a, ..), ...`` →
    (kernel name, result shapes, operand shapes); None for any other op."""
    m = _CUSTOM.match(op)
    if m is None:
        return None
    return m.group(1), shapes(m.group(2)), shapes(m.group(3))


def union_seconds(intervals: Iterable[Tuple[int, int]]) -> float:
    """Length of the union of [start, end) nanosecond intervals, in s."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total / 1e9


def gaps(intervals: List[Tuple[int, int]], lo: int, hi: int
         ) -> List[Tuple[int, int]]:
    """The parts of [lo, hi) that no interval covers."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def find_xplane(trace_dir) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _label_gaps(gap_list, spans) -> Dict[str, float]:
    """Seconds of idle gap by the innermost host span open at each gap's
    middle (``spans``: (start, end, name), any order)."""
    spans = sorted(spans)
    starts = [s for s, _, _ in spans]
    out: Dict[str, float] = defaultdict(float)
    for s, e in gap_list:
        mid = (s + e) // 2
        label = "none"
        # the latest-starting span still open at ``mid`` is the innermost
        for j in range(bisect.bisect_right(starts, mid) - 1, -1, -1):
            if spans[j][1] > mid:
                label = spans[j][2]
                break
        out[label] += (e - s) / 1e9
    return out


def reduce_profile(pd, span_names: Iterable[str]) -> dict:
    """One trace (a ``jax.profiler.ProfileData``) → the window's busy
    time, kernels and breakdown."""
    span_names = set(span_names) | {SOLVE}
    host_spans, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in span_names:
                        s = int(ev.start_ns)
                        host_spans.append((s, s + int(ev.duration_ns),
                                           ev.name))
        elif plane.name.startswith("/device:TPU:"):
            devices.append(plane)
    solves = [(s, e) for s, e, n in host_spans if n == SOLVE]
    if not solves or not devices:
        raise ValueError(f"no '{SOLVE}' annotation or no TPU plane in the "
                         "trace")
    lo = min(s for s, _ in solves)
    hi = max(e for _, e in solves)
    busy, programs = [], defaultdict(float)
    kernels: Dict[Tuple, List[float]] = {}
    chip0_ops: List[Tuple[int, int]] = []
    for i, plane in enumerate(sorted(devices, key=lambda p: p.name)):
        ops = []
        for line in plane.lines:
            if line.name not in ("XLA Ops", "XLA Modules"):
                continue
            for ev in line.events:
                s = int(ev.start_ns)
                e = s + int(ev.duration_ns)
                if e <= lo or s >= hi:
                    continue
                s, e = max(s, lo), min(e, hi)
                if line.name == "XLA Modules":
                    programs[_PROGRAM.match(ev.name).group(1)] += (
                        (e - s) / 1e9)
                    continue
                ops.append((s, e))
                k = parse_kernel(ev.name) if "tpu_custom_call" in ev.name \
                    else None
                if k is not None:
                    key = (k[0], tuple(k[1]), tuple(k[2]))
                    rec = kernels.setdefault(key, [0, 0.0])
                    rec[0] += 1
                    rec[1] += (e - s) / 1e9
        busy.append(union_seconds(ops))
        if i == 0:
            chip0_ops = ops
    window_s = (hi - lo) / 1e9
    by_kernel: Dict[str, float] = defaultdict(float)
    for (name, _, _), (_, sec) in kernels.items():
        by_kernel[name] += sec
    device_ops = sorted([(f"program:{p}", s) for p, s in programs.items()]
                        + [(f"kernel:{k}", s) for k, s in by_kernel.items()],
                        key=lambda x: -x[1])[:TOP]
    idle = _label_gaps(gaps(chip0_ops, lo, hi), host_spans)
    idle_gaps = sorted(idle.items(), key=lambda x: -x[1])[:TOP]
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": window_s,
        "chips": len(busy),
        "kernels": [{"name": name, "results": [[d, list(s)] for d, s in res],
                     "operands": [[d, list(s)] for d, s in opd],
                     "calls": c, "seconds": sec}
                    for (name, res, opd), (c, sec) in kernels.items()],
        "breakdown": {"device_ops": [list(x) for x in device_ops],
                      "idle_gaps": [list(x) for x in idle_gaps]},
    }


def reduce_trace(trace_dir, window) -> dict:
    """The harness's hook: the window's trace directory and its recorder
    events (whose span names label the idle gaps) → ``reduce_profile``."""
    from jax.profiler import ProfileData
    names = {ev["name"] for ev in window.events if ev.get("ph") == "B"}
    return reduce_profile(
        ProfileData.from_file(str(find_xplane(trace_dir))), names)


def roofline_share(trace: Optional[dict], kernel: str, cost, peaks: dict
                   ) -> Optional[float]:
    """Least time over measured device time of ``kernel``'s calls, in %.

    The least time of a call is its bytes (``cost.cost``) over the HBM
    bandwidth: the kernels' 32-bit vector arithmetic has no published
    peak.  None where the trace holds no call of it."""
    if not trace:
        return None
    least = spent = 0.0
    for k in trace["kernels"]:
        if k["name"] != kernel:
            continue
        res = [(d, tuple(s)) for d, s in k["results"]]
        opd = [(d, tuple(s)) for d, s in k["operands"]]
        least += cost.cost(res, opd) / peaks["hbm_bytes_per_s"] * k["calls"]
        spent += k["seconds"]
    if spent <= 0:
        return None
    return 100.0 * least / spent
