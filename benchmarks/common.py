"""Shared benchmark helpers: timing, run metadata + CSV emission.

Every bench prints ``name,us_per_call,derived`` rows (derived = the
paper-relevant quality metric: cut, replication, QAP, fill-in, …) and
stamps its JSON report with ``run_metadata()`` so BENCH_*.json artifacts
record which jax/backend/host produced them.
"""
from __future__ import annotations

import time


def _block(out):
    """Wait for any async device work hiding in ``out`` (pytree-safe).

    JAX dispatch is asynchronous: without this, a timed region can stop
    the clock while the device is still computing.  Works on arbitrary
    pytrees and is a no-op for host values (numpy arrays, scalars).
    """
    try:
        import jax
        jax.block_until_ready(out)
    except Exception:
        pass
    return out


def timed(fn, *args, repeat: int = 1, **kw):
    """Run ``fn`` ``repeat`` times, blocking on the result each time, and
    return ``(last_out, mean_microseconds)``."""
    t0 = time.perf_counter()
    out = None
    for _ in range(repeat):
        out = _block(fn(*args, **kw))
    dt = (time.perf_counter() - t0) / repeat
    return out, dt * 1e6


def timed_call(fn, *args, **kw):
    """Single synchronized call → ``(out, seconds)``."""
    t0 = time.perf_counter()
    out = _block(fn(*args, **kw))
    return out, time.perf_counter() - t0


def peak_rss_mb() -> float:
    """Peak resident set size of this process in MiB (VmHWM), or 0.0 when
    /proc is unavailable — the host-memory column of the scale benches."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmHWM:"):
                    return round(int(line.split()[1]) / 1024.0, 1)
    except OSError:
        pass
    return 0.0


def span_seconds(events, name: str) -> float:
    """Total seconds spent inside ``name`` spans of an ``obs.Recorder``
    event list (sums every matched B→E pair; ts is µs)."""
    total, stack = 0.0, []
    for ev in events:
        if ev.get("name") != name:
            continue
        if ev.get("ph") == "B":
            stack.append(ev["ts"])
        elif ev.get("ph") == "E" and stack:
            total += ev["ts"] - stack.pop()
    return total / 1e6


def run_metadata() -> dict:
    """Environment stamp for BENCH_*.json reports (DESIGN.md §11): host,
    toolchain and the device every number in the report was measured on."""
    import datetime
    import platform
    import socket

    import jax
    import jaxlib
    dev = jax.devices()[0]
    return {
        "hostname": socket.gethostname(),
        "python": platform.python_version(),
        "timestamp_utc": datetime.datetime.now(
            datetime.timezone.utc).isoformat(timespec="seconds"),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": jax.device_count(),
    }


def row(name: str, us: float, derived) -> str:
    line = f"{name},{us:.0f},{derived}"
    print(line, flush=True)
    return line
