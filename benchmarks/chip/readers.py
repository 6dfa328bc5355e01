"""What the per-layer readers in ``metrics/`` take from a run: each
reader is one file named as its metric, and metrics that read the same
quantity in cells that report different end-to-end metrics (``.graph``,
``.hypergraph``) share these functions.  ``span_seconds`` is a copy of
``benchmarks/common.span_seconds``: every matched begin/end pair of the
name is summed; timestamps are microseconds."""
from __future__ import annotations


def span_seconds(events, name: str):
    """Total seconds inside ``name`` spans, or None where there is none."""
    total, stack, seen = 0.0, [], False
    for ev in events:
        if ev.get("name") != name:
            continue
        if ev.get("ph") == "B":
            stack.append(ev["ts"])
        elif ev.get("ph") == "E" and stack:
            total += ev["ts"] - stack.pop()
            seen = True
    return total / 1e6 if seen else None


def per_solve_span(ctx, span: str):
    """Seconds per solve inside the engine's ``span`` spans, or None."""
    s = span_seconds(ctx.events, span)
    return None if s is None else s / ctx.n_solves


def device_idle(ctx):
    """Share of the traced window in which no operation ran on the device
    (chips averaged), in %, or None without a trace."""
    t = ctx.trace
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def fresh_job(ctx, key: str):
    """``compiles`` or ``compile_s`` of the traced run's job outside the
    pool, or None where the run made none."""
    return None if ctx.fresh is None else ctx.fresh[key]
