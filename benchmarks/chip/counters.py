"""Counter arithmetic the per-layer readers in ``metrics/`` share: the
engine's counters are ``ph: "C"`` events of the window's recorder, each
with the ``value`` it added."""
from __future__ import annotations


def counter_total(events, name: str):
    """Sum of the ``name`` counter's increments, or None where there is
    none."""
    vals = [ev["value"] for ev in events
            if ev.get("ph") == "C" and ev.get("name") == name]
    return sum(vals) if vals else None


def wasted_rounds(events, prefix: str):
    """Share of the round program's row-rounds that moved no vertex, in %:
    100 × (1 − ``<prefix>rounds_moved`` / ``<prefix>rounds``), or None
    where either counter is missing or no round ran."""
    ran = counter_total(events, prefix + "rounds")
    moved = counter_total(events, prefix + "rounds_moved")
    if not ran or moved is None:
        return None
    return 100.0 * (1.0 - moved / ran)
