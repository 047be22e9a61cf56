"""Readings shared by several per-layer metric readers (`bench/metrics/`).

Each takes the run's `Context` (see `bench/run.py`) and returns a float,
or None where the run holds nothing to read.
"""

from __future__ import annotations

# Module name of the engine's jitted mapping pass in the device trace.
MAPPING = ("build_one",)


def module_seconds(ctx, names) -> tuple[int, float]:
    """(runs started in the window, device seconds in it) of the modules."""
    runs, secs = 0, 0.0
    for name, m in (ctx.trace or {}).get("modules", {}).items():
        if name in names:
            runs += m["runs"]
            secs += m["seconds"]
    return runs, secs


def mapping_ms(ctx):
    """Device milliseconds of the mapping program per scene it mapped."""
    runs, secs = module_seconds(ctx, MAPPING)
    return secs / runs * 1e3 if runs else None


def idle_share(ctx):
    """Percent of the traced window in which no operation ran, averaged
    over the chips."""
    if ctx.trace is None:
        return None
    busy = sum(ctx.trace["busy_s"]) / len(ctx.trace["busy_s"])
    return 100.0 * (1.0 - busy / ctx.trace["window_s"])

