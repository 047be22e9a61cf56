"""Reduce a JAX profiler trace (`.xplane.pb`) to the benchmark's numbers.

Planes named `/device:TPU:<n>` are the chips.  On each, the `XLA Ops`
line holds one event per operation run and the `XLA Modules` line one
event per program run, named after the jitted function
(`jit_build_one(...)`: the served mapping pass; `jit_apply_one(...)`: the
served trunk).  The host plane `/host:CPU` holds the benchmark's own
annotations (`bench.window` around the measured window, `bench.submit`,
`bench.poll`, `bench.idle` around its calls), on the same clock.

`reduce_trace` gives, inside the window (from the start of the
`bench.window` annotation, `seconds` long, or to its end):
  - busy seconds per chip: the union of its op intervals;
  - per module: runs started in the window and device seconds in it;
  - per op, named `<module>/<op>`: device seconds (for the breakdown);
  - the longest idle gaps of chip 0, each labelled with the innermost
    `bench.*` annotation running at the gap's midpoint.
"""

from __future__ import annotations

import bisect
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
WINDOW = "bench.window"
TOP = 10


def module_name(event_name: str) -> str:
    """`jit_apply_one(123)` -> `apply_one`; other names unchanged."""
    name = event_name.split("(", 1)[0].strip()
    return name[4:] if name.startswith("jit_") else name


def op_name(event_name: str) -> str:
    """`%fusion.12 = f32[...] fusion(...)` -> `fusion.12`."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def union_seconds(intervals, t0: float, t1: float) -> float:
    """Length of the union of [start, end) intervals (ns), clipped to
    [t0, t1), in seconds."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, t0), min(e, t1)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total * 1e-9


def gaps(intervals, t0: float, t1: float) -> list[tuple[float, float]]:
    """Idle [start, end) stretches (ns) of [t0, t1) between intervals."""
    out, cur = [], t0
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, t1)))
        cur = max(cur, e)
        if cur >= t1:
            break
    if cur < t1:
        out.append((cur, t1))
    return [(s, e) for s, e in out if e > s]


def events(plane, line_name: str):
    for line in plane.lines:
        if line.name == line_name:
            for e in line.events:
                yield e.name, e.start_ns, e.start_ns + e.duration_ns


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def reduce_trace(profile, seconds: float | None = None) -> dict | None:
    """The numbers of one trace (see the module docstring); None when the
    trace holds no `bench.window` annotation or no chip."""
    planes = list(profile.planes)
    host = [p for p in planes if p.name == HOST_PLANE]
    annotations = []
    for p in host:
        for line in p.lines:
            for e in line.events:
                if e.name.startswith("bench."):
                    annotations.append((e.name, e.start_ns,
                                        e.start_ns + e.duration_ns))
    windows = [(s, e) for n, s, e in annotations if n == WINDOW]
    devices = sorted(((int(DEVICE_PLANE.match(p.name).group(1)), p)
                      for p in planes if DEVICE_PLANE.match(p.name)),
                     key=lambda x: x[0])
    if not windows or not devices:
        return None
    t0, t1 = windows[0]
    if seconds is not None:
        t1 = t0 + seconds * 1e9
    busy, modules, ops = [], {}, {}
    idle0 = []
    for i, plane in devices:
        runs = sorted((s, e, module_name(name))
                      for name, s, e in events(plane, MODULES_LINE))
        for s, e, name in runs:
            cs, ce = max(s, t0), min(e, t1)
            m = modules.setdefault(name, [0, 0.0])
            if t0 <= s < t1:
                m[0] += 1
            if ce > cs:
                m[1] += (ce - cs) * 1e-9
        starts = [r[0] for r in runs]
        spans = []
        for name, s, e in events(plane, OPS_LINE):
            spans.append((s, e))
            cs, ce = max(s, t0), min(e, t1)
            if ce > cs:
                k = bisect.bisect_right(starts, s) - 1
                owner = runs[k][2] + "/" if k >= 0 and s < runs[k][1] \
                    else ""
                key = owner + op_name(name)
                ops[key] = ops.get(key, 0.0) + (ce - cs) * 1e-9
        busy.append(union_seconds(spans, t0, t1))
        if i == devices[0][0]:
            idle0 = gaps(spans, t0, t1)
    longest = sorted(idle0, key=lambda g: g[0] - g[1])[:TOP]
    return {
        "window_s": (t1 - t0) * 1e-9,
        "busy_s": busy,
        "modules": {k: {"runs": v[0], "seconds": v[1]}
                    for k, v in modules.items()},
        "device_ops": sorted(([k, v] for k, v in ops.items()),
                             key=lambda kv: -kv[1])[:TOP],
        "idle_gaps": [[_label(annotations, (s + e) / 2), (e - s) * 1e-9]
                      for s, e in longest],
    }


def _label(annotations, t: float) -> str:
    """The innermost `bench.*` annotation (not the window) covering t."""
    best = None
    for name, s, e in annotations:
        if name != WINDOW and s <= t < e and \
                (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "no bench call"
