#!/usr/bin/env python3
"""Span-tree decomposition of one traced window of a cell (run by hand on
the chip; the benchmark's runs never call it).

    python bench/spans.py --workload mini-minkunet-kitti.stream \\
        --seed 5 --seconds 51 [--out chiprun_out/spans.json]

Set-up as `bench/run.py` makes it (weights, scene pool, engine and
scheduler, warm-up), then one window of the cell's mix under the JAX
profiler and the scheduler's span tracer.  The tracer anchors its clock in
the profiler trace (`SpanTracer.anchor`, a host event named `obs.clock`)
just after the trace starts and just before it stops, so the program's
spans (monotonic seconds) can be laid over the device trace (ns).  Prints
one JSON line:

  latency_p50_ms    of the traced window; against an untraced
                    `bench/run.py` run on the same seed, the cost of
                    tracing;
  readings          medians over the window's requests of the spans
                    `lock_wait`, `mapping` (scenes that missed the mapping
                    cache), `stack_levels` (one per micro-batch),
                    `device_wait` and `result_wait`, in ms; and
                    `idle_host_share`, the percent of the window in which
                    a chip is idle while one of the work spans
                    (`WORK_SPANS`) is open, averaged over the chips;
  uncovered_share   max and median over ok requests of the share of the
                    root that its direct children leave uncovered;
  dispatches        micro-batches by trigger and dispatching thread;
  idle_gaps         chip 0's longest idle gaps, each charged to the
                    program span open at its midpoint;
  clock             the first anchor's offset and the drift between the
                    two anchors' offsets, in ns;
  modules           device runs and seconds of each program in the window.
"""

from __future__ import annotations

import argparse
import collections
import glob
import json
import statistics
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import loadgen as LG  # noqa: E402
from bench import run as R  # noqa: E402
from bench import trace_reduce as TR  # noqa: E402

ANCHOR = "obs.clock"
# Spans in which the host works for a request, rather than waits.
WORK_SPANS = ("admission", "mapping", "stack_levels", "launch")


def log(msg: str) -> None:
    print(f"spans: {msg}", file=sys.stderr, flush=True)


# -- the program's spans on the trace's clock ----------------------------

class Clock:
    """Monotonic seconds of the program's spans -> trace ns, by the offset
    of the first `obs.clock` anchor."""

    def __init__(self, offset_ns: float, drift_ns: float | None):
        self.offset_ns = offset_ns
        self.drift_ns = drift_ns    # last anchor's offset - the first's

    def ns(self, t_s: float) -> float:
        return t_s * 1e9 + self.offset_ns


def host_events(profile, name: str) -> list[tuple[float, float]]:
    """[start, end) (ns) of the host events named `name`, in time order."""
    out = []
    for p in profile.planes:
        if p.name == TR.HOST_PLANE:
            for line in p.lines:
                out.extend((e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events if e.name == name)
    return sorted(out)


def clock(profile, anchors) -> Clock | None:
    """The mapping from the tracer's clock to the trace's.  `anchors` are
    `SpanTracer.anchors`: (name, ns before, ns inside the annotation);
    the i-th `obs.clock` host event of the trace is the i-th anchor, and
    it started between the two stamps, taken as their midpoint.  Logs the
    drift between the first and the last offset.  None when the trace and
    the tracer do not hold the same number of anchors."""
    events = host_events(profile, ANCHOR)
    mine = [(b, i) for n, b, i in anchors if n == ANCHOR]
    if not events or len(events) != len(mine):
        return None
    offsets = [s - (b + i) / 2 for (s, _), (b, i) in zip(events, mine)]
    drift = offsets[-1] - offsets[0] if len(offsets) > 1 else None
    log(f"clock anchors={len(offsets)} offset_ns={offsets[0]!r} "
        f"drift_ns={drift!r} "
        f"stamp_width_ns={max(i - b for b, i in mine)!r}")
    return Clock(offsets[0], drift)


def device_idle(profile, seconds: float | None = None) -> dict | None:
    """The window as `trace_reduce.reduce_trace` takes it, [t0, t1) in
    trace ns, each chip's idle stretches in it (`idle`, in chip order) and
    the benchmark's own `bench.*` annotations (name, start, end); None
    when the trace holds no `bench.window` annotation or no chip."""
    annotations = []
    for p in profile.planes:
        if p.name == TR.HOST_PLANE:
            for line in p.lines:
                annotations.extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events if e.name.startswith("bench."))
    windows = [(s, e) for n, s, e in annotations if n == TR.WINDOW]
    devices = sorted(
        ((int(TR.DEVICE_PLANE.match(p.name).group(1)), p)
         for p in profile.planes if TR.DEVICE_PLANE.match(p.name)),
        key=lambda x: x[0])
    if not windows or not devices:
        return None
    t0, t1 = windows[0]
    if seconds is not None:
        t1 = t0 + seconds * 1e9
    idle = [TR.gaps([(s, e) for _, s, e in TR.events(p, TR.OPS_LINE)],
                    t0, t1) for _, p in devices]
    return {"t0": t0, "t1": t1, "idle": idle, "annotations": annotations}


def merged(intervals) -> list[tuple[float, float]]:
    """The union of [start, end) intervals as sorted disjoint ones."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def overlap_ns(a, b) -> float:
    """Length of the intersection of two sets of sorted disjoint
    intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


# -- readings of the window's span trees ----------------------------------

def spans(traces, name: str) -> list:
    """Finished spans named `name` in the span trees `traces`."""
    return [s for tr in traces for s in tr.find(name)
            if s.t_end is not None]


def span_median_ms(traces, name: str, keep=None) -> float | None:
    """Median duration, in ms, of the `name` spans that `keep` accepts."""
    d = [s.t_end - s.t_start for s in spans(traces, name)
         if keep is None or keep(s)]
    return statistics.median(d) * 1e3 if d else None


def stack_ms(traces) -> float | None:
    """Median, in ms, over micro-batches (one per `dispatch_id`) of the
    stacking of their scenes' pyramids."""
    per = {}
    for s in spans(traces, "stack_levels"):
        per.setdefault(s.attrs.get("dispatch_id"), s.t_end - s.t_start)
    return statistics.median(per.values()) * 1e3 if per else None


def idle_host_share(traces, clk: Clock | None,
                    dev: dict | None) -> float | None:
    """Percent of the window in which a chip is idle while at least one
    work span is open, averaged over the chips."""
    if dev is None or clk is None:
        return None
    work = [(max(clk.ns(s.t_start), dev["t0"]),
             min(clk.ns(s.t_end), dev["t1"]))
            for name in WORK_SPANS for s in spans(traces, name)]
    if not work:
        return None
    work = merged(work)
    window = dev["t1"] - dev["t0"]
    return 100.0 * sum(overlap_ns(work, idle) for idle in dev["idle"]) \
        / len(dev["idle"]) / window


def readings(traces, clk: Clock | None, dev: dict | None) -> dict:
    """The window's per-request decomposition (see the module
    docstring); a value is None where nothing was recorded."""
    return {
        "lock_wait_ms": span_median_ms(traces, "lock_wait"),
        "mapping_wall_ms": span_median_ms(
            traces, "mapping", lambda s: not s.attrs.get("cache_hit")),
        "stack_ms": stack_ms(traces),
        "device_wait_ms": span_median_ms(traces, "device_wait"),
        "result_wait_ms": span_median_ms(traces, "result_wait"),
        "idle_host_share": idle_host_share(traces, clk, dev),
    }


def uncovered_share(trace) -> float | None:
    """Share of a closed request root not covered by the union of its
    direct children."""
    root = trace.spans.get(trace.root_id)
    if root is None or root.t_end is None or root.t_end <= root.t_start:
        return None
    kids = [(max(s.t_start, root.t_start), min(s.t_end, root.t_end))
            for s in trace.span_list()
            if s.parent_id == root.span_id and s.t_end is not None]
    covered = sum(e - s for s, e in merged(kids))
    return 1.0 - covered / (root.t_end - root.t_start)


def charged_gaps(dev: dict, clk: Clock | None, traces) -> list:
    """Chip 0's longest idle gaps of the window, each charged to the
    program span open at its midpoint: a work span (`WORK_SPANS`) before
    a waiting one, the shortest among equals, with the trigger and thread
    of its micro-batch where it has one; the benchmark's own `bench.*`
    label where no span is open or there is no clock."""
    open_spans = []
    for tr in traces if clk is not None else ():
        for s in tr.span_list():
            if s.parent_id is None or s.t_end is None or s.t_end <= s.t_start:
                continue
            up, where = s, ""
            while up is not None and up.name != "dispatch":
                up = tr.spans.get(up.parent_id)
            if up is not None and "thread" in up.attrs:
                where = f" [{up.attrs['trigger']} on {up.attrs['thread']}]"
            open_spans.append((clk.ns(s.t_start), clk.ns(s.t_end),
                               s.name not in WORK_SPANS, s.name + where))
    out = []
    for s, e in sorted(dev["idle"][0], key=lambda g: g[0] - g[1])[:TR.TOP]:
        mid = (s + e) / 2
        cover = [c for c in open_spans if c[0] <= mid < c[1]]
        label = min(cover, key=lambda c: (c[2], c[1] - c[0]))[3] if cover \
            else TR._label(dev["annotations"], mid)
        out.append([label, (e - s) * 1e-9])
    return out


def dispatches(traces) -> dict:
    """Micro-batches of the window by trigger and dispatching thread."""
    by = {}
    for t in traces:
        for d in t.find("dispatch"):
            by[d.attrs.get("dispatch_id")] = \
                f"{d.attrs.get('trigger')} on {d.attrs.get('thread')}"
    return dict(sorted(collections.Counter(by.values()).items()))


def window_traces(tracer, win: LG.Window) -> list:
    """The scheduler's span trees of the requests sent in the window."""
    out = []
    for rid, q in win.requests.items():
        tr = tracer.get(f"scheduler:rid:{rid}")
        if tr is not None and win.in_window(q.sent):
            out.append(tr)
    return out


# -- one traced window ----------------------------------------------------

def run(name: str, seed: int, seconds: float, *, root: Path = R.ROOT,
        on_chip: bool = True) -> dict:
    """One traced window of cell `name`; returns the line's object."""
    cell = R.load_cell(name, root)
    devs = R.open_devices(cell, on_chip, root)
    import jax

    from bench.weights import make_params
    from repro.obs import Observability

    compiles = R.CompileClock()
    params = make_params(cell.cfg, int(cell.cfg["weights_seed"]), devs[0])
    stream, _ = R.make_traffic(cell, seed)
    obs = Observability.enabled(max_finished=1 << 16)
    engine, sched = R.make_server(cell, devs, params, obs)
    R.warm_up(sched, stream, R.buckets(engine, stream),
              cell.traffic.get("warm_batch_sizes", [sched.max_batch]),
              compiles)

    profile_dir = tempfile.mkdtemp(prefix="bench-spans-")
    marks = {}

    def on_open():
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(profile_dir, profiler_options=opts)
        obs.tracer.anchor(ANCHOR)
        # made once the trace runs: one made before records nothing
        marks["note"] = jax.profiler.TraceAnnotation(TR.WINDOW)
        marks["note"].__enter__()

    def on_close():
        marks["note"].__exit__(None, None, None)

    try:
        win = R.window(cell, sched, stream, seconds, [], on_open=on_open,
                       on_close=on_close)
        obs.tracer.anchor(ANCHOR)
        jax.profiler.stop_trace()
        traces = window_traces(obs.tracer, win)
        files = glob.glob(f"{profile_dir}/**/*.xplane.pb", recursive=True)
        prof = TR.load(files[0]) if files else None
    finally:
        sched.close()               # joins the max_wait_s watchdog
        R._rmtree(profile_dir)
    w_compile_s, w_compiles = compiles.lap()
    log(f"window: compiles={w_compiles} compile_s={w_compile_s!r}")

    clk = clock(prof, obs.tracer.anchors) if prof is not None else None
    dev = device_idle(prof, seconds) if prof is not None else None
    red = TR.reduce_trace(prof, seconds) if prof is not None else None
    shares = sorted(x for x in (
        uncovered_share(t) for t in traces
        if t.spans[t.root_id].attrs.get("outcome") == "ok")
        if x is not None)
    return {
        "workload": name, "seed": seed,
        "requests": len(traces),
        "failed": sum(1 for q in win.due_in_window() if not q.ok),
        "window_compiles": w_compiles,
        "latency_p50_ms": LG.quantile(LG.latency_ms(win), 0.5),
        "readings": readings(traces, clk, dev),
        "uncovered_share": {
            "ok_requests": len(shares),
            "max": shares[-1] if shares else None,
            "median": statistics.median(shares) if shares else None},
        "dispatches": dispatches(traces),
        "idle_gaps": charged_gaps(dev, clk, traces) if dev else None,
        "clock": None if clk is None else {"offset_ns": clk.offset_ns,
                                           "drift_ns": clk.drift_ns},
        "modules": red["modules"] if red else None,
        "device": {"platform": devs[0].platform,
                   "kind": devs[0].device_kind, "count": len(devs)},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    try:
        out = run(args.workload, args.seed, args.seconds)
    except Exception as e:  # noqa: BLE001 — every failure: exit 1, no line
        import traceback
        traceback.print_exc()
        log(f"FAIL: {type(e).__name__}: {e}")
        return 1
    line = json.dumps(out)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        with open(args.out, "a") as f:
            f.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
