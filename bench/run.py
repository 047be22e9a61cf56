#!/usr/bin/env python3
"""Benchmark of the served point-cloud path on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (`bench/configs/`), a traffic mix
(`bench/traffic/<mix>.json`) and its chips, in `BENCHMARK.json`.  A run:

  1. refuses a host whose first JAX device is not a TPU, or that has
     fewer chips than the cell asks for: exit 1, no result line;
  2. set-up: JAX's persistent compile cache in `<checkout>/.jax_cache`,
     the configuration's weights made on the device in one call, a pool
     of base scenes from the seed with their work counted once
     (`bench.count`), the engine and scheduler at the program's defaults
     plus what the mix sets, and one micro-batch per bucket and per batch
     size the window will use;
  3. measures `--seconds` of open-loop traffic through
     `ServeScheduler.submit/poll` (`bench.loadgen`), counting backend
     compiles inside the window, and waits for the requests due in it;
  4. reads the peak device memory, frees the served program, and compares
     a sample of the served scenes, the largest among them, with the
     plain float32 reference at the configuration's matmul precision
     (`bench.reference`);
  5. prints its last line: one JSON object with `correct`, `attempted`,
     `failed`, `metrics`, `device`, `breakdown` (traced runs) and,
     last, `compared`: each number compared beside its limit.

With `--trace 1` the window runs under the JAX profiler and the span
tracer, and the metrics are the cell's per-layer ones, each read by its
own file `bench/metrics/<metric>.py` (`read(ctx) -> float | None`).
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE = ".jax_cache"
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import count as C  # noqa: E402
from bench import loadgen as LG  # noqa: E402
from bench import scenes as S  # noqa: E402
from bench.chip import CompileClock, NoChip, require_tpu  # noqa: E402


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell's configuration, traffic and metrics, found by name."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())

    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    return Cell(name, int(w["chips"]), cfg, traffic,
                [m for m in spec["end_to_end"] if mine(m)],
                [m for m in spec["per_layer"] if mine(m)])


def load_reader(metric: str, root: Path = ROOT):
    """`read(ctx)` of `bench/metrics/<metric>.py`."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader may read."""

    cell: Cell
    chips: int
    peaks: dict                 # {"flops_per_s", "bytes_per_s"} of the chip
    work: list                  # per base scene: {"flops", "bytes"}
    window: LG.Window           # the measured window's requests
    trace: dict | None          # trace_reduce.reduce_trace(...)
    queue_wait_s: list          # scheduler queue_wait spans of the window
    gen_lag_s: list             # how late each send ran behind its due time


def _peaks(kind: str, root: Path) -> dict:
    table = json.loads((root / "bench" / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table["devices"][kind]


def _queue_waits(tracer, win: LG.Window) -> list:
    """Durations of the scheduler's `queue_wait` spans of the window's
    requests (the tracer is on only in traced runs)."""
    if tracer is None:
        return []
    out = []
    for rid, q in win.requests.items():
        tr = tracer.get(f"scheduler:rid:{rid}")
        if tr is None or not win.in_window(q.sent):
            continue
        for s in tr.find("queue_wait"):
            if s.t_end is not None:
                out.append(s.t_end - s.t_start)
    return out


def warm_up(sched, stream, caps: dict, sizes, clock) -> None:
    """Run one micro-batch per bucket the pool uses and per batch size the
    mix will dispatch, with requests the window never sends."""
    r = -1
    for cap, bases in sorted(caps.items()):
        for k in sizes:
            rids = []
            for j in range(k):
                _, coords, feats = stream.request(r, bases[j % len(bases)])
                r -= 1
                rids.append(sched.submit(coords, feats))
            sched.flush()
            for res in sched.take(rids).values():
                if not res.ok:
                    raise RuntimeError(f"warm-up request failed: "
                                       f"{res.error}")
    s, n = clock.lap()
    log(f"warm-up: compile_s={s!r} compiles={n}")


def open_devices(cell: Cell, on_chip: bool, root: Path) -> list:
    """The cell's devices, the persistent compile cache and the program's
    import path.  `on_chip=False` (tests) skips the look for a TPU and the
    cache, and takes whatever devices JAX sees."""
    import jax
    if on_chip:
        devs = require_tpu(cell.chips)
    else:
        devs = jax.devices()[:cell.chips]
        if len(devs) < cell.chips:
            raise NoChip(f"need {cell.chips} devices, JAX sees "
                         f"{len(jax.devices())}")
    src = str(root / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    if on_chip:
        # The cache lives inside the checkout whatever the environment
        # says, at a fixed path (the path is part of what a later run must
        # match), with no size cap and no minimum compile time, so that
        # every program of a run is found again by the next one.
        jax.config.update("jax_compilation_cache_dir", str(root / CACHE))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        jax.config.update("jax_compilation_cache_max_size", -1)
        log(f"compile cache: {root / CACHE}")
    return devs


def make_traffic(cell: Cell, seed: int):
    """(request stream, per-base-scene work) of the cell's mix."""
    cfg, traffic = cell.cfg, cell.traffic
    n_stages = len(cfg["enc_planes"])
    lo, hi = traffic["scene_voxels"]
    pool = S.base_scenes(seed, S.pool_sizes(lo, hi, traffic["pool"]))
    work = [C.scene_work(cfg, C.level_counts(xyz, n_stages + 1))
            for xyz in pool]
    stream = S.RequestStream(seed, pool, cfg["c_in"],
                             traffic.get("schedule_seed"))
    return stream, work


def make_server(cell: Cell, devs, params, obs=None, serving=None):
    """(engine, scheduler) at the program's defaults plus the mix's
    settings; a four-chip cell serves over a scene mesh of its chips."""
    from repro.distributed import sharding as SH
    from repro.serve.engine import PointCloudEngine
    from repro.serve.scheduler import ServeScheduler

    serving = dict(cell.traffic.get("serving", {}) if serving is None
                   else serving)
    mesh = SH.make_scene_mesh(devices=devs) if len(devs) > 1 else None
    engine = PointCloudEngine(params, n_stages=len(cell.cfg["enc_planes"]),
                              mesh=mesh, max_batch=serving.get("max_batch"))
    kw = {} if obs is None else {"obs": obs}
    return engine, ServeScheduler(engine, mesh=mesh, **kw, **serving)


def buckets(engine, stream) -> dict:
    """{bucket capacity: base scenes that land in it}."""
    caps = {}
    for b, xyz in enumerate(stream.pool):
        caps.setdefault(engine.ladder.bucket_for(len(xyz)), []).append(b)
    return caps


def window(cell: Cell, sched, stream, seconds: float, lags: list,
           arrivals=None, **hooks) -> LG.Window:
    """One measured window of the cell's mix (`arrivals` overrides its
    own; `hooks` are the loop's on_open / on_close); the requests due in
    it are awaited after it."""
    traffic = cell.traffic
    if traffic["loop"] != "open":
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    due = LG.due_times(arrivals or traffic["arrivals"],
                       int(traffic["schedule_seed"]), seconds)
    win = LG.open_loop(sched, stream, due, seconds,
                       float(traffic.get("await_s", 60)), **hooks)
    lags.extend(max(0.0, q.sent - q.due) for q in win.due_in_window())
    return win


def run(name: str, seed: int, seconds: float, trace: bool, *,
        root: Path = ROOT, on_chip: bool = True) -> dict:
    """One run of cell `name`; returns the result line's object."""
    cell = load_cell(name, root)
    cfg, traffic = cell.cfg, cell.traffic
    readers = {m["name"]: load_reader(m["name"], root)
               for m in cell.per_layer} if trace else {}
    devs = open_devices(cell, on_chip, root)
    import jax

    from bench.weights import make_params
    from repro.obs import Observability

    clock = CompileClock()
    peaks = _peaks(devs[0].device_kind if on_chip else "TPU v5 lite", root)
    params = make_params(cfg, int(cfg["weights_seed"]), devs[0])
    stream, work = make_traffic(cell, seed)
    obs = Observability.enabled(max_finished=1 << 16) if trace else None
    engine, sched = make_server(cell, devs, params, obs)
    caps = buckets(engine, stream)
    log(f"cell {name}: seed={seed} chips={cell.chips} "
        f"pool={[len(x) for x in stream.pool]} buckets="
        f"{ {c: len(v) for c, v in caps.items()} } "
        f"useful_gflop_per_scene="
        f"{sum(w['flops'] for w in work) / len(work) / 1e9!r}")
    warm_up(sched, stream, caps,
            traffic.get("warm_batch_sizes", [sched.max_batch]), clock)

    profile_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    marks = {}

    def on_open():
        c_s, c_n = clock.lap()
        log(f"set-up compiles: {c_n} ({c_s!r} s)")
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(profile_dir, profiler_options=opts)
        marks["setup_s"] = time.monotonic() - T_START
        marks["note"] = jax.profiler.TraceAnnotation("bench.window")
        marks["note"].__enter__()

    def on_close():
        marks["note"].__exit__(None, None, None)

    lags = []
    win = window(cell, sched, stream, seconds, lags, on_open=on_open,
                 on_close=on_close)
    log("completions at (s from the window's start): " + json.dumps(sorted(
        round(q.done - win.t0, 3) for q in win.requests.values()
        if q.ok)))
    if trace:
        jax.profiler.stop_trace()
    setup_s = marks["setup_s"]
    log(f"setup_s={setup_s!r}")
    w_compile_s, w_compiles = clock.lap()
    log(f"window: compiles={w_compiles} compile_s={w_compile_s!r}"
        + (" WARM-UP FAULT: the window compiled" if w_compiles else ""))
    stats = sched.stats()
    log(f"scheduler: submitted={stats['n_submitted']} "
        f"completed={stats['n_completed']} ok={stats['n_ok']} "
        f"padding_overhead={stats['padding_overhead']!r} "
        f"buckets={json.dumps(stats['buckets'])}")
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    queue_waits = _queue_waits(obs.tracer if obs else None, win)
    sched.close()                   # joins the max_wait_s watchdog
    del sched, engine, obs
    jax.clear_caches()

    attempted = win.due_in_window()
    failed = [q for q in attempted if not q.ok]
    compared = compare(cfg, stream, params, win, seed,
                       int(traffic.get("sample", 3)))
    max_gap = compared["max_gap_rel"]["value"]
    correct = not failed and max_gap is not None and \
        max_gap <= compared["max_gap_rel"]["limit"]
    compared["failed"] = {"value": len(failed), "limit": 0}

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    out = {"correct": bool(correct), "attempted": len(attempted),
           "failed": len(failed), "metrics": None, "device": device}
    if not trace:
        out["metrics"] = end_to_end(cell, win, setup_s)
    else:
        from bench import trace_reduce as TR
        files = glob.glob(f"{profile_dir}/**/*.xplane.pb", recursive=True)
        red = TR.reduce_trace(TR.load(files[0]), seconds) if files else None
        _rmtree(profile_dir)
        ctx = Context(cell, len(devs), peaks, work, win, red, queue_waits,
                      lags)
        out["metrics"] = {}
        for m in cell.per_layer:
            v = readers[m["name"]](ctx)
            if v is not None:
                out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        if red is not None:
            device["busy_s"] = sum(red["busy_s"]) / len(red["busy_s"])
            device["window_s"] = red["window_s"]
            out["breakdown"] = {"device_ops": red["device_ops"],
                                "idle_gaps": red["idle_gaps"]}
    out["compared"] = compared
    return out


LATENCY_QUANTILES = {"latency_p50_ms": 0.50}


def end_to_end(cell: Cell, win: LG.Window, setup_s: float) -> dict:
    names = {m["name"]: m for m in cell.end_to_end}
    lat = LG.latency_ms(win)
    out = {k: LG.quantile(lat, q) for k, q in LATENCY_QUANTILES.items()}
    out["setup_s"] = setup_s
    return {k: {"value": v, "unit": names[k]["unit"]}
            for k, v in out.items() if k in names}


def pick_sample(win: LG.Window, seed: int, n: int) -> list:
    """Up to n requests that came back ok: the largest and the smallest
    scene, and the rest drawn from the seed.  With both ends in every
    sample, every run compiles the reference for the same size classes,
    so only the first run in a checkout pays for it."""
    import numpy as np
    done = sorted((q for q in win.requests.values() if q.ok),
                  key=lambda q: q.r)
    if not done:
        return []
    largest = max(done, key=lambda q: (len(q.preds), -q.r))
    smallest = min(done, key=lambda q: (len(q.preds), q.r))
    ends = [largest] + ([smallest] if smallest is not largest and n > 1
                        else [])
    rest = [q for q in done if all(q is not e for e in ends)]
    rng = np.random.default_rng(np.random.SeedSequence([seed, 7005]))
    return ends + [rest[i] for i in rng.choice(
        len(rest), max(0, min(n - len(ends), len(rest))), replace=False)]


def reference_logits(cfg, stream, params, q, **precision):
    """The reference's logits for request q's scene (`precision`: the
    dtype / mm_dtype of `reference.forward`)."""
    from bench import reference as R
    _, coords, feats = stream.request(q.r)
    geo = R.geometry(coords[:, 1:], len(cfg["enc_planes"]))
    return R.forward(params, geo, feats, cfg, **precision)


def compare(cfg, stream, params, win: LG.Window, seed: int,
            n_sample: int) -> dict:
    """Served class ids of a sample of finished requests against the
    reference at the configuration's precision: the widest gap by which a
    served class's reference logit lies below the row's best, as a share
    of the scene's largest |logit| (`reference.gaps`)."""
    from bench import reference as R
    limit = float(cfg["correct"]["max_gap_rel"])
    pick = pick_sample(win, seed, n_sample)
    if not pick:
        return {"max_gap_rel": {"value": None, "limit": limit}}
    t = time.monotonic()
    worst = 0.0
    for q in pick:
        g = float(R.gaps(reference_logits(cfg, stream, params, q),
                         q.preds).max())
        log(f"compared request {q.r} (base {q.base}, {len(q.preds)} rows): "
            f"max_gap_rel={g!r}")
        worst = max(worst, g)
    log(f"reference: {len(pick)} scenes in {time.monotonic() - t!r} s")
    return {"max_gap_rel": {"value": worst, "limit": limit}}


def _rmtree(path: str) -> None:
    import shutil
    shutil.rmtree(path, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except Exception as e:  # noqa: BLE001 — every failure: exit 1, no line
        import traceback
        traceback.print_exc()
        log(f"FAIL: {type(e).__name__}: {e}")
        return 1
    for k, v in out["compared"].items():
        log(f"compared {k}={v['value']!r} limit={v['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
