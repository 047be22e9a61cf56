#!/usr/bin/env python3
"""Offered-rate sweep of an open-loop cell, to find the highest rate the
tree sustains (run by hand on the chip; the benchmark's runs never call it).

    python bench/sweep.py --workload mini-minkunet-kitti.stream \\
        --rates 0.35,0.7 --seconds 51 --seed 5 [--max-wait 0.1,0.2]

One process, one set-up and warm-up; then, for each max_wait_s and each
rate, a window of the cell's mix at that Poisson rate on scenes drawn from
a seed of its own.  Each line reports the offered and completed rates,
the backlog left when the window closed (requests due in it and not yet
back), the latency median and 95th percentile (due -> result, requests
awaited after the window) and the generator's lag.  A rate is sustained
when completions keep up with arrivals and the backlog does not grow.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import loadgen as LG  # noqa: E402
from bench import run as R  # noqa: E402


def sweep(name: str, rates, seconds: float, seed: int, max_waits,
          on_chip: bool = True, root: Path = R.ROOT):
    cell = R.load_cell(name, root)
    devs = R.open_devices(cell, on_chip, root)
    from bench.weights import make_params
    clock = R.CompileClock()
    params = make_params(cell.cfg, int(cell.cfg["weights_seed"]), devs[0])
    for mw in max_waits:
        serving = dict(cell.traffic.get("serving", {}))
        if mw is not None:
            serving["max_wait_s"] = mw
        engine, sched = R.make_server(cell, devs, params, serving=serving)
        stream, _ = R.make_traffic(cell, seed)
        R.warm_up(sched, stream, R.buckets(engine, stream),
                  cell.traffic["warm_batch_sizes"], clock)
        for k, rate in enumerate(rates):
            s = seed + 1 + k
            stream, _ = R.make_traffic(cell, s)
            arrivals = dict(cell.traffic["arrivals"], rate_per_s=rate)
            lags = []
            win = R.window(cell, sched, stream, seconds, lags, arrivals)
            due = win.due_in_window()
            back = [q for q in due if q.done is not None and q.done < win.t1]
            lat = LG.latency_ms(win)
            line = {
                "max_wait_s": serving.get("max_wait_s"), "rate": rate,
                "scene_voxels": cell.traffic["scene_voxels"],
                "offered_per_s": len(due) / seconds,
                "completed_per_s": len(back) / seconds,
                "backlog_at_close": len(due) - len(back),
                "failed": sum(1 for q in due if not q.ok),
                "p50_ms": LG.quantile(lat, 0.5),
                "p95_ms": LG.quantile(lat, 0.95),
                "gen_lag_p95_ms": LG.quantile(lags, 0.95) * 1e3,
                "window_compiles": clock.lap()[1],
            }
            print(json.dumps(line), flush=True)
            yield line
        sched.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--max-wait", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    rates = [float(x) for x in args.rates.split(",")]
    waits = [float(x) for x in args.max_wait.split(",")] \
        if args.max_wait else [None]
    lines = list(sweep(args.workload, rates, args.seconds, args.seed, waits))
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(lines, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
