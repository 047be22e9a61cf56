#!/usr/bin/env python3
"""Readings that set the limit of `correct` (run by hand on the chip; the
benchmark's runs never call it).

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 20

For each seed it serves one short window of the cell's mix at its own
load, samples the served scenes as a run samples them, and compares,
against the float32 reference at the configuration's matmul precision,
the widest gap (`reference.gaps`) of:

  program   the served class ids;
  bf16      the reference computed in bfloat16 (the next precision
            below the configuration's float32), put in the program's
            place: the gap of the class it ranks first;
  altered   the served ids shifted by one class, the fault "an answer
            altered where it is produced".

`*_disagree` is the share of rows whose class is not the reference's.

Each seed prints one JSON line; `--out` also writes them all to a file.
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

CONTROLS = {"bf16": {"dtype": "bfloat16"}}


def readings(name: str, seeds, seconds: float, on_chip: bool = True,
             root: Path = R.ROOT):
    from bench import reference as REF
    from bench.weights import make_params
    cell = R.load_cell(name, root)
    cfg = cell.cfg
    devs = R.open_devices(cell, on_chip, root)
    clock = R.CompileClock()
    params = make_params(cfg, int(cfg["weights_seed"]), devs[0])
    engine, sched = R.make_server(cell, devs, params)
    n = int(cell.traffic["sample"])
    for seed in seeds:
        stream, _ = R.make_traffic(cell, seed)
        if clock.count == 0:
            R.warm_up(sched, stream, R.buckets(engine, stream),
                      cell.traffic.get("warm_batch_sizes",
                                       [sched.max_batch]), clock)
        win = R.window(cell, sched, stream, seconds, [])
        sched.flush()
        LG.collect(sched, win)
        pick = R.pick_sample(win, seed, n)
        line = {"seed": seed, "scenes": []}
        for q in pick:
            ref = R.reference_logits(cfg, stream, params, q)
            got = {k: REF.gaps(ref, R.reference_logits(
                cfg, stream, params, q, **kw).argmax(-1))
                for k, kw in CONTROLS.items()}
            got["program"] = REF.gaps(ref, q.preds)
            got["altered"] = REF.gaps(ref, (q.preds + 1) % cfg["n_classes"])
            line["scenes"].append({
                "r": q.r, "rows": len(ref),
                **{k: float(v.max()) for k, v in got.items()},
                **{k + "_disagree": float((v > 0).mean())
                   for k, v in got.items()}})
        for k in line["scenes"][0]:
            if k not in ("r", "rows") and not k.endswith("_disagree"):
                line[k] = max(sc[k] for sc in line["scenes"])
        print(json.dumps(line), flush=True)
        yield line
    sched.close()

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    lines = list(readings(args.workload, seeds, args.seconds))
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(lines, indent=1))
    return 0

if __name__ == "__main__":
    sys.exit(main())
