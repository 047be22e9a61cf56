"""Benchmark harness entry point: one module per paper table/figure.

  bench_mapping   Fig. 17-left   sort-merge vs hash kernel mapping
  bench_convflow  Fig. 17-right  Gather-MatMul-Scatter vs Fetch-on-Demand
  bench_cache     Fig. 18/19     MMU configurable cache: miss rate / DRAM
  bench_fusion    Fig. 20        temporal layer fusion DRAM reduction
  bench_models    Figs. 13/14/16 the 8 paper networks + co-design point
  bench_serve     beyond-paper   pipelined serve hot loop vs synchronous
  bench_moe       beyond-paper   PointAcc dispatch on MoE routing

Prints ``name,us_per_call,derived`` CSV and (with --json, default
BENCH_models.json under --smoke) dumps every row as JSON so CI can archive
the perf trajectory.  Roofline terms come from the dry-run (see
launch/dryrun.py + roofline_table.py), not from here — this container has
no TPU to time.
"""

import argparse
import inspect
import sys
import traceback

from benchmarks.common import dump_json, header


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small sizes everywhere (CI)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="dump rows as JSON (default BENCH_models.json "
                         "with --smoke)")
    args = ap.parse_args(argv)
    json_path = args.json or ("BENCH_models.json" if args.smoke else None)

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    header()
    from benchmarks import (bench_cache, bench_convflow, bench_fusion,
                            bench_mapping, bench_models, bench_moe,
                            bench_serve)
    failed = []
    for mod in (bench_mapping, bench_convflow, bench_cache, bench_fusion,
                bench_models, bench_serve, bench_moe):
        takes_argv = "argv" in inspect.signature(mod.main).parameters
        try:
            if takes_argv:
                mod.main(["--smoke"] if args.smoke else [])
            else:
                mod.main()
        except Exception:
            failed.append(mod.__name__)
            traceback.print_exc()
    if json_path:
        dump_json(json_path)
        print(f"wrote {json_path}", file=sys.stderr)
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
