"""End-to-end driver (the paper's kind is an inference accelerator):
serve a heterogeneous stream of point-cloud segmentation requests through
Mini-MinkowskiUNet via the continuous-batching `ServeScheduler`.

Simulates a LiDAR stream with *varying point counts per scene* — the
realistic serving shape.  Each scene is admitted into the scheduler,
padded up to its capacity bucket (`serve.buckets.BucketLadder`), grouped
with bucket peers into fixed-shape micro-batches, and executed on the
engine's vmapped path (shard_map-sharded over a scene-axis mesh when the
host has several devices).  Compilations are bounded by the number of
buckets, not the number of distinct scene sizes; results drain
out-of-order with per-request latency + padding telemetry.

The Mapping Unit output (the ranked SortedCloud + every level's kernel
maps) depends only on the coordinates, so repeated geometry — a parked
scanner, multi-sweep aggregation, re-scored frames — is served from the
session's LRU digest-keyed MappingCache, per scene: batch composition can
change around a repeated scene and it still hits.  One level up, a
micro-batch whose ORDERED composition repeats (the stream replays a
whole batch) hits the composition-keyed AssemblyCache and skips the
stacking pass entirely; dispatch is asynchronous (double-buffered
in-flight slots), so assembling one micro-batch overlaps executing the
previous one.  `--min-hit-rate` turns the cache telemetry into a CI
assertion: the combined mapping+assembly hit rate of the stream must
reach the floor or the driver exits nonzero.

`--inject-faults` runs the same stream through a low-rate chaos plan
(`serve.faults.FaultPlan`: one transient dispatch failure, one
NaN-corrupted scene, plus one oversized scene appended to the stream) and
asserts the fault-tolerance contract: every request completes with
predictions or a typed error, the transient failure is retried (≥ 1
recorded retry, zero `exec_failed`), exactly the two bad scenes are
rejected, and no exception escapes the serve loop.  The failure counters
land in `--metrics-json` alongside the cache telemetry.

`--workers N` (N >= 1) serves the same stream through the multi-worker
`serve.router.ServeRouter` instead of a bare scheduler: each worker owns
its own engine + scheduler, and scenes are rendezvous-routed by geometry
digest so repeated geometry keeps hitting the worker that already cached
it.  `--kill-worker {auto|ORDINAL}` is the router chaos smoke: the
chosen worker (auto = the one the digests load most) is killed by an
injected fault on its second request, and the driver asserts the
failover contract — every request completes with predictions, >= 1
request was replayed onto a survivor, 0 requests lost.

`--partition` is the city-scale smoke: one `--points`-row scene (default
200000 — an order of magnitude past the top bucket) that the seed path
must reject with a typed `rejected`/`oversized` result, then complete
through `segment(partition='auto')` — octree-chunked over packed keys
with exact receptive-field halos (`repro.partition`), every chunk served
through the scheduler as an ordinary scene, 0 chunks rejected.  A
mid-size control scene is additionally served both monolithically and
force-chunked and must match exactly on every valid row (the halo-
exactness invariant as a CI assertion).  Partition telemetry (chunk
count, halo fraction, points/s) lands in `--metrics-json`.

`--storm` is the overload-control smoke: a single-bucket stream offered
at 2x the (chaos-throttled) service rate — `FaultPlan.storm_buckets`
paces the device to a deterministic batch rate — served through a
scheduler with the SLO-aware `OverloadController` attached
(`overload=`).  Every request carries `deadline_s = --slo-s`, priorities
alternate to exercise the EDF lanes, and the driver asserts the
overload contract: every request completes (conservation: submitted ==
ok + shed + timeout + rejected), zero exec_failed, >= 1 request shed
with a `retry_after_s` backpressure hint, and the p95 latency of the
requests that DID complete stays within the SLO — overload degrades
into typed sheds, never into blown latency for admitted work.  Stats
(including the controller's rate estimates, brownout level, and breaker
states) land in `--metrics-json`.

`--trace-out PATH` / `--prom-out PATH` switch on the observability
stack (`repro.obs`): every request gets a span tree (admission, queue
wait, assembly, device wait, retire — plus router hops, failover
replays, and partition chunk fan-out where applicable) and a bounded
flight recorder rides along, dumping its ring automatically on
exec_failed / failover / watchdog deadline flushes.  `--trace-out`
writes the span + dump stream as JSONL (validated against the schema
before exit — invalid output fails the run), `--prom-out` writes a
Prometheus text-exposition snapshot of the unified metrics registry.
Both work in all three modes (bare scheduler, --workers, --partition).

Run:  PYTHONPATH=src python examples/serve_pointcloud.py [--scenes 16]
      [--distinct-scenes 8] [--flow fod] [--max-batch 4]
      [--pipeline-depth 2] [--assembly-cache 16] [--max-wait-s T]
      [--min-hit-rate R] [--metrics-json serve_metrics.json]
      [--inject-faults] [--workers 3] [--kill-worker auto]
      [--partition --points 200000 --smoke]
      [--storm --scenes 72 --storm-rate 4 --slo-s 2.0]
      [--trace-out serve_trace.jsonl] [--prom-out serve_metrics.prom]
"""

import argparse
import json
import sys
import time

import numpy as np
import jax

from repro.compile_cache import enable_compile_cache
from repro.data.synthetic import city_scene, lidar_scene
from repro.models import minkunet as MU
from repro.serve.buckets import geometric_ladder
from repro.serve.engine import PointCloudEngine
from repro.serve.scheduler import ServeScheduler

N_STAGES = 2
SIZE_CYCLE = (384, 640, 900, 1400)     # heterogeneous point counts


def _build_obs(args):
    """Observability handle when --trace-out/--prom-out asked for one
    (tracer + flight recorder enabled); None keeps the serve stack on
    its always-on metrics-only default."""
    if args.trace_out or args.prom_out:
        from repro.obs import Observability
        return Observability.enabled()
    return None


def _export_obs(args, obs):
    """Write the requested exporter artifacts; exit nonzero if the
    JSONL trace stream fails its own schema validation."""
    if obs is None:
        return
    from repro.obs import (TraceSchemaError, validate_trace_jsonl,
                           write_prometheus, write_trace_jsonl)
    if args.trace_out:
        n = write_trace_jsonl(args.trace_out, obs.tracer,
                              recorder=obs.recorder)
        try:
            report = validate_trace_jsonl(args.trace_out)
        except TraceSchemaError as e:
            print(f"FAIL: {args.trace_out} failed trace-schema "
                  f"validation: {e}", file=sys.stderr)
            sys.exit(1)
        print(f"wrote {n} trace records to {args.trace_out} "
              f"({report['traces']} traces, {report['closed_traces']} "
              f"closed, {report['dumps']} flight-recorder dumps)")
    if args.prom_out:
        write_prometheus(args.prom_out, obs.registry)
        print(f"wrote Prometheus snapshot to {args.prom_out}")


def _stream(args):
    """The example's deterministic scene stream: (coords, feats, mask,
    labels) per scene, geometry repeating every --distinct-scenes."""
    out = []
    for i in range(args.scenes):
        gen = i % args.distinct_scenes
        n = SIZE_CYCLE[gen % len(SIZE_CYCLE)]
        coords, mask, feats = lidar_scene(seed=7 + gen, n_points=n, grid=48)
        labels = (coords[:, 3] > 0).astype(np.int32)
        labels[~mask] = 0
        out.append((coords, feats, mask, labels))
    return out


def run_router(args):
    """--workers N: the same stream through the digest-affinity
    `ServeRouter`; --kill-worker adds the failover chaos contract."""
    from repro.serve.faults import FaultPlan
    from repro.serve.router import ServeRouter

    params = MU.mini_minkunet_init(jax.random.key(0), c_in=4, n_classes=2)
    factory = PointCloudEngine.factory(params, N_STAGES, flow=args.flow,
                                       ladder=geometric_ladder(512, 2048))
    scenes = _stream(args)
    obs = _build_obs(args)

    def build(plan, obs=None):
        return ServeRouter(factory, args.workers, fault_plan=plan,
                           max_batch=args.max_batch,
                           pipeline_depth=args.pipeline_depth,
                           assembly_cache_entries=args.assembly_cache,
                           max_wait_s=args.max_wait_s, obs=obs)

    plan = None
    kill_ordinal = None
    if args.kill_worker is not None:
        if args.kill_worker == "auto":
            # routing is deterministic (seeded scenes, fixed worker
            # names): preview which worker the digests load most and
            # kill that one on its SECOND request (so >= 1 replays)
            probe = build(None)
            names = [probe.preview(c, m) for c, f, m, lb in scenes]
            busiest = max(set(names), key=names.count)
            kill_ordinal = probe.stats()["workers"][busiest]["ordinal"]
            probe.close()
            if names.count(busiest) < 2:
                print("FAIL: no worker receives >= 2 scenes; nothing "
                      "to replay", file=sys.stderr)
                sys.exit(1)
        else:
            kill_ordinal = int(args.kill_worker)
        plan = FaultPlan(kill_workers={kill_ordinal: 1})
        print(f"chaos: killing worker ordinal {kill_ordinal} on its "
              f"2nd request")

    router = build(plan, obs=obs)
    rids = {}
    for coords, feats, mask, labels in scenes:
        rids[router.submit(coords, feats, mask)] = (mask, labels)
    results = router.drain()
    print(f"drained {len(results)} results over {args.workers} workers "
          f"(completion order: {[r.rid for r in results]})")
    for r in results:
        if r.error is not None:
            print(f"  req {r.rid:2d}: {r.n_points:5d} pts -> {r.error}")
            continue
        mask, labels = rids[r.rid]
        acc = (r.preds[mask] == labels[mask]).mean()
        print(f"  req {r.rid:2d}: {r.n_points:5d} pts -> bucket "
              f"{r.bucket:5d}, latency {r.latency_s * 1e3:7.1f} ms, "
              f"untrained-acc {acc:.2f}")

    stats = router.stats()
    router.close()
    pc = stats["pool_cache"]
    ft = stats["faults"]
    print(f"\nrouter served {stats['n_completed']}/{stats['n_submitted']} "
          f"scenes on {stats['n_live']}/{stats['n_workers']} live workers: "
          f"pool cache {pc['mapping_hits']}+{pc['assembly_hits']} hits "
          f"(combined rate {pc['combined_hit_rate'] * 100:.0f}%), "
          f"mean latency {stats['latency_avg_s'] * 1e3:.1f} ms")
    for name, w in stats["workers"].items():
        print(f"  worker {name} [{w['state']:5s}]: routed {w['routed']}, "
              f"processed {w['processed']}"
              + (f", died: {w['reason']}" if w["reason"] else ""))
    print(f"faults: {ft['rejected']} rejected, {ft['shed']} shed, "
          f"{ft['timeout']} timeout, {ft['exec_failed']} exec_failed; "
          f"{ft['failovers']} failovers, {ft['replayed']} replayed"
          + (f", recovery {ft['recovery_s'] * 1e3:.1f} ms"
             if ft["recovery_s"] is not None else ""))

    if args.metrics_json:
        if plan is not None:
            stats = dict(stats, fault_plan=plan.stats())
        with open(args.metrics_json, "w") as f:
            json.dump(stats, f, indent=2, sort_keys=True)
        print(f"wrote router metrics to {args.metrics_json}")
    _export_obs(args, obs)

    if args.kill_worker is not None:
        problems = []
        if len(results) != args.scenes:
            problems.append(f"{len(results)}/{args.scenes} requests "
                            f"completed (lost requests)")
        bad = [r.rid for r in results if r.error is not None]
        if bad:
            problems.append(f"requests {bad} completed without "
                            f"predictions")
        if ft["failovers"] != 1:
            problems.append(f"expected exactly 1 failover, got "
                            f"{ft['failovers']}")
        if ft["replayed"] < 1:
            problems.append("no request was replayed onto a survivor")
        if plan.stats()["workers_killed"] != 1:
            problems.append("the planned worker kill never fired")
        if problems:
            print("FAIL: worker-failover contract violated: "
                  + "; ".join(problems), file=sys.stderr)
            sys.exit(1)
        print("worker-failover contract held: every request completed "
              f"with predictions, {ft['replayed']} replayed onto "
              "survivors, 0 lost")

    if args.min_hit_rate is not None:
        combined = pc["combined_hit_rate"]
        print(f"combined pool hit rate {combined * 100:.0f}% "
              f"(floor {args.min_hit_rate * 100:.0f}%)")
        if combined < args.min_hit_rate:
            print(f"FAIL: combined hit rate {combined:.2f} below the "
                  f"--min-hit-rate floor {args.min_hit_rate:.2f}",
                  file=sys.stderr)
            sys.exit(1)


def run_partition(args):
    """--partition: the city-scale chunk-streaming smoke (see module
    docstring).  Exit nonzero unless the seed path rejects the big scene
    as oversized, the partition path completes it with 0 rejected
    chunks, and forced chunking of a mid-size control scene matches the
    monolithic predictions exactly on every valid row."""
    from repro.partition import PartitionPolicy
    from repro.serve import faults as FLT

    params = MU.mini_minkunet_init(jax.random.key(0), c_in=4, n_classes=2)
    ladder = geometric_ladder(1024, 16384)
    obs = _build_obs(args)
    engine = PointCloudEngine(params, N_STAGES, flow=args.flow,
                              ladder=ladder, max_batch=args.max_batch,
                              obs=obs)
    coords, mask, feats = city_scene(seed=11, n_points=args.points)
    n_valid = int(mask.sum())
    print(f"city scene: {coords.shape[0]} rows, {n_valid} valid voxels, "
          f"ladder top {ladder.capacities[-1]}")

    # the seed path must reject this scene — typed, detail='oversized'
    sched = engine.scheduler()
    rid = sched.submit(coords, feats, mask)
    sched.flush()
    seed_res = sched.take([rid])[rid]
    seed_rejected = (seed_res.error is not None
                     and seed_res.error.code == FLT.REJECTED
                     and seed_res.error.detail == FLT.OVERSIZED)
    print(f"seed path: {seed_res.error}")

    t0 = time.perf_counter()
    preds, _ = engine.segment(coords, mask, feats, partition="auto")
    elapsed = time.perf_counter() - t0
    preds = np.asarray(preds)
    pstats = dict(engine.last_partition_stats)
    pstats.pop("chunk_points", None)
    uncovered = int((preds[mask] < 0).sum())
    print(f"partitioned: {pstats['n_chunks']} chunks (budget "
          f"{pstats['budget']}, max {pstats['max_chunk_points']} pts, "
          f"halo {pstats['halo_fraction'] * 100:.1f}%), "
          f"{pstats['chunk_errors']} chunk errors, {uncovered} uncovered "
          f"valid rows, {n_valid / elapsed:,.0f} points/s")

    # mid-size control scene: forced chunking == monolithic, exactly
    c2, m2, f2 = city_scene(seed=13, n_points=args.control_points)
    mono, _ = engine.segment(c2, m2, f2)
    part, _ = engine.segment(
        c2, m2, f2, partition=PartitionPolicy(chunk_budget=1024, force=True))
    parity = bool(np.array_equal(np.asarray(mono)[m2], np.asarray(part)[m2]))
    print(f"control parity ({int(m2.sum())} valid rows, "
          f"{engine.last_partition_stats['n_chunks']} chunks): "
          f"{'exact' if parity else 'MISMATCH'}")

    if args.metrics_json:
        metrics = {"n_rows": int(coords.shape[0]), "n_valid": n_valid,
                   "elapsed_s": elapsed,
                   "points_per_s": n_valid / elapsed,
                   "seed_rejected_oversized": seed_rejected,
                   "uncovered_valid_rows": uncovered,
                   "control_parity_exact": parity, **pstats,
                   "scheduler": engine.scheduler().stats()["faults"]}
        with open(args.metrics_json, "w") as f:
            json.dump(metrics, f, indent=2, sort_keys=True)
        print(f"wrote partition metrics to {args.metrics_json}")
    _export_obs(args, obs)

    problems = []
    if not seed_rejected:
        problems.append(f"seed path did not reject the {args.points}-row "
                        f"scene as oversized (got {seed_res.error})")
    if pstats["chunk_errors"]:
        problems.append(f"{pstats['chunk_errors']} chunks rejected")
    if uncovered:
        problems.append(f"{uncovered} valid rows left unpredicted")
    if not parity:
        problems.append("chunked control scene diverged from the "
                        "monolithic predictions")
    if problems:
        print("FAIL: partition contract violated: " + "; ".join(problems),
              file=sys.stderr)
        if args.smoke:
            sys.exit(1)
        return
    print("partition contract held: oversized scene rejected by the seed "
          "path, completed chunked with 0 rejected, control scene exact")


def run_storm(args):
    """--storm: the overload-control smoke (see module docstring).
    Exit nonzero unless the controller turns a sustained 2x overload
    into typed sheds with retry hints while the completed requests'
    p95 latency stays within the SLO."""
    from repro.serve.faults import FaultPlan
    from repro.serve.overload import OverloadPolicy, ServeSLO

    params = MU.mini_minkunet_init(jax.random.key(0), c_in=4, n_classes=2)
    obs = _build_obs(args)
    ladder = geometric_ladder(512, 2048)
    engine = PointCloudEngine(params, N_STAGES, flow=args.flow,
                              ladder=ladder, max_batch=args.max_batch,
                              obs=obs)
    # one geometry size -> one bucket, so the storm's paced service
    # rate (and with it the offered-load multiple) is exact
    n, bucket = 640, ladder.bucket_for(640)
    capacity = args.storm_rate * args.max_batch
    plan = FaultPlan(storm_buckets={bucket: args.storm_rate})
    policy = OverloadPolicy(
        slo=ServeSLO(deadline_headroom_s=0.5 * args.slo_s), tick_s=0.02)
    sched = ServeScheduler(engine, max_batch=args.max_batch,
                           pipeline_depth=16, max_backlog=64,
                           assembly_cache_entries=args.assembly_cache,
                           max_wait_s=0.05, fault_plan=plan,
                           overload=policy, obs=obs, instance="storm")
    print(f"storm: bucket {bucket} throttled to {args.storm_rate:.0f} "
          f"micro-batches/s ({capacity:.0f} scenes/s), offering 2x with "
          f"deadline_s={args.slo_s}")

    dist = max(1, args.distinct_scenes)
    geoms = [lidar_scene(seed=7 + g, n_points=n, grid=48)
             for g in range(dist)]
    for coords, mask, feats in geoms:          # un-timed compile warmup
        sched.submit(coords, feats, mask)
    sched.flush()
    sched.drain()

    pace_s = 1.0 / (2.0 * capacity)
    rids = []
    t0 = time.perf_counter()
    for i in range(args.scenes):
        coords, mask, feats = geoms[i % dist]
        rids.append(sched.submit(coords, feats, mask,
                                 deadline_s=args.slo_s, priority=i % 2))
        time.sleep(pace_s)
    sched.flush()
    out = sched.take(rids)
    wall = time.perf_counter() - t0

    stats = sched.stats()
    ov = sched.overload.stats()
    sched.close()
    ok = [r for r in out.values() if r.ok]
    shed = [r for r in out.values()
            if r.error is not None and r.error.code == "shed"]
    lat = np.sort([r.latency_s for r in ok]) if ok else np.empty(0)
    p50 = float(lat[int(0.50 * (len(lat) - 1))]) if len(lat) else None
    p95 = float(lat[int(0.95 * (len(lat) - 1))]) if len(lat) else None
    good = sum(1 for r in ok if r.latency_s <= args.slo_s)
    ft = stats["faults"]
    print(f"storm served {len(ok)}/{args.scenes} within-capacity scenes "
          f"in {wall:.2f}s (goodput {good / wall:.1f}/s of "
          f"{capacity:.0f}/s capacity): {ft['shed']} shed, "
          f"{ft['timeout']} timeout, {ft['exec_failed']} exec_failed"
          + (f"; ok p50 {p50 * 1e3:.0f} ms, p95 {p95 * 1e3:.0f} ms"
             if len(lat) else ""))
    print(f"controller: level {ov['level']} "
          f"({ov['transitions']} brownout transitions), service rate "
          + ", ".join(f"{c}: {r:.1f}/s"
                      for c, r in ov["service_rate"].items())
          + f", effective bound {ov['effective_backlog']}")

    if args.metrics_json:
        dump = dict(stats, overload=ov, fault_plan=plan.stats(),
                    storm={"wall_s": wall, "offered": args.scenes,
                           "capacity_per_s": capacity,
                           "goodput_per_s": good / wall,
                           "slo_s": args.slo_s,
                           "ok_p50_s": p50, "ok_p95_s": p95})
        with open(args.metrics_json, "w") as f:
            json.dump(dump, f, indent=2, sort_keys=True)
        print(f"wrote storm metrics to {args.metrics_json}")
    _export_obs(args, obs)

    problems = []
    if len(out) != args.scenes:
        problems.append(f"{len(out)}/{args.scenes} requests resolved "
                        f"(lost requests)")
    accounted = (len(ok) + ft["shed"] + ft["timeout"] + ft["rejected"])
    if accounted != args.scenes:
        problems.append(f"accounting leak: {len(ok)} ok + {ft['shed']} "
                        f"shed + {ft['timeout']} timeout + "
                        f"{ft['rejected']} rejected != {args.scenes}")
    if ft["exec_failed"] != 0:
        problems.append(f"{ft['exec_failed']} requests exec_failed "
                        f"(overload must shed, not break execution)")
    if not shed:
        problems.append("2x offered load produced no shed (controller "
                        "never engaged)")
    if any(r.error.retry_after_s is None for r in shed):
        problems.append("a shed response carried no retry_after_s hint")
    if not ok:
        problems.append("no request completed at all")
    elif p95 > args.slo_s:
        problems.append(f"p95 of completed requests {p95 * 1e3:.0f} ms "
                        f"blew the {args.slo_s * 1e3:.0f} ms SLO")
    if problems:
        print("FAIL: overload contract violated: " + "; ".join(problems),
              file=sys.stderr)
        sys.exit(1)
    print("overload contract held: every request accounted, overload "
          f"became {ft['shed']} typed sheds with retry hints, completed "
          "p95 within the SLO")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenes", type=int, default=16,
                    help="total scenes pushed through the scheduler")
    ap.add_argument("--distinct-scenes", type=int, default=8,
                    help="geometry repeats every N scenes (cache hits)")
    ap.add_argument("--flow", default="fod",
                    choices=["fod", "gms", "pallas", "pallas_fused"])
    ap.add_argument("--max-batch", type=int, default=4,
                    help="scenes per micro-batch (the vmapped axis)")
    ap.add_argument("--pipeline-depth", type=int, default=2,
                    help="in-flight micro-batches per bucket "
                         "(0 = synchronous)")
    ap.add_argument("--assembly-cache", type=int, default=16,
                    help="composition-keyed stacked-pyramid cache entries "
                         "(0 = per-batch stacking, the PR-4 path)")
    ap.add_argument("--max-wait-s", type=float, default=None,
                    help="deadline before a partial micro-batch runs")
    ap.add_argument("--min-hit-rate", type=float, default=None,
                    help="fail unless the combined mapping+assembly hit "
                         "rate reaches this floor (CI smoke assertion)")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="dump scheduler stats() as JSON (CI artifact)")
    ap.add_argument("--inject-faults", action="store_true",
                    help="run through a low-rate FaultPlan and assert "
                         "the fault-tolerance contract (CI chaos smoke)")
    ap.add_argument("--workers", type=int, default=0,
                    help="serve through a ServeRouter over N workers "
                         "(0 = bare scheduler)")
    ap.add_argument("--kill-worker", default=None, metavar="auto|ORDINAL",
                    help="router chaos: kill this worker ordinal (or the "
                         "busiest, 'auto') mid-stream and assert the "
                         "failover contract (needs --workers >= 2)")
    ap.add_argument("--partition", action="store_true",
                    help="city-scale smoke: serve one oversized scene "
                         "chunked via segment(partition='auto') and "
                         "assert seed-path rejection + halo exactness")
    ap.add_argument("--points", type=int, default=200000,
                    help="city-scene rows for --partition (should exceed "
                         "the ladder top so the seed path rejects it)")
    ap.add_argument("--control-points", type=int, default=4000,
                    help="mid-size control scene for the chunked-vs-"
                         "monolithic parity check under --partition")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode for --partition: exit nonzero on any "
                         "contract violation instead of just reporting")
    ap.add_argument("--storm", action="store_true",
                    help="overload-control smoke: offer 2x the throttled "
                         "service rate through the SLO-aware controller "
                         "and assert the shed/latency contract")
    ap.add_argument("--storm-rate", type=float, default=4.0,
                    help="chaos-throttled service rate for --storm "
                         "(micro-batches/s of the storm bucket)")
    ap.add_argument("--slo-s", type=float, default=2.0,
                    help="per-request deadline_s and the p95 latency "
                         "ceiling the --storm contract asserts")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="enable span tracing + flight recorder and "
                         "write the trace stream as schema-validated "
                         "JSONL (CI artifact)")
    ap.add_argument("--prom-out", default=None, metavar="PATH",
                    help="write a Prometheus text-exposition snapshot "
                         "of the serve metrics registry (CI artifact)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.partition and (args.workers or args.inject_faults):
        ap.error("--partition is its own smoke; it takes no --workers "
                 "or --inject-faults")
    if args.storm and (args.partition or args.workers
                       or args.inject_faults):
        ap.error("--storm is its own smoke; it takes no --partition, "
                 "--workers, or --inject-faults")
    if args.partition:
        return run_partition(args)
    if args.storm:
        return run_storm(args)
    if args.kill_worker is not None and args.workers < 2:
        ap.error("--kill-worker needs --workers >= 2 (a survivor to "
                 "replay onto)")
    if args.workers and args.inject_faults:
        ap.error("--inject-faults is the bare-scheduler chaos smoke; "
                 "use --kill-worker for router chaos")

    if args.workers:
        return run_router(args)

    plan = None
    if args.inject_faults:
        from repro.serve.faults import FaultPlan
        # one transient dispatch failure + one NaN sensor frame; the
        # oversized scene is appended to the stream below
        plan = FaultPlan(fail_dispatches={1}, corrupt_scenes={2})

    params = MU.mini_minkunet_init(jax.random.key(0), c_in=4, n_classes=2)
    obs = _build_obs(args)
    engine = PointCloudEngine(params, N_STAGES, flow=args.flow,
                              ladder=geometric_ladder(512, 2048))
    sched = ServeScheduler(engine, max_batch=args.max_batch,
                           pipeline_depth=args.pipeline_depth,
                           assembly_cache_entries=args.assembly_cache,
                           max_wait_s=args.max_wait_s, fault_plan=plan,
                           obs=obs)

    scenes = {}
    for coords, feats, mask, labels in _stream(args):
        rid = sched.submit(coords, feats, mask)
        scenes[rid] = (mask, labels)
    if args.inject_faults:
        # oversized vs the ladder's top bucket: must come back `rejected`
        coords, mask, feats = lidar_scene(seed=999, n_points=3000, grid=48)
        rid = sched.submit(coords, feats, mask)
        scenes[rid] = (mask, None)
    sched.flush()

    results = sched.drain()
    print(f"drained {len(results)} results "
          f"(completion order: {[r.rid for r in results]})")
    for r in results:
        if r.error is not None:
            print(f"  req {r.rid:2d}: {r.n_points:5d} pts -> {r.error}")
            continue
        mask, labels = scenes[r.rid]
        acc = (r.preds[mask] == labels[mask]).mean()
        print(f"  req {r.rid:2d}: {r.n_points:5d} pts -> bucket "
              f"{r.bucket:5d} (padding {r.padding_frac * 100:4.1f}%), "
              f"mapping {'hit ' if r.mapping_hit else 'miss'}, "
              f"latency {r.latency_s * 1e3:7.1f} ms, "
              f"untrained-acc {acc:.2f}")

    stats = sched.stats()
    mc = stats["mapping_cache"]
    ac = stats["assembly_cache"] or {"hits": 0, "misses": 0,
                                     "hit_rate": 0.0}
    print(f"\nserved {stats['n_completed']}/{stats['n_submitted']} scenes "
          f"on {stats['n_devices']} device(s), max_batch "
          f"{stats['max_batch']}: padding overhead "
          f"{stats['padding_overhead'] * 100:.1f}%, mapping cache "
          f"{mc['hits']} hits / {mc['misses']} misses "
          f"(hit rate {mc['hit_rate'] * 100:.0f}%), assembly cache "
          f"{ac['hits']} hits / {ac['misses']} misses "
          f"(hit rate {ac['hit_rate'] * 100:.0f}%), "
          f"{stats['deadline_flushes']} deadline flushes, compiles "
          f"{stats['compiles']}, mean latency "
          f"{stats['latency_avg_s'] * 1e3:.1f} ms")
    for cap, b in sorted(stats["buckets"].items()):
        print(f"  bucket {cap:5d}: {b['scenes']} scenes in "
              f"{b['batches']} micro-batches of {b['max_batch']} "
              f"(occupancy {b['occupancy'] * 100:.0f}%, "
              f"{b['dummy_scenes']} dummy fills)")

    ft = stats["faults"]
    print(f"faults: {ft['rejected']} rejected, {ft['shed']} shed, "
          f"{ft['timeout']} timeout, {ft['exec_failed']} exec_failed; "
          f"{ft['failed_dispatches']} failed dispatches, "
          f"{ft['retries']} retries"
          + (f", recovery {ft['recovery_s'] * 1e3:.1f} ms"
             if ft["recovery_s"] is not None else ""))

    if args.metrics_json:
        if plan is not None:
            stats = dict(stats, fault_plan=plan.stats())
        with open(args.metrics_json, "w") as f:
            json.dump(stats, f, indent=2, sort_keys=True)
        print(f"wrote scheduler metrics to {args.metrics_json}")
    _export_obs(args, obs)

    if args.inject_faults:
        n_expected = args.scenes + 1
        problems = []
        if len(results) != n_expected:
            problems.append(f"{len(results)}/{n_expected} requests "
                            f"completed")
        if ft["rejected"] != 2:
            problems.append(f"expected 2 rejected (NaN + oversized), got "
                            f"{ft['rejected']}")
        if ft["retries"] < 1:
            problems.append("no retry recorded for the injected "
                            "dispatch failure")
        if ft["exec_failed"] != 0:
            problems.append(f"{ft['exec_failed']} requests exec_failed "
                            f"(transient fault not recovered)")
        if problems:
            print("FAIL: fault-injection contract violated: "
                  + "; ".join(problems), file=sys.stderr)
            sys.exit(1)
        print("fault-injection contract held: every request completed, "
              "transient failure retried, bad scenes rejected")
    else:
        failed = [r for r in results if not r.ok]
        if failed or len(results) != len(scenes):
            print(f"FAIL: {len(failed)} of {len(scenes)} requests not ok, "
                  f"{len(results)} completed; first: "
                  f"{failed[0].error if failed else 'missing result'}",
                  file=sys.stderr)
            sys.exit(1)

    if args.min_hit_rate is not None:
        lookups = mc["hits"] + mc["misses"] + ac["hits"] + ac["misses"]
        combined = (mc["hits"] + ac["hits"]) / lookups if lookups else 0.0
        print(f"combined mapping+assembly hit rate "
              f"{combined * 100:.0f}% (floor "
              f"{args.min_hit_rate * 100:.0f}%)")
        if combined < args.min_hit_rate:
            print(f"FAIL: combined hit rate {combined:.2f} below the "
                  f"--min-hit-rate floor {args.min_hit_rate:.2f}",
                  file=sys.stderr)
            sys.exit(1)


if __name__ == "__main__":
    main()
