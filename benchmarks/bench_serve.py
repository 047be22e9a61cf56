"""Serve hot loop: the pipelined scheduler vs the synchronous PR-4 path.

PointAcc's thesis is that sparse point-cloud inference is bottlenecked by
data movement, not MACs; our serving telemetry showed the same thing one
level up — per-scene overhead dominated by host-side micro-batch
assembly (per-batch `np.stack` + `tree_map(jnp.stack)` over cached
pyramids) and by the synchronous `block_until_ready` that serialized
assembly against device execution.  This benchmark measures the fix on a
steady-state *repeated-composition* stream (a replayed sensor rig — the
hot loop the AssemblyCache is keyed for), same stream and same bucket
ladder through both paths:

  serve/sync_per_scene   pipeline_depth=0, assembly_cache_entries=0
                         (bit-for-bit the PR-4 scheduler)
  serve/pipe_per_scene   composition-keyed assembly cache + pinned host
                         arenas + double-buffered async dispatch
  serve/speedup          sync / pipelined (acceptance: >= 1.3x, i.e.
                         >= 30% lower steady-state per-scene latency)
  serve/assembly         host assembly time per micro-batch, both paths,
                         + mapping/assembly cache hit rates

The fault-tolerance PR adds two rows on the same stream:

  serve/ft_overhead      no-fault steady-state cost of the guards
                         (admission validation + background watchdog) in
                         % of per-scene latency, measured per component
                         (validation timed directly; watchdog tick cost
                         amortized over its 20Hz rate) — an end-to-end
                         A/B delta is also reported but not asserted,
                         because host drift dwarfs a ~1% effect
                         (acceptance: <= 3%, asserted in the full run)
  serve/recovery         injected mid-stream dispatch failure -> next
                         successful retire (the retry/bisect pipeline
                         restart cost), with the failure counters

The city-scale partition PR adds one more row family on a single
mid-size city scene (chunked predictions asserted bit-identical to the
monolithic path first):

  serve/partition_throughput  one row per chunk budget: steady-state
                         points/s of `segment(partition=)` — octree
                         chunking over packed keys + exact receptive-
                         field halos, every chunk served through the
                         scheduler — with the halo overhead fraction
                         (halo rows / total served rows) and the
                         monolithic points/s as the derived baseline.
                         Smaller budgets mean more chunks and a larger
                         halo fraction: the row quantifies that tax.

The multi-worker router PR adds two more rows:

  serve/router_overhead  single-worker `ServeRouter` vs the bare
                         scheduler it fronts, same stream.  The router
                         adds one routing hop per scene (affinity
                         digest + rendezvous ranking + inbox handoff);
                         like serve/ft_overhead, the asserted number is
                         that hop timed directly against the per-scene
                         latency (an end-to-end A/B delta of a ~1-2%
                         effect drowns in +-20% host drift and is
                         reported informationally only).  Acceptance:
                         <= 5%, asserted in the full run after a
                         bit-identity parity check.
  serve/failover_recovery  2-worker router, one worker killed by an
                         injected fault mid-stream on warm engines:
                         worker death -> last replayed victim completed
                         (the failover + replay pipeline cost, no
                         compile in the path)

The observability PR upgrades the latency rows: serve/sync_per_scene
and serve/pipe_per_scene carry p50/p95/p99 from the registry's
per-request latency histogram into BENCH_*.json.

The overload-control PR adds two rows:

  serve/overload_goodput  the SLO-aware controller vs the static
                         max_backlog baseline on the SAME 2x-offered
                         storm-paced stream (FaultPlan.storm_buckets
                         caps the service rate, every request carries
                         deadline_s = the SLO): goodput is completions
                         that are OK *and* within the SLO per second.
                         The uncontrolled path queues until most
                         completions are late; the controller sheds at
                         the Little's-law bound so what it admits
                         finishes on time.  The row value is the
                         goodput ratio (acceptance: >= 1.3x, asserted
                         in the full run).  Parity is asserted first:
                         at nominal load the controller-on scheduler
                         must produce bit-identical predictions.
  serve/overload_overhead  the controller's per-scene hot-path cost at
                         nominal load — the admission gate (rate-
                         limited estimator tick + bound check) and the
                         dispatch-success breaker hook, timed directly
                         against the per-scene latency (ft_overhead
                         discipline; the e2e A/B delta is
                         informational).  Acceptance: <= 3%, asserted
                         in the full run.

Per-request predictions are asserted bit-identical between the paths
before any row is emitted.
"""

from __future__ import annotations

import argparse
import time

import numpy as np
import jax

from benchmarks.common import emit
from repro.data.synthetic import city_scene, lidar_scene
from repro.models import minkunet as MU
from repro.serve.buckets import BucketLadder
from repro.serve.engine import PointCloudEngine
from repro.serve.scheduler import ServeScheduler


def _stream_once(sched, scenes):
    """One pass: submit every scene (full buckets dispatch on submit),
    flush stragglers, take this pass's results."""
    rids = [sched.submit(c, f, m) for (c, m, f) in scenes]
    sched.flush()
    return sched.take(rids)


def _window_us(sched, scenes, reps):
    """Per-scene latency (us) of one continuous measurement window:
    `reps` repeated-composition passes submitted back to back (full
    buckets dispatch on submit — the pipelined path overlaps pass i+1's
    assembly with pass i's execution), one flush+drain at the end."""
    t0 = time.perf_counter()
    for _ in range(reps):
        for (c, m, f) in scenes:
            sched.submit(c, f, m)
    sched.flush()
    n = len(sched.drain())
    return (time.perf_counter() - t0) * 1e6 / n


def bench_hot_loop(n_points: int, reps: int, windows: int,
                   max_batch: int = 4):
    # narrow trunk on small scenes: the serving shape where host-side
    # assembly is a first-order cost (the regime the pipeline targets)
    params = MU.minkunet_init(jax.random.key(0), c_in=4, n_classes=4,
                              stem=8, enc_planes=(8, 16),
                              dec_planes=(16, 8), blocks_per_stage=1)
    scenes = [lidar_scene(seed=21 + i, n_points=n_points, grid=32)
              for i in range(max_batch)]

    def build(**kw):
        # exact-fit single bucket: measures the hot loop, not padding
        engine = PointCloudEngine(params, n_stages=2, flow="fod",
                                  ladder=BucketLadder((n_points,)),
                                  max_batch=max_batch, mesh=None)
        return ServeScheduler(engine, max_batch=max_batch, mesh=None, **kw)

    sync = build(pipeline_depth=0, assembly_cache_entries=0)
    pipe = build()

    # parity first (doubles as compile + cache warmup): same stream,
    # bit-identical per-request predictions
    ref = _stream_once(sync, scenes)
    got = _stream_once(pipe, scenes)
    for rid in ref:
        np.testing.assert_array_equal(ref[rid].preds, got[rid].preds)

    def _asm_snapshot(sched):
        st = sched.stats()
        batches = sum(b["batches"] for b in st["buckets"].values())
        return st["assembly_time_s"], batches

    # interleaved measurement windows, median per path: host-load drift
    # hits both paths instead of whichever ran second
    asm0 = {"sync": _asm_snapshot(sync), "pipe": _asm_snapshot(pipe)}
    sync_w, pipe_w = [], []
    for _ in range(windows):
        sync_w.append(_window_us(sync, scenes, reps))
        pipe_w.append(_window_us(pipe, scenes, reps))
    sync_us = float(np.median(sync_w))
    pipe_us = float(np.median(pipe_w))
    speedup = sync_us / pipe_us

    def _asm_per_batch_us(sched, name):
        t1, b1 = _asm_snapshot(sched)
        t0, b0 = asm0[name]
        return (t1 - t0) * 1e6 / max(1, b1 - b0)

    asm_sync = _asm_per_batch_us(sync, "sync")
    asm_pipe = _asm_per_batch_us(pipe, "pipe")
    s_sync = sync.stats()
    s_pipe = pipe.stats()
    ac = s_pipe["assembly_cache"]

    def _q(st):
        # per-request latency quantiles from the registry histogram,
        # carried into BENCH_*.json next to the window medians
        q = st["latency_quantiles_s"]
        return {"latency_quantiles_us":
                {k: v * 1e6 for k, v in q.items()}}

    emit("serve/sync_per_scene", sync_us,
         f"scenes_per_pass={max_batch};n={n_points};reps={reps};"
         f"windows={windows};path=pr4_synchronous;"
         f"p50_us={s_sync['latency_quantiles_s']['p50'] * 1e6:.0f};"
         f"p99_us={s_sync['latency_quantiles_s']['p99'] * 1e6:.0f}",
         extra=_q(s_sync))
    emit("serve/pipe_per_scene", pipe_us,
         f"assembly_hit_rate={ac['hit_rate']:.2f};"
         f"map_hit_rate={s_pipe['mapping_cache']['hit_rate']:.2f};"
         f"pipeline_depth={s_pipe['pipeline_depth']};"
         f"p50_us={s_pipe['latency_quantiles_s']['p50'] * 1e6:.0f};"
         f"p99_us={s_pipe['latency_quantiles_s']['p99'] * 1e6:.0f}",
         extra=_q(s_pipe))
    emit("serve/speedup", speedup,
         f"sync_us={sync_us:.0f};pipe_us={pipe_us:.0f};parity=ok;"
         f"latency_cut={(1 - pipe_us / sync_us) * 100:.0f}%;"
         f"speedup={speedup:.2f}x")
    emit("serve/assembly", asm_pipe,
         f"sync_per_batch_us={asm_sync:.0f};"
         f"pipe_per_batch_us={asm_pipe:.0f};"
         f"assembly_hits={ac['hits']};assembly_misses={ac['misses']}")
    assert speedup >= 1.3, (
        f"pipelined serve path must cut steady-state per-scene latency by "
        f">= 30% vs the synchronous scheduler, got {speedup:.2f}x "
        f"({sync_us:.0f}us -> {pipe_us:.0f}us)")
    return speedup


def bench_fault_tolerance(n_points: int, reps: int, windows: int,
                          max_batch: int = 4,
                          assert_overhead: bool = True):
    """serve/ft_overhead + serve/recovery on the repeated-composition
    stream: the guarded path (admission validation + watchdog ticker) vs
    the unguarded PR-5 submit path, and the injected-failure recovery
    latency of the retry/bisect machinery."""
    from repro.serve.faults import FaultPlan, validate_scene

    params = MU.minkunet_init(jax.random.key(0), c_in=4, n_classes=4,
                              stem=8, enc_planes=(8, 16),
                              dec_planes=(16, 8), blocks_per_stage=1)
    scenes = [lidar_scene(seed=21 + i, n_points=n_points, grid=32)
              for i in range(max_batch)]

    def build(fault_plan=None, **kw):
        engine = PointCloudEngine(params, n_stages=2, flow="fod",
                                  ladder=BucketLadder((n_points,)),
                                  max_batch=max_batch, mesh=None)
        return ServeScheduler(engine, max_batch=max_batch, mesh=None,
                              fault_plan=fault_plan, **kw)

    base = build(validate=False, watchdog_s=0)   # PR-5 submit path
    ft = build(validate=True, watchdog_s=0.05)   # guarded steady state

    # parity + warmup: the guarded no-fault path must stay bit-identical
    ref = _stream_once(base, scenes)
    got = _stream_once(ft, scenes)
    for rid in ref:
        np.testing.assert_array_equal(ref[rid].preds, got[rid].preds)

    # The guards' per-scene cost is the admission validation (the
    # watchdog is a fixed-rate lock touch, ~us at 20Hz, amortized below
    # noise) — time it directly against the measured per-scene serve
    # latency.  An end-to-end A/B diff of a ~1% effect is hopeless on a
    # shared host (window drift is +-20%), so the interleaved windows
    # below only provide the latency denominator and an informational
    # end-to-end delta.
    base_w, ft_w = [], []
    for _ in range(max(windows, 8)):
        base_w.append(_window_us(base, scenes, reps))
        ft_w.append(_window_us(ft, scenes, reps))
    base_us = float(np.mean(base_w))
    ft_us = float(np.mean(ft_w))
    e2e_delta = ft_us / base_us - 1.0

    c0, m0, f0 = scenes[0]
    n_val = 1000
    t0 = time.perf_counter()
    for _ in range(n_val):
        validate_scene(c0, f0, m0, ft.ladder)
    val_us = (time.perf_counter() - t0) * 1e6 / n_val
    # amortized watchdog cost: one tick per (watchdog period / per-scene
    # latency) scenes; the tick on a busy scheduler is a lock + deadline
    # check + head-readiness probe
    t0 = time.perf_counter()
    for _ in range(n_val):
        ft._watchdog_tick()
    tick_us = (time.perf_counter() - t0) * 1e6 / n_val
    wd_us = tick_us * (base_us / (0.05 * 1e6))
    overhead = (val_us + wd_us) / base_us
    emit("serve/ft_overhead", overhead * 100,
         f"validate_us={val_us:.1f};watchdog_us={wd_us:.2f};"
         f"per_scene_us={base_us:.0f};e2e_delta_pct={e2e_delta * 100:.1f};"
         f"guards=validate+watchdog;target_pct=3")
    ft.close()

    # recovery latency: one mid-stream dispatch failure; the bisected
    # retries run at the already-compiled shape, so this measures the
    # pipeline restart, not a compile
    plan = FaultPlan(fail_dispatches={2})
    rec = build(fault_plan=plan)
    out = _stream_once(rec, scenes * 4)          # 4 full dispatches
    assert all(r.ok for r in out.values()), "recovery run lost requests"
    st = rec.stats()["faults"]
    assert st["failed_dispatches"] == 1 and st["recovery_s"] is not None
    emit("serve/recovery", st["recovery_s"] * 1e3,
         f"retries={st['retries']};exec_failed={st['exec_failed']};"
         f"failure_to_next_retire_ms={st['recovery_s'] * 1e3:.2f}")

    if assert_overhead:
        assert overhead <= 0.03, (
            f"validation + watchdog must cost <= 3% on the no-fault "
            f"steady state, got {overhead * 100:.1f}% "
            f"({base_us:.0f}us -> {ft_us:.0f}us)")
    return overhead


def bench_partition(n_points: int, budgets: tuple[int, ...],
                    reps: int = 2):
    """serve/partition_throughput: chunk-streamed `segment(partition=)`
    points/s per chunk budget on one city scene that itself fits the
    ladder — so the monolithic path provides both the bit-identity
    reference and the baseline points/s the halo tax is measured
    against."""
    from repro.partition import PartitionPolicy
    from repro.serve.buckets import geometric_ladder

    params = MU.mini_minkunet_init(jax.random.key(0), c_in=4, n_classes=4)
    engine = PointCloudEngine(params, n_stages=2, flow="fod",
                              ladder=geometric_ladder(512, 16384),
                              max_batch=4, mesh=None)
    coords, mask, feats = city_scene(seed=29, n_points=n_points)
    n_valid = int(mask.sum())

    def _time(fn):
        fn()                                      # compile + cache warmup
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn()
        return out, (time.perf_counter() - t0) / reps

    ref, mono_s = _time(lambda: engine.segment(coords, mask, feats)[0])
    mono_pps = n_valid / mono_s
    ref = np.asarray(ref)

    for budget in budgets:
        policy = PartitionPolicy(chunk_budget=budget, force=True)
        got, part_s = _time(lambda: engine.segment(
            coords, mask, feats, partition=policy)[0])
        np.testing.assert_array_equal(ref[mask], np.asarray(got)[mask])
        st = engine.last_partition_stats
        pps = n_valid / part_s
        emit("serve/partition_throughput", pps,
             f"budget={budget};chunks={st['n_chunks']};"
             f"halo_frac={st['halo_fraction']:.2f};"
             f"max_chunk={st['max_chunk_points']};"
             f"mono_pts_per_s={mono_pps:.0f};"
             f"rel_mono={pps / mono_pps:.4f}x;n={n_valid};parity=ok")


def bench_router(n_points: int, reps: int, windows: int,
                 max_batch: int = 4, assert_overhead: bool = True):
    """serve/router_overhead + serve/failover_recovery: the
    digest-affinity router's no-fault cost over the bare scheduler
    (single worker, bit-identity asserted first) and the time a
    worker-kill failover takes to make the stream whole on warm
    engines."""
    import itertools

    from repro.serve.faults import FaultPlan
    from repro.serve.router import ServeRouter

    params = MU.minkunet_init(jax.random.key(0), c_in=4, n_classes=4,
                              stem=8, enc_planes=(8, 16),
                              dec_planes=(16, 8), blocks_per_stage=1)
    scenes = [lidar_scene(seed=21 + i, n_points=n_points, grid=32)
              for i in range(max_batch)]

    def engine():
        return PointCloudEngine(params, n_stages=2, flow="fod",
                                ladder=BucketLadder((n_points,)),
                                max_batch=max_batch, mesh=None)

    # routers cycle a 2-engine pool: workers of one router get distinct
    # engines, successive routers reuse them warm (jit caches persist)
    pool = [engine(), engine()]
    counter = itertools.count()

    def factory():
        return pool[next(counter) % len(pool)]

    bare = ServeScheduler(engine(), max_batch=max_batch, mesh=None)
    router = ServeRouter(factory, 1, max_batch=max_batch, mesh=None)

    # parity first (doubles as warmup): the 1-worker router must be
    # bit-identical to the bare scheduler
    ref = _stream_once(bare, scenes)
    got = router.serve([(c, f, m) for (c, m, f) in scenes])
    for rid, brid in zip(sorted(got), sorted(ref)):
        np.testing.assert_array_equal(ref[brid].preds, got[rid].preds)

    def _router_window_us():
        t0 = time.perf_counter()
        for _ in range(reps):
            for (c, m, f) in scenes:
                router.submit(c, f, m)
        router.flush()
        n = len(router.drain())
        return (time.perf_counter() - t0) * 1e6 / n

    bare_w, rout_w = [], []
    for _ in range(windows):
        bare_w.append(_window_us(bare, scenes, reps))
        rout_w.append(_router_window_us())
    bare_us = float(np.median(bare_w))
    rout_us = float(np.median(rout_w))
    e2e_delta = rout_us / bare_us - 1.0

    # the router's per-scene addition is the routing hop: affinity
    # digest + rendezvous ranking (preview IS that hop; the remaining
    # handoff is a deque append + condition notify).  Time it directly
    # — the e2e A/B delta above is drift-dominated and informational.
    c0, m0, _ = scenes[0]
    n_hop = 300
    t0 = time.perf_counter()
    for _ in range(n_hop):
        router.preview(c0, m0)
    hop_us = (time.perf_counter() - t0) * 1e6 / n_hop
    overhead = hop_us / bare_us
    emit("serve/router_overhead", overhead * 100,
         f"hop_us={hop_us:.1f};bare_us={bare_us:.0f};"
         f"router_us={rout_us:.0f};e2e_delta_pct={e2e_delta * 100:.1f};"
         f"parity=ok;workers=1;target_pct=5")
    router.close()
    bare.close()

    # failover recovery: routing is deterministic, so probe which worker
    # the stream loads most, then kill it on its 2nd request of a fresh
    # (warm-engine) run and measure death -> stream made whole
    probe = ServeRouter(factory, 2, max_batch=max_batch, mesh=None)
    probe.serve([(c, f, m) for (c, m, f) in scenes] * reps)
    name, w = max(probe.stats()["workers"].items(),
                  key=lambda kv: kv[1]["routed"])
    ordinal, routed = w["ordinal"], w["routed"]
    probe.close()
    assert routed >= 2, "stream must load one worker with >= 2 scenes"

    plan = FaultPlan(kill_workers={ordinal: 1})
    chaos = ServeRouter(factory, 2, max_batch=max_batch, mesh=None,
                        fault_plan=plan)
    t0 = time.perf_counter()
    out = chaos.serve([(c, f, m) for (c, m, f) in scenes] * reps)
    drain_ms = (time.perf_counter() - t0) * 1e3
    st = chaos.stats()["faults"]
    assert all(r.error is None for r in out.values()), \
        "failover run lost requests"
    assert st["failovers"] == 1 and st["replayed"] >= 1
    assert st["recovery_s"] is not None
    emit("serve/failover_recovery", st["recovery_s"] * 1e3,
         f"replayed={st['replayed']};stream_ms={drain_ms:.1f};"
         f"death_to_recovered_ms={st['recovery_s'] * 1e3:.2f};"
         f"workers=2->1")
    chaos.close()

    if assert_overhead:
        assert overhead <= 0.05, (
            f"single-worker router must cost <= 5% over the bare "
            f"scheduler, got {overhead * 100:.1f}% "
            f"({bare_us:.0f}us -> {rout_us:.0f}us)")
    return overhead


def bench_overload(n_points: int, reps: int, windows: int,
                   max_batch: int = 4, n_scenes: int = 120,
                   storm_rate: float = 10.0,
                   assert_goodput: bool = True):
    """serve/overload_goodput + serve/overload_overhead: the SLO-aware
    controller vs the static max_backlog baseline on a storm-paced
    stream offered at 2x the (throttled) service rate, plus the
    controller's directly-timed per-scene hot-path cost at nominal load
    (ft_overhead discipline).  Parity is asserted first: at nominal
    load the controller must not perturb predictions."""
    from repro.serve.faults import FaultPlan
    from repro.serve.overload import OverloadPolicy, ServeSLO

    params = MU.minkunet_init(jax.random.key(0), c_in=4, n_classes=4,
                              stem=8, enc_planes=(8, 16),
                              dec_planes=(16, 8), blocks_per_stage=1)
    scenes = [lidar_scene(seed=21 + i, n_points=n_points, grid=32)
              for i in range(max_batch)]

    def build(**kw):
        engine = PointCloudEngine(params, n_stages=2, flow="fod",
                                  ladder=BucketLadder((n_points,)),
                                  max_batch=max_batch, mesh=None)
        return ServeScheduler(engine, max_batch=max_batch, mesh=None,
                              **kw)

    slo_s = 0.25
    policy = OverloadPolicy(slo=ServeSLO(deadline_headroom_s=0.15),
                            tick_s=0.02)

    # parity first (doubles as warmup): the controller-on scheduler at
    # nominal load must produce bit-identical predictions — deferred
    # dispatch reorders nothing when every batch is admitted
    plain = build()
    ctrl = build(overload=policy)
    ref = _stream_once(plain, scenes)
    got = _stream_once(ctrl, scenes)
    for rid in ref:
        np.testing.assert_array_equal(ref[rid].preds, got[rid].preds)

    # the controller's per-scene addition at nominal load is the
    # admission gate (rate-limited estimator tick + effective-backlog
    # check) plus the dispatch-success breaker hook — time it directly
    # against the per-scene latency; the interleaved windows provide
    # the denominator and an informational e2e delta (a sub-1% effect
    # drowns in host drift, same story as ft/router overhead)
    plain_w, ctrl_w = [], []
    for _ in range(windows):
        plain_w.append(_window_us(plain, scenes, reps))
        ctrl_w.append(_window_us(ctrl, scenes, reps))
    base_us = float(np.median(plain_w))
    e2e_delta = float(np.median(ctrl_w)) / base_us - 1.0

    ov = ctrl.overload
    n_adm = 1000
    t0 = time.perf_counter()
    for _ in range(n_adm):
        ov.check_admission_locked(n_points, 1, 0)
        ov.record_dispatch_success(n_points)
    adm_us = (time.perf_counter() - t0) * 1e6 / n_adm
    overhead = adm_us / base_us
    emit("serve/overload_overhead", overhead * 100,
         f"admission_us={adm_us:.2f};per_scene_us={base_us:.0f};"
         f"e2e_delta_pct={e2e_delta * 100:.1f};parity=ok;target_pct=3")
    plain.close()
    ctrl.close()

    # goodput at 2x offered load: storm pacing caps the service rate at
    # storm_rate batches/s, the producer offers scenes at twice that,
    # every request carries deadline_s = the SLO.  goodput counts only
    # completions that are OK *and* within the SLO — the static path
    # queues to max_backlog so most completions land late, the
    # controller sheds at the Little's-law bound so admissions finish
    # on time (and the shed errors tell clients when to retry)
    # the static config is tuned the way burst-absorbing deployments
    # are: deep pipeline, generous backlog — under SUSTAINED 2x load
    # that queue depth is exactly what turns every completion late.
    # The controller runs the same config; its Little's-law bound
    # (service_rate x headroom, ~2 batches here) replaces the static
    # depth as the effective admission limit
    def overloaded_run(overload):
        plan = FaultPlan(storm_buckets={n_points: storm_rate})
        s = build(fault_plan=plan, overload=overload,
                  pipeline_depth=16, max_backlog=64, max_wait_s=0.05)
        for (c, m, f) in scenes:        # un-timed compile/cache warmup
            s.submit(c, f, m)
        s.flush()
        s.drain()
        pace_s = 1.0 / (2.0 * storm_rate * max_batch)
        rids = []
        t0 = time.perf_counter()
        for i in range(n_scenes):
            c, m, f = scenes[i % len(scenes)]
            rids.append(s.submit(c, f, m, deadline_s=slo_s))
            time.sleep(pace_s)
        s.flush()
        out = s.take(rids)
        wall = time.perf_counter() - t0
        st = s.stats()
        ov_st = s.overload.stats() if s.overload is not None else None
        s.close()
        assert st["faults"]["exec_failed"] == 0, \
            "overload must shed, never fail execution"
        good = sum(1 for r in out.values()
                   if r.ok and r.latency_s is not None
                   and r.latency_s <= slo_s)
        return good / wall, st, ov_st, wall

    static_gps, static_st, _, static_wall = overloaded_run(None)
    ctrl_gps, ctrl_st, ov_st, ctrl_wall = overloaded_run(policy)
    # a floor of one good scene per wall keeps the ratio meaningful
    # when the uncontrolled path blows the SLO for every completion
    ratio = ctrl_gps / max(static_gps, 1.0 / static_wall)
    sf, cf = static_st["faults"], ctrl_st["faults"]
    emit("serve/overload_goodput", ratio,
         f"ctrl_good_per_s={ctrl_gps:.1f};"
         f"static_good_per_s={static_gps:.1f};"
         f"capacity_per_s={storm_rate * max_batch:.0f};"
         f"offered_x=2;slo_ms={slo_s * 1e3:.0f};"
         f"ctrl_shed={cf['shed']};ctrl_timeout={cf['timeout']};"
         f"static_shed={sf['shed']};static_timeout={sf['timeout']};"
         f"walls_s={static_wall:.2f}/{ctrl_wall:.2f};parity=ok",
         extra={"controller": ov_st})

    if assert_goodput:
        assert ctrl_gps >= 1.3 * static_gps, (
            f"the controller must deliver >= 1.3x the static baseline's "
            f"within-SLO goodput at 2x offered load, got "
            f"{ctrl_gps:.1f}/s vs {static_gps:.1f}/s")
        assert overhead <= 0.03, (
            f"the controller's admission hot path must cost <= 3% per "
            f"scene at nominal load, got {overhead * 100:.1f}% "
            f"({adm_us:.2f}us vs {base_us:.0f}us/scene)")
    return ratio


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="smaller cloud / fewer reps (CI)")
    args = ap.parse_args(argv)
    if args.smoke:
        bench_hot_loop(n_points=128, reps=3, windows=3)
        bench_fault_tolerance(n_points=128, reps=3, windows=3,
                              assert_overhead=False)
        bench_router(n_points=128, reps=3, windows=3,
                     assert_overhead=False)
        bench_overload(n_points=128, reps=3, windows=3, n_scenes=90,
                       storm_rate=15.0, assert_goodput=False)
        bench_partition(n_points=3000, budgets=(512, 1024), reps=1)
    else:
        bench_hot_loop(n_points=128, reps=6, windows=5)
        bench_fault_tolerance(n_points=128, reps=6, windows=5)
        bench_router(n_points=128, reps=8, windows=5)
        bench_overload(n_points=128, reps=6, windows=5, n_scenes=120,
                       storm_rate=10.0)
        bench_partition(n_points=12000, budgets=(1024, 2048, 4096))


if __name__ == "__main__":
    main()
