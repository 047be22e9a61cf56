"""Continuous-batching serve scheduler: the bucket ladder (capacities,
padding, numerical invariance), the ServeScheduler (queueing, bucketed
micro-batches, out-of-order drain, telemetry), bounded compile counts
through every engine entry point, and mixed-bucket parity with a
per-scene loop across the fod / pallas / pallas_fused flows — plus the
pipelined hot loop: the composition-keyed AssemblyCache (hit / permute /
evict), pre-stacked dummy tails, double-buffered async dispatch +
FIFO retirement, thread-safe submit under concurrent producers,
deadline-aware flush, per-bucket max_batch overrides, and bit-identical
parity with the synchronous (PR-4) path.  The shard_map-sharded executor
is covered on a mocked multi-device mesh in tests/test_distributed.py;
here the same code degrades to the single-device vmapped path.

The fault-tolerance layer (admission rejection, shed, deadline timeout,
retry/bisect isolation, watchdog, close()) has its own unit suite in
tests/test_serve_faults.py; THIS file holds the end-to-end chaos test —
concurrent producers through an injected FaultPlan."""

import threading
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.api import MappingCache, PointAccSession
from repro.core import mapping as M
from repro.data.synthetic import lidar_scene
from repro.models import minkunet as MU
from repro.serve.buckets import (BucketLadder, geometric_ladder,
                                 max_batch_from_occupancy, pad_scene)
from repro.serve.engine import PointCloudEngine
from repro.serve.scheduler import ServeScheduler, _HostArena


def _mini_params(n_classes=2):
    return MU.mini_minkunet_init(jax.random.key(0), c_in=4,
                                 n_classes=n_classes)


def _ref_preds(params, coords, mask, feats, flow="fod"):
    pc = M.make_point_cloud(jnp.asarray(coords), jnp.asarray(mask))
    logits = MU.minkunet_apply(params, pc, jnp.asarray(feats), flow=flow)
    return np.asarray(jnp.argmax(logits, -1))


# ---------------------------------------------------------------------------
# bucket ladder policy
# ---------------------------------------------------------------------------

def test_arena_stage_survives_refill():
    """A staged slot is a copy: refilling the one slot in place (as the
    next micro-batch does) leaves the staged device arrays unchanged."""
    arena = _HostArena(1, 2, 64, 4)
    s = arena.next_slot(np.zeros((64, 4), np.float32))
    arena.feats[s] = 1.0
    arena.mask[s] = True
    feats, mask = arena.stage(arena.feats[s]), arena.stage(arena.mask[s])
    assert arena.next_slot(np.zeros((64, 4), np.float32)) == s
    arena.feats[s] = 2.0
    arena.mask[s] = False
    assert (np.asarray(feats) == 1.0).all() and np.asarray(mask).all()


def test_bucket_ladder_selection_and_bounds():
    ladder = BucketLadder((64, 128, 256))
    assert ladder.n_buckets == 3
    assert ladder.bucket_for(1) == 64
    assert ladder.bucket_for(64) == 64
    assert ladder.bucket_for(65) == 128
    assert ladder.bucket_for(256) == 256
    assert ladder.index_for(200) == 2
    with pytest.raises(ValueError, match="exceeds the bucket ladder"):
        ladder.bucket_for(257)
    assert ladder.padding_fraction(96) == pytest.approx(0.25)


def test_bucket_ladder_validation():
    with pytest.raises(ValueError, match="ascending"):
        BucketLadder((128, 64))
    with pytest.raises(ValueError, match="ascending"):
        BucketLadder((64, 64))
    with pytest.raises(ValueError, match="positive"):
        BucketLadder((0, 64))
    with pytest.raises(ValueError, match="growth"):
        geometric_ladder(64, 256, growth=1.0)


def test_geometric_ladder_growth_bounds_padding():
    ladder = geometric_ladder(128, 4096, growth=2.0)
    caps = ladder.capacities
    assert caps[0] == 128 and caps[-1] >= 4096
    assert all(c % 8 == 0 for c in caps)
    # worst-case padding of a geometric ladder is 1 - 1/growth
    for n in range(129, 4096, 97):
        assert ladder.padding_fraction(n) < 0.5 + 1e-9


def test_pad_scene_sentinels_and_masked_rows():
    rng = np.random.default_rng(0)
    coords = rng.integers(0, 10, size=(5, 4)).astype(np.int32)
    mask = np.array([True, True, False, True, True])
    feats = rng.normal(size=(5, 3)).astype(np.float32)
    c, m, f = pad_scene(coords, mask, feats, 8)
    assert c.shape == (8, 4) and m.shape == (8,) and f.shape == (8, 3)
    np.testing.assert_array_equal(m, list(mask) + [False] * 3)
    # padding rows AND pre-masked rows are sentinel-filled / zeroed
    assert (c[5:] == M.SENTINEL).all() and (c[2] == M.SENTINEL).all()
    assert (f[5:] == 0).all() and (f[2] == 0).all()
    np.testing.assert_array_equal(c[0], coords[0])
    with pytest.raises(ValueError, match="pad.*down"):
        pad_scene(coords, mask, feats, 4)
    # feats=None path (mapping-only padding)
    c2, m2, f2 = pad_scene(coords, mask, None, 8)
    np.testing.assert_array_equal(c2, c)
    assert f2 is None


@pytest.mark.parametrize("flow", ["fod", "pallas_fused"])
def test_bucket_padding_preserves_logits(flow):
    """The core invariant the ladder relies on: padding a scene to a
    bucket capacity leaves the valid rows' logits unchanged (atol 1e-5)
    — sentinel rows sort to the end and never enter a kernel map."""
    coords, mask, feats = lidar_scene(3, 72, grid=16)
    params = _mini_params()
    session = PointAccSession(flow=flow)
    x = session.tensor(jnp.asarray(coords), jnp.asarray(mask),
                       jnp.asarray(feats))
    ref = MU.minkunet_forward(session, params, x)

    session2 = PointAccSession(flow=flow)
    xp = session2.tensor(jnp.asarray(coords), jnp.asarray(mask),
                         jnp.asarray(feats)).padded_to(128)
    assert xp.capacity == 128
    out = MU.minkunet_forward(session2, params, xp)
    np.testing.assert_allclose(np.asarray(out)[:72], np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_padded_to_rejects_shrink_and_is_idempotent():
    coords, mask, feats = lidar_scene(3, 40, grid=12)
    session = PointAccSession()
    x = session.tensor(jnp.asarray(coords), jnp.asarray(mask),
                       jnp.asarray(feats))
    assert x.padded_to(40) is x
    with pytest.raises(ValueError, match="buckets only grow"):
        x.padded_to(16)


# ---------------------------------------------------------------------------
# bucket-aware MappingCache keys
# ---------------------------------------------------------------------------

def test_mapping_cache_extra_distinguishes_buckets():
    cache = MappingCache()
    a = np.zeros(4, np.int32)
    assert cache.get((a,), lambda: "b128", extra=("levels", 128)) \
        == ("b128", False)
    # same bytes, different bucket metadata -> different entry
    assert cache.get((a,), lambda: "b256", extra=("levels", 256)) \
        == ("b256", False)
    assert cache.get((a,), lambda: None, extra=("levels", 128)) \
        == ("b128", True)
    assert MappingCache.digest((a,)) != MappingCache.digest((a,), "tag")
    assert "hit_rate" in cache.stats()


# ---------------------------------------------------------------------------
# acceptance: heterogeneous stream through the scheduler
# ---------------------------------------------------------------------------

def test_scheduler_heterogeneous_stream_acceptance():
    """ISSUE-4 acceptance: >= 16 scenes with >= 4 distinct point counts;
    compilations bounded by #buckets; results match the per-scene loop;
    out-of-order drain; padding / occupancy / hit-rate telemetry."""
    params = _mini_params()
    ladder = geometric_ladder(64, 512)
    engine = PointCloudEngine(params, n_stages=2, flow="fod",
                              ladder=ladder)
    sched = ServeScheduler(engine, max_batch=4, mesh=None)

    sizes = [40, 90, 150, 300]
    scenes = []
    for i in range(16):
        c, m, f = lidar_scene(seed=20 + i % 8, n_points=sizes[i % 4],
                              grid=24)
        scenes.append((c, m, f))
    rids = [sched.submit(c, f, m) for (c, m, f) in scenes]
    assert sched.flush() + sum(len(q) for q in sched._queues.values()) <= 16
    results = sched.drain()
    assert len(results) == 16
    assert sched.drain() == []                        # drained once

    # out-of-order completion: buckets fill at different times
    drained_order = [r.rid for r in results]
    assert sorted(drained_order) == sorted(rids)
    assert drained_order != sorted(drained_order)

    # numerical parity with a per-scene loop, un-padded row counts
    by_rid = {r.rid: r for r in results}
    for rid, (c, m, f) in zip(rids, scenes):
        r = by_rid[rid]
        assert r.n_points == c.shape[0]
        np.testing.assert_array_equal(r.preds, _ref_preds(params, c, m, f))

    # compile bound: one program per bucket per entry point
    n_buckets_used = len({r.bucket for r in results})
    assert n_buckets_used == 4
    comp = engine.compile_stats()
    assert 0 < comp["build"] <= n_buckets_used
    assert 0 < comp["apply_batch"] <= n_buckets_used

    # telemetry: second half of the stream repeats the first's geometry
    stats = sched.stats()
    assert stats["n_completed"] == 16 and stats["queue_depth"] == 0
    assert stats["mapping_cache"]["hits"] == 8
    assert stats["mapping_cache"]["hit_rate"] == pytest.approx(0.5)
    assert stats["padding_overhead"] > 0
    assert stats["n_devices"] == 1                    # CPU degrade path
    for cap, b in stats["buckets"].items():
        assert 0 < b["occupancy"] <= 1.0
        assert b["scenes"] == 4
    # per-request telemetry: repeated geometry reports a mapping hit
    for rid in rids[8:]:
        assert by_rid[rid].mapping_hit
    for rid in rids[:8]:
        assert not by_rid[rid].mapping_hit


def test_scheduler_full_bucket_executes_on_submit():
    """Continuous batching: a bucket that reaches max_batch runs without
    waiting for flush()."""
    params = _mini_params()
    engine = PointCloudEngine(params, n_stages=2, flow="fod",
                              ladder=geometric_ladder(64, 128))
    sched = ServeScheduler(engine, max_batch=2, mesh=None)
    sched.submit(*_scene_cf(0, 40))
    assert len(sched.drain()) == 0
    sched.submit(*_scene_cf(1, 40))                   # fills the bucket
    res = sched.drain()
    assert [r.rid for r in res] == [0, 1]
    assert sched.stats()["queue_depth"] == 0


def _scene_cf(seed, n):
    c, m, f = lidar_scene(seed=40 + seed, n_points=n, grid=16)
    return c, f, m


def test_scheduler_partial_flush_uses_dummy_fill():
    """A straggler still runs (padded with masked dummy scenes) and the
    fill is visible in the occupancy telemetry, not the mapping cache."""
    params = _mini_params()
    engine = PointCloudEngine(params, n_stages=2, flow="fod",
                              ladder=geometric_ladder(64, 64))
    sched = ServeScheduler(engine, max_batch=4, mesh=None)
    c, f, m = _scene_cf(0, 50)
    rid = sched.submit(c, f, m)
    assert sched.flush() == 1
    (res,) = sched.drain()
    assert res.rid == rid
    np.testing.assert_array_equal(res.preds, _ref_preds(params, c, m, f))
    stats = sched.stats()
    assert stats["buckets"][64]["dummy_scenes"] == 3
    assert stats["buckets"][64]["occupancy"] == pytest.approx(0.25)
    # dummy pyramids are cached scheduler-side: cache counts real scenes
    assert stats["mapping_cache"]["misses"] == 1


def test_scheduler_serve_convenience_and_ladder_overflow():
    params = _mini_params()
    engine = PointCloudEngine(params, n_stages=2, flow="fod",
                              ladder=geometric_ladder(64, 128))
    sched = ServeScheduler(engine, max_batch=2, mesh=None)
    out = sched.serve([_scene_cf(i, n) for i, n in enumerate((30, 80))])
    assert set(out) == {0, 1}
    # regression (ISSUE-6 satellite): an oversized scene no longer leaks
    # ValueError out of submit() — it completes as a typed `rejected`
    # result and IS counted as submitted
    rid = sched.submit(*_scene_cf(9, 400))
    res = sched.take([rid])[rid]
    assert not res.ok and res.preds is None
    assert res.error.code == "rejected"
    assert "exceeds the bucket ladder" in res.error.message
    st = sched.stats()
    assert st["n_submitted"] == 3 and st["faults"]["rejected"] == 1
    with pytest.raises(ValueError, match="max_batch"):
        ServeScheduler(engine, max_batch=0, mesh=None)


# ---------------------------------------------------------------------------
# mixed-bucket parity across flows (vmapped pallas/pallas_fused)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("flow", ["pallas", "pallas_fused"])
def test_scheduler_parity_across_flows_mixed_buckets(flow):
    """Satellite: vmapped `pallas`/`pallas_fused` under mixed bucket
    sizes — scheduler results match a per-scene fod loop (exact argmax,
    logits agree at atol 1e-5 per the flow-parity suite), including the
    out-of-order drain path."""
    params = _mini_params()
    engine = PointCloudEngine(params, n_stages=2, flow=flow,
                              ladder=geometric_ladder(48, 96))
    sched = ServeScheduler(engine, max_batch=2, mesh=None)
    sizes = [30, 70, 40, 90]                      # alternating buckets
    scenes = [_scene_cf(i, n) for i, n in enumerate(sizes)]
    rids = [sched.submit(c, f, m) for (c, f, m) in scenes]
    sched.flush()
    results = sched.drain()
    assert sorted(r.rid for r in results) == rids
    by_rid = {r.rid: r for r in results}
    for rid, (c, f, m) in zip(rids, scenes):
        np.testing.assert_array_equal(
            by_rid[rid].preds, _ref_preds(params, c, m, f, flow="fod"))
    assert engine.compile_stats()["apply_batch"] <= 2


# ---------------------------------------------------------------------------
# engine entry points: bounded retraces through the ladder
# ---------------------------------------------------------------------------

def test_engine_segment_bounded_jit_cache_across_sizes():
    """Satellite fix: distinct (B, N) no longer retrace per point count —
    every entry point pads through the ladder, so the jit cache is
    bounded by the number of buckets actually touched."""
    params = _mini_params()
    engine = PointCloudEngine(params, n_stages=2, flow="fod",
                              ladder=geometric_ladder(128, 256))
    refs = {}
    for n in (50, 80, 100, 128):                  # all -> bucket 128
        c, m, f = lidar_scene(seed=60 + n, n_points=n, grid=20)
        preds, hit = engine.segment(c, m, f)
        assert not hit and preds.shape == (n,)
        refs[n] = (np.asarray(preds), c, m, f)
    comp = engine.compile_stats()
    assert comp["build"] == 1 and comp["apply"] == 1

    c, m, f = lidar_scene(seed=61, n_points=200, grid=20)  # bucket 256
    engine.segment(c, m, f)
    comp = engine.compile_stats()
    assert comp["build"] == 2 and comp["apply"] == 2

    # parity: padded serving == per-scene unpadded reference
    for n, (preds, c, m, f) in refs.items():
        np.testing.assert_array_equal(preds, _ref_preds(params, c, m, f))

    # repeated geometry is a cache hit; levels can be passed back in
    c, m, f = refs[80][1:]
    levels, hit = engine.levels_for(c, m)
    assert hit
    preds, hit2 = engine.segment(c, m, f, levels=levels)
    assert hit2 is None
    np.testing.assert_array_equal(np.asarray(preds), refs[80][0])
    assert engine.compile_stats()["apply"] == 2   # still bounded


def test_segment_batch_shares_scheduler_without_stealing_results():
    """A scene submitted directly to the engine's scheduler survives a
    segment_batch call on the same scheduler: the batch flush executes
    it, but its result stays drainable (take() vs drain())."""
    params = _mini_params()
    engine = PointCloudEngine(params, n_stages=2, flow="fod",
                              ladder=geometric_ladder(64, 64),
                              max_batch=2)
    sched = engine.scheduler()
    c, f, m = _scene_cf(0, 40)
    rid = sched.submit(c, f, m)

    bc, bm, bf = [], [], []
    for i in (1, 2):
        sc, sf, sm = _scene_cf(i, 40)
        bc.append(sc), bm.append(sm), bf.append(sf)
    preds, _ = engine.segment_batch(np.stack(bc), np.stack(bm),
                                    np.stack(bf))
    assert preds.shape == (2, 40)
    # the foreign request was executed by the batch's flush, not lost
    res = sched.drain()
    assert [r.rid for r in res] == [rid]
    np.testing.assert_array_equal(res[0].preds,
                                  _ref_preds(params, c, m, f))


def test_segment_batch_ladder_overflow_leaves_no_orphans():
    """A ladder overflow raises BEFORE any scene is admitted, so the
    shared scheduler holds no orphaned queue state."""
    params = _mini_params()
    engine = PointCloudEngine(params, n_stages=2, flow="fod",
                              ladder=geometric_ladder(64, 128))
    scenes = [_scene_cf(i, 160) for i in range(2)]   # > ladder max
    coords = np.stack([c for c, _, _ in scenes])
    feats = np.stack([f for _, f, _ in scenes])
    mask = np.stack([m for _, _, m in scenes])
    with pytest.raises(ValueError, match="exceeds the bucket ladder"):
        engine.segment_batch(coords, mask, feats)
    stats = engine.scheduler().stats()
    assert stats["n_submitted"] == 0 and stats["queue_depth"] == 0


def test_padding_telemetry_counts_valid_rows():
    """padding_frac / padding_overhead count dead rows from pre-masked
    scenes, not just ladder padding."""
    params = _mini_params()
    engine = PointCloudEngine(params, n_stages=2, flow="fod",
                              ladder=geometric_ladder(64, 64))
    sched = ServeScheduler(engine, max_batch=1, mesh=None)
    c, m, f = lidar_scene(seed=80, n_points=64, grid=12)
    assert not m.all()                 # lidar dedupe masks some rows
    rid = sched.submit(c, f, m)
    res = sched.take([rid])[rid]
    expected = 1.0 - m.sum() / 64
    assert res.padding_frac == pytest.approx(expected)
    assert sched.stats()["padding_overhead"] == pytest.approx(
        64 / m.sum() - 1.0)


# ---------------------------------------------------------------------------
# pipelined hot loop: assembly cache, dummy tails, async dispatch, threads
# ---------------------------------------------------------------------------

def test_assembly_cache_repeated_vs_permuted_composition():
    """The composition key is ORDERED per-scene pyramid digests: a
    replayed micro-batch hits (and bypasses the per-scene mapping cache
    wholesale), a permuted one misses the assembly cache but still hits
    the mapping cache scene by scene."""
    params = _mini_params()
    engine = PointCloudEngine(params, n_stages=2, flow="fod",
                              ladder=geometric_ladder(64, 64))
    sched = ServeScheduler(engine, max_batch=2, mesh=None)
    a, b = _scene_cf(0, 40), _scene_cf(1, 50)

    r1 = sched.take([sched.submit(c, f, m) for (c, f, m) in (a, b)])
    ac = sched.stats()["assembly_cache"]
    assert (ac["hits"], ac["misses"]) == (0, 1)

    mc0 = engine.cache_stats()
    r2 = sched.take([sched.submit(c, f, m) for (c, f, m) in (a, b)])
    ac = sched.stats()["assembly_cache"]
    assert (ac["hits"], ac["misses"]) == (1, 1)
    mc = engine.cache_stats()           # mapping cache never consulted
    assert mc["hits"] == mc0["hits"] and mc["misses"] == mc0["misses"]
    assert all(r.mapping_hit for r in r2.values())

    r3 = sched.take([sched.submit(c, f, m) for (c, f, m) in (b, a)])
    ac = sched.stats()["assembly_cache"]
    assert (ac["hits"], ac["misses"]) == (1, 2)
    mc = engine.cache_stats()           # per-scene pyramids still reused
    assert mc["hits"] == mc0["hits"] + 2

    for res, order in ((r1, (a, b)), (r2, (a, b)), (r3, (b, a))):
        for rid, (c, f, m) in zip(sorted(res), order):
            np.testing.assert_array_equal(res[rid].preds,
                                          _ref_preds(params, c, m, f))
    # one bucket, cache on: still one compiled batch program
    assert engine.compile_stats()["apply_batch"] == 1


def test_assembly_cache_lru_eviction_bound():
    params = _mini_params()
    engine = PointCloudEngine(params, n_stages=2, flow="fod",
                              ladder=geometric_ladder(64, 64))
    sched = ServeScheduler(engine, max_batch=1, mesh=None,
                           assembly_cache_entries=1)
    a, b = _scene_cf(0, 40), _scene_cf(1, 50)
    for scene in (a, b, a):             # a evicted by b, then b by a
        (c, f, m) = scene
        sched.take([sched.submit(c, f, m)])
    ac = sched.stats()["assembly_cache"]
    assert ac == {"hits": 0, "misses": 3, "hit_rate": 0.0,
                  "evictions": 2, "entries": 1, "max_entries": 1}
    with pytest.raises(ValueError, match="max_entries"):
        ServeScheduler(engine, mesh=None, assembly_cache_entries=-1)


def test_dummy_tails_prestacked_per_bucket_and_count():
    """Partial micro-batches reuse a pre-stacked dummy pyramid tail per
    (bucket, n_dummies); a replayed straggler composition (same scene,
    same tail length) hits the assembly cache outright."""
    params = _mini_params()
    engine = PointCloudEngine(params, n_stages=2, flow="fod",
                              ladder=geometric_ladder(64, 64))
    sched = ServeScheduler(engine, max_batch=4, mesh=None)
    a, b = _scene_cf(0, 40), _scene_cf(1, 50)

    rid = sched.submit(*a)
    sched.flush()                       # 1 real + 3 dummies
    assert set(sched._dummy_tails) == {(64, 3)}
    sched.submit(*a), sched.submit(*b)
    sched.flush()                       # 2 real + 2 dummies
    assert set(sched._dummy_tails) == {(64, 3), (64, 2)}
    sched.submit(*a)
    sched.flush()                       # same straggler composition
    assert set(sched._dummy_tails) == {(64, 3), (64, 2)}
    assert sched.stats()["assembly_cache"]["hits"] == 1

    res = {r.rid: r for r in sched.drain()}
    (c, f, m) = a
    np.testing.assert_array_equal(res[rid].preds,
                                  _ref_preds(params, c, m, f))
    # dummy pyramids built scheduler-side: cache counts real scenes only
    assert sched.stats()["mapping_cache"]["misses"] == 2


def test_async_dispatch_parks_in_flight_fifo_retirement():
    """Dispatch no longer blocks: a full bucket's micro-batch parks on
    the in-flight FIFO and completes in drain()/poll(); exceeding
    pipeline_depth retires the oldest slot first, so completion order is
    dispatch order."""
    params = _mini_params()
    engine = PointCloudEngine(params, n_stages=2, flow="fod",
                              ladder=geometric_ladder(64, 128))
    sched = ServeScheduler(engine, max_batch=2, mesh=None,
                           pipeline_depth=2)
    for n in (40, 40, 90, 90):          # fills bucket 64, then bucket 128
        sched.submit(*_scene_cf(n, n))
    st = sched.stats()
    assert st["in_flight"] == 2         # both parked, neither retired
    assert st["n_completed"] == 0
    assert [r.rid for r in sched.drain()] == [0, 1, 2, 3]
    assert sched.stats()["in_flight"] == 0

    # depth 1: the third dispatch to one bucket forces the first two out
    sched2 = ServeScheduler(engine, max_batch=1, mesh=None,
                            pipeline_depth=1)
    for i in range(3):
        sched2.submit(*_scene_cf(i, 40))
    st = sched2.stats()
    assert st["in_flight"] == 1 and st["n_completed"] == 2
    with pytest.raises(ValueError, match="pipeline_depth"):
        ServeScheduler(engine, mesh=None, pipeline_depth=-1)


def test_thread_safe_submit_under_concurrent_producers():
    """submit() from several producer threads while earlier micro-batches
    execute: no lost/duplicated rids, telemetry adds up, every result
    matches the per-scene reference."""
    params = _mini_params()
    engine = PointCloudEngine(params, n_stages=2, flow="fod",
                              ladder=geometric_ladder(64, 128))
    sched = ServeScheduler(engine, max_batch=4, mesh=None)
    submitted = []

    def producer(t):
        for j in range(4):
            c, f, m = _scene_cf(4 * t + j, 40 if j % 2 else 90)
            rid = sched.submit(c, f, m)
            submitted.append((rid, (c, f, m)))

    threads = [threading.Thread(target=producer, args=(t,))
               for t in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    sched.flush()
    results = {r.rid: r for r in sched.drain()}

    assert len(submitted) == 16
    rids = [rid for rid, _ in submitted]
    assert sorted(rids) == list(range(16))      # unique, gap-free
    st = sched.stats()
    assert st["n_submitted"] == 16 and st["n_completed"] == 16
    assert st["queue_depth"] == 0 and st["in_flight"] == 0
    for rid, (c, f, m) in submitted:
        np.testing.assert_array_equal(results[rid].preds,
                                      _ref_preds(params, c, m, f))


@pytest.mark.parametrize("flow", ["pallas", "pallas_fused"])
def test_pipelined_parity_with_synchronous_path(flow):
    """Acceptance: the pipelined path (assembly cache + arenas + async
    dispatch) is bit-identical to the synchronous PR-4 path
    (pipeline_depth=0, assembly_cache_entries=0) on the same repeated
    stream, per flow."""
    params = _mini_params()

    def run(**kw):
        engine = PointCloudEngine(params, n_stages=2, flow=flow,
                                  ladder=geometric_ladder(48, 96))
        sched = ServeScheduler(engine, max_batch=2, mesh=None, **kw)
        base = [_scene_cf(i, n) for i, n in enumerate((30, 70, 40, 90))]
        return sched, sched.serve(base * 2)     # repeat -> assembly hits

    sync_sched, sync_out = run(pipeline_depth=0, assembly_cache_entries=0)
    pipe_sched, pipe_out = run()
    assert sync_sched.stats()["assembly_cache"] is None
    assert pipe_sched.stats()["assembly_cache"]["hits"] >= 2
    assert sorted(sync_out) == sorted(pipe_out)
    for rid in sync_out:
        np.testing.assert_array_equal(sync_out[rid].preds,
                                      pipe_out[rid].preds)


def test_serve_returns_only_own_requests():
    """Satellite fix: serve() on a shared scheduler returns the rids IT
    submitted; a foreign request executed by the same flush stays
    drainable."""
    params = _mini_params()
    engine = PointCloudEngine(params, n_stages=2, flow="fod",
                              ladder=geometric_ladder(64, 64))
    sched = ServeScheduler(engine, max_batch=4, mesh=None)
    c, f, m = _scene_cf(0, 40)
    foreign = sched.submit(c, f, m)
    out = sched.serve([_scene_cf(i, 40) for i in (1, 2)])
    assert set(out) == {1, 2}                   # not the foreign rid
    res = sched.drain()
    assert [r.rid for r in res] == [foreign]
    np.testing.assert_array_equal(res[0].preds,
                                  _ref_preds(params, c, m, f))


def test_deadline_flush_runs_overdue_partial_batch():
    """max_wait_s policy: a partial micro-batch executes once its oldest
    queued request exceeds the deadline (checked in submit()/poll()),
    counted in stats()["deadline_flushes"]."""
    params = _mini_params()
    engine = PointCloudEngine(params, n_stages=2, flow="fod",
                              ladder=geometric_ladder(64, 64))
    # watchdog_s=0 keeps the firing synchronous (in poll()) for a
    # deterministic count; background firing is covered in
    # test_serve_faults.py
    sched = ServeScheduler(engine, max_batch=4, mesh=None,
                           max_wait_s=0.05, watchdog_s=0)
    c, f, m = _scene_cf(0, 40)
    rid = sched.submit(c, f, m)                 # 1/4: queued, not overdue
    assert sched.stats()["deadline_flushes"] == 0
    assert sched.stats()["queue_depth"] == 1
    time.sleep(0.06)
    results = sched.poll()                      # deadline fires here
    assert sched.stats()["deadline_flushes"] == 1
    res = {r.rid: r for r in results + sched.drain()}
    np.testing.assert_array_equal(res[rid].preds,
                                  _ref_preds(params, c, m, f))
    assert sched.stats()["buckets"][64]["dummy_scenes"] == 3


def test_per_bucket_max_batch_overrides_and_ladder_config():
    """Satellite: per-bucket micro-batch widths via a dict override or
    ladder-level config, seeded from occupancy telemetry."""
    params = _mini_params()
    engine = PointCloudEngine(params, n_stages=2, flow="fod",
                              ladder=geometric_ladder(64, 128))
    sched = ServeScheduler(engine, mesh=None,
                           max_batch={64: 2, "default": 4})
    assert sched.max_batch_for(64) == 2 and sched.max_batch_for(128) == 4
    sched.submit(*_scene_cf(0, 40))
    sched.submit(*_scene_cf(1, 40))             # width-2 bucket dispatches
    assert len(sched.drain()) == 2
    st = sched.stats()["buckets"][64]
    assert st["batches"] == 1 and st["dummy_scenes"] == 0
    assert st["max_batch"] == 2
    with pytest.raises(ValueError, match="not on the ladder"):
        ServeScheduler(engine, mesh=None, max_batch={999: 2})

    ladder = BucketLadder((64, 128), max_batch=(1, 2))
    engine2 = PointCloudEngine(params, n_stages=2, flow="fod",
                               ladder=ladder)
    sched2 = ServeScheduler(engine2, mesh=None)
    assert sched2.max_batch_for(64) == 1 and sched2.max_batch_for(128) == 2
    with pytest.raises(ValueError, match="one positive width"):
        BucketLadder((64, 128), max_batch=(2,))

    # occupancy telemetry -> suggested overrides (mean real scenes/batch)
    assert max_batch_from_occupancy(
        {64: {"scenes": 2, "batches": 2}, 128: {"scenes": 7, "batches": 2}},
        default=4) == {64: 1, 128: 4}


# ---------------------------------------------------------------------------
# chaos: concurrent producers through an injected FaultPlan
# ---------------------------------------------------------------------------

def test_chaos_concurrent_producers_with_injected_faults():
    """ISSUE-6 acceptance: concurrent producers stream mixed-size scenes
    through an injected FaultPlan (1 transient dispatch failure + 1
    NaN-corrupted scene + 1 oversized scene).  Every submitted rid
    resolves to predictions or a typed error, no exception escapes
    submit/flush/drain/serve, every surviving prediction is bit-identical
    to the fault-free per-scene reference, and the scheduler serves a
    clean follow-up stream afterwards."""
    from repro.serve.faults import FaultPlan

    # this test compiles several fresh full-model programs late in the
    # suite; drop the session's accumulated executables first so the
    # CPU backend's JIT doesn't run out of code space mid-compile
    jax.clear_caches()
    params = _mini_params()
    engine = PointCloudEngine(params, n_stages=2, flow="fod",
                              ladder=geometric_ladder(64, 128))
    plan = FaultPlan(fail_dispatches={0},   # first micro-batch wait fails
                     corrupt_scenes={5})    # 6th submit gets NaN feats
    sched = ServeScheduler(engine, max_batch=2, mesh=None,
                           fault_plan=plan)
    submitted = []

    def producer(t):
        for j in range(4):
            scene = _scene_cf(4 * t + j, 40 if j % 2 else 90)
            # rid pairs with its scene via locals; list.append is atomic
            submitted.append((sched.submit(*scene), scene))

    threads = [threading.Thread(target=producer, args=(t,))
               for t in range(3)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    # oversized scene last (its submit ordinal can't collide with the
    # corrupt_scenes ordinal, which lands on a producer submit)
    big_rid = sched.submit(*_scene_cf(99, 400))
    submitted.append((big_rid, None))
    sched.flush()
    results = {r.rid: r for r in sched.drain()}

    # every rid completed, exactly once, with preds XOR a typed error
    assert sorted(results) == sorted(rid for rid, _ in submitted)
    errors = {rid: r.error for rid, r in results.items()
              if r.error is not None}
    assert results[big_rid].error.code == "rejected"
    assert len(errors) == 2                 # corrupted + oversized
    assert all(e.code == "rejected" for e in errors.values())
    # surviving predictions are bit-identical to the no-fault reference
    # (per-scene vmap independence: the retried composition can't leak)
    n_ok = 0
    for rid, scene in submitted:
        if rid in errors:
            continue
        c, f, m = scene
        np.testing.assert_array_equal(results[rid].preds,
                                      _ref_preds(params, c, m, f))
        n_ok += 1
    assert n_ok == 11

    st = sched.stats()
    assert st["n_submitted"] == 13 and st["n_completed"] == 13
    assert st["faults"]["rejected"] == 2
    assert st["faults"]["exec_failed"] == 0  # transient failure retried
    assert st["faults"]["failed_dispatches"] == 1
    assert st["faults"]["retries"] >= 1
    assert st["faults"]["recovery_s"] is not None
    assert plan.stats()["failures_injected"] == 1
    assert plan.stats()["scenes_corrupted"] == 1

    # the stream survives: a clean follow-up batch serves normally
    follow = [_scene_cf(200 + i, 40) for i in range(2)]
    out = sched.serve(follow)
    assert len(out) == 2
    for rid, (c, f, m) in zip(sorted(out), follow):
        assert out[rid].ok
        np.testing.assert_array_equal(out[rid].preds,
                                      _ref_preds(params, c, m, f))


def test_engine_batched_levels_cache_per_scene():
    """levels_for(batched=True) stacks per-scene cached pyramids: a new
    batch composition around a repeated scene still hits."""
    params = _mini_params()
    engine = PointCloudEngine(params, n_stages=2, flow="fod",
                              ladder=geometric_ladder(128, 128))
    scenes = [lidar_scene(seed=70 + i, n_points=100, grid=20)
              for i in range(3)]
    coords = np.stack([c for c, _, _ in scenes])
    mask = np.stack([m for _, m, _ in scenes])
    _, hit = engine.levels_for(coords, mask, batched=True)
    assert not hit
    # reversed composition: every scene already cached
    _, hit = engine.levels_for(coords[::-1], mask[::-1], batched=True)
    assert hit
    assert engine.cache_stats()["hits"] == 3
