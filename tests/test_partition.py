"""City-scale partitioning (repro.partition): octree range splitting
over the 62-bit packed keys, exact receptive-field halos, chunk-streamed
serving through the scheduler, and the halo-exactness acceptance —
chunked predictions equal the monolithic network's on every valid row,
for all three conv flows.  The border behaviour of the underlying
mapping ops (`downsample_sorted` / `kernel_map_v2` at chunk boundaries,
including stride cells straddling a split) is pinned at the map level."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import mapping as M
from repro.core import packed as PK
from repro.data.synthetic import city_scene, lidar_scene
from repro.models import minkunet as MU
from repro.partition import HaloSpec, PartitionPolicy, plan_partition, \
    split_ranges
from repro.partition.halo import build_pyramid
from repro.partition.octree import rank_keys
from repro.serve import faults as FLT
from repro.serve.buckets import geometric_ladder
from repro.serve.engine import PointCloudEngine


def _mini_params(n_classes=2):
    return MU.mini_minkunet_init(jax.random.key(0), c_in=4,
                                 n_classes=n_classes)


def _ref_preds(params, coords, mask, feats, flow="fod"):
    pc = M.make_point_cloud(jnp.asarray(coords), jnp.asarray(mask))
    logits = MU.minkunet_apply(params, pc, jnp.asarray(feats), flow=flow)
    return np.asarray(jnp.argmax(logits, -1))


def _rand_sorted_keys(rng, n, dup_frac=0.3):
    """Sorted packed keys of random in-budget coords, with deliberate
    duplicates (multi-row sites)."""
    coords = np.concatenate(
        [rng.integers(0, PK.BATCH_MAX + 1, size=(n, 1)),
         rng.integers(PK.COORD_MIN, PK.COORD_MAX + 1, size=(n, 3))],
        axis=1).astype(np.int64)
    n_dup = int(n * dup_frac)
    coords[:n_dup] = coords[rng.integers(n_dup, n, size=n_dup)]
    return np.sort(PK.pack_coords_host(coords))


# ---------------------------------------------------------------------------
# octree range splitting
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("budget", [1, 7, 64, 10_000])
def test_split_ranges_invariants(budget):
    """Coverage, ordering, budget bound, and the no-split-equal-keys
    guarantee, on keys with duplicate sites."""
    rng = np.random.default_rng(5)
    keys = _rand_sorted_keys(rng, 400)
    ranges = split_ranges(keys, budget)

    # exact disjoint cover of [0, n) in order
    assert ranges[0][0] == 0 and ranges[-1][1] == keys.shape[0]
    for (s, e), (s2, _) in zip(ranges, ranges[1:]):
        assert s < e and e == s2
    for s, e in ranges:
        # a leaf over budget is only legal when the trie ran out of bits
        # — i.e. every key in the leaf is the same site
        if e - s > budget:
            assert (keys[s:e] == keys[s]).all()
        # equal keys are never separated across a boundary
        if s > 0:
            assert keys[s - 1] != keys[s]


def test_split_ranges_equal_keys_stay_together():
    keys = np.full(17, 12345, np.uint64)
    assert split_ranges(keys, 1) == [(0, 17)]
    assert split_ranges(np.empty(0, np.uint64), 4) == []


def test_rank_keys_orders_valid_rows_first():
    coords, mask, _ = lidar_scene(seed=2, n_points=120, grid=16)
    keys, order, n_valid = rank_keys(coords, mask)
    assert n_valid == int(mask.sum())
    # ascending keys, sentinels (invalid rows) ranked last
    assert (np.diff(keys.astype(np.uint64)) >= 0).all()
    assert (keys[:n_valid] < PK.KEY64_SENTINEL).all()
    assert (keys[n_valid:] == PK.KEY64_SENTINEL).all()
    assert mask[order[:n_valid]].all() and not mask[order[n_valid:]].any()
    # keys really are the packed coords of the ranked rows
    np.testing.assert_array_equal(
        keys[:n_valid], PK.pack_coords_host(coords[order[:n_valid]]))


# ---------------------------------------------------------------------------
# plan: ownership and halo accounting
# ---------------------------------------------------------------------------

def test_every_valid_point_is_interior_to_exactly_one_chunk():
    """Border ownership: wherever the octree cuts, each valid row lands
    in exactly one chunk's interior; halo rows are duplicates on top."""
    coords, mask, feats = city_scene(seed=4, n_points=1500)
    ladder = geometric_ladder(128, 2048)
    plan = plan_partition(coords, mask, feats,
                          spec=HaloSpec.uniform(2, 1), ladder=ladder,
                          policy=PartitionPolicy(chunk_budget=256,
                                                 force=True))
    assert plan.n_chunks > 1
    owned = np.concatenate([c.rows[c.interior] for c in plan.chunks])
    assert owned.shape[0] == int(mask.sum())          # no row lost ...
    assert np.unique(owned).shape[0] == owned.shape[0]  # ... or doubled
    assert set(owned) == set(np.flatnonzero(mask))
    for c in plan.chunks:
        assert c.mask.all() and c.n_points <= ladder.capacities[-1]
        np.testing.assert_array_equal(c.coords, coords[c.rows])
        np.testing.assert_array_equal(c.feats, feats[c.rows])
    assert 0.0 <= plan.halo_fraction < 1.0
    assert plan.stats()["halo_rows"] == sum(c.n_halo for c in plan.chunks)


def test_stitch_marks_failed_chunks_and_invalid_rows():
    coords, mask, feats = lidar_scene(seed=6, n_points=200, grid=16)
    plan = plan_partition(coords, mask, feats,
                          spec=HaloSpec.uniform(2, 1),
                          ladder=geometric_ladder(64, 512),
                          policy=PartitionPolicy(chunk_budget=48,
                                                 force=True))
    assert plan.n_chunks >= 2
    preds = [np.full(c.n_points, 7, np.int32) for c in plan.chunks]
    preds[0] = None                                   # a failed chunk
    out = plan.stitch(preds)
    dead = plan.chunks[0].rows[plan.chunks[0].interior]
    assert (out[dead] == -1).all()
    assert (out[~mask] == -1).all()
    alive = np.concatenate([c.rows[c.interior] for c in plan.chunks[1:]])
    assert (out[alive] == 7).all()


def test_policy_validation_and_unpartitionable_scene():
    coords, mask, feats = lidar_scene(seed=8, n_points=600, grid=12)
    spec = HaloSpec.uniform(2, 1)
    with pytest.raises(ValueError, match="chunk_budget"):
        plan_partition(coords, mask, feats, spec=spec,
                       ladder=geometric_ladder(64, 128),
                       policy=PartitionPolicy(chunk_budget=4096))
    # a dense 12^3 blob's receptive-field ball cannot fit a 128-row top
    # bucket at any budget: planning must fail loudly, not silently drop
    with pytest.raises(ValueError, match="halo outgrows the ladder"):
        plan_partition(coords, mask, feats, spec=spec,
                       ladder=geometric_ladder(64, 128),
                       policy=PartitionPolicy(chunk_budget=64, force=True))


def test_halo_spec_from_params():
    spec = MU.halo_spec(_mini_params())
    assert spec == HaloSpec.uniform(2, 1)
    assert spec.n_stages == 2
    assert spec.dec_rounds == (2, 2) and spec.enc_rounds == (1, 2, 2)


# ---------------------------------------------------------------------------
# mapping ops at chunk borders (downsample_sorted / kernel_map_v2)
# ---------------------------------------------------------------------------

def _subm_neighbor_sets(coords, k=3):
    """{point coord -> frozenset of matched k^3 neighbour coords} via
    kernel_map_v2's inverse table (all rows valid)."""
    mask = np.ones(coords.shape[0], bool)
    pc = M.make_point_cloud(jnp.asarray(coords), jnp.asarray(mask))
    sc = M.sort_cloud(pc)
    inv = np.asarray(M.kernel_map_v2(sc, sc, k).inv)
    cn = np.asarray(pc.coords)
    return {tuple(cn[j]): frozenset(tuple(cn[inv[o, j]])
                                    for o in range(inv.shape[0])
                                    if inv[o, j] >= 0)
            for j in range(coords.shape[0])}


def _down_member_sets(coords):
    """{stride-2 cell coord -> frozenset of its member point coords} via
    downsample_sorted + the k=2 kernel map (all rows valid)."""
    mask = np.ones(coords.shape[0], bool)
    pc = M.make_point_cloud(jnp.asarray(coords), jnp.asarray(mask))
    sc0 = M.sort_cloud(pc)
    sc1 = M.downsample_sorted(sc0)
    inv = np.asarray(M.kernel_map_v2(sc0, sc1, 2).inv)
    c0 = np.asarray(pc.coords)
    c1, m1 = np.asarray(sc1.pc.coords), np.asarray(sc1.pc.mask)
    return {tuple(c1[j]): frozenset(tuple(c0[inv[o, j]])
                                    for o in range(inv.shape[0])
                                    if inv[o, j] >= 0)
            for j in range(c1.shape[0]) if m1[j]}


def test_chunk_border_maps_match_monolithic_on_interior():
    """A straddling-stride split: collinear points along z cut mid cell-
    pair (the octree's lowest split bit is z's bit 0, so stride-2 cell
    partners CAN land in different chunks).  Every boundary point must be
    interior to exactly one chunk, and on interior sites both the k=3
    submanifold map and the stride-2 downsample map of the halo'd chunk
    cloud must match the monolithic cloud's exactly."""
    n = 16
    coords = np.zeros((n, 4), np.int32)
    coords[:, 3] = np.arange(n)                       # a line along z
    mask = np.ones(n, bool)
    feats = np.zeros((n, 4), np.float32)
    plan = plan_partition(coords, mask, feats,
                          spec=HaloSpec.uniform(1, 1),
                          ladder=geometric_ladder(8, 64),
                          policy=PartitionPolicy(chunk_budget=2,
                                                 force=True))
    assert plan.n_chunks == n // 2
    # cell partners {2k, 2k+1} really do straddle chunk boundaries:
    # interiors are 2-point ranges, so every odd z is a border
    interiors = sorted(tuple(sorted(c.coords[c.interior][:, 3]))
                       for c in plan.chunks)
    assert interiors == [(2 * k, 2 * k + 1) for k in range(n // 2)]

    mono_subm = _subm_neighbor_sets(coords)
    mono_down = _down_member_sets(coords)
    for chunk in plan.chunks:
        sub = _subm_neighbor_sets(chunk.coords)
        down = _down_member_sets(chunk.coords)
        for p in map(tuple, chunk.coords[chunk.interior]):
            assert sub[p] == mono_subm[p]
        # every stride-2 cell owned by an interior point is present in
        # the chunk's downsampled cloud with its full member set
        cells = {tuple(q) for q in
                 np.asarray(M.quantize_coords(
                     jnp.asarray(chunk.coords[chunk.interior]), 2))}
        for cell in cells:
            assert down[cell] == mono_down[cell]


def test_build_pyramid_matches_downsample_sorted():
    """The host-side key pyramid the halo walk uses = the device
    `downsample_sorted` pyramid, level by level."""
    coords, mask, _ = lidar_scene(seed=9, n_points=300, grid=16)
    keys, _, n_valid = rank_keys(coords, mask)
    pyr = build_pyramid(np.unique(keys[:n_valid]), n_stages=2)
    sc = M.sort_cloud(M.make_point_cloud(jnp.asarray(coords),
                                         jnp.asarray(mask)))
    for level in range(3):
        cn = np.asarray(sc.pc.coords)[np.asarray(sc.pc.mask)]
        np.testing.assert_array_equal(
            pyr.levels[level], np.sort(PK.pack_coords_host(cn)))
        if level < 2:
            sc = M.downsample_sorted(sc)


# ---------------------------------------------------------------------------
# acceptance: chunked == monolithic, oversized completes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("flow", ["fod", "pallas", "pallas_fused"])
def test_forced_partition_matches_monolithic(flow):
    """Halo exactness end to end: a scene that fits the ladder, served
    monolithically and force-chunked, gives identical class ids on every
    valid row (and -1 on masked rows), for all three conv flows."""
    params = _mini_params()
    engine = PointCloudEngine(params, n_stages=2, flow=flow,
                              ladder=geometric_ladder(128, 512))
    coords, mask, feats = lidar_scene(seed=12, n_points=400, grid=16)
    mono, _ = engine.segment(coords, mask, feats)
    part, _ = engine.segment(
        coords, mask, feats,
        partition=PartitionPolicy(chunk_budget=96, force=True))
    part = np.asarray(part)
    assert engine.last_partition_stats["n_chunks"] > 1
    assert engine.last_partition_stats["chunk_errors"] == 0
    np.testing.assert_array_equal(part[mask], np.asarray(mono)[mask])
    assert (part[~mask] == -1).all()
    np.testing.assert_array_equal(
        part[mask], _ref_preds(params, coords, mask, feats, flow)[mask])


def test_oversized_scene_completes_via_partition():
    """The PR's headline: a scene the seed path rejects — segment()
    raises, the scheduler returns a typed `rejected`/`oversized` result
    whose message carries the ladder max and the packed-key budget —
    completes through segment(partition='auto') and matches the
    reference network output exactly."""
    params = _mini_params()
    ladder = geometric_ladder(256, 2048)
    engine = PointCloudEngine(params, n_stages=2, flow="fod",
                              ladder=ladder)
    coords, mask, feats = city_scene(seed=15, n_points=3000)

    with pytest.raises(ValueError, match="exceeds the bucket ladder"):
        engine.segment(coords, mask, feats)
    sched = engine.scheduler()
    res = sched.take([sched.submit(coords, feats, mask)]).popitem()[1]
    assert res.error is not None
    assert res.error.code == FLT.REJECTED
    assert res.error.detail == FLT.OVERSIZED          # vs "malformed"
    assert str(ladder.capacities[-1]) in res.error.message
    assert "packed-key budget" in res.error.message
    assert "partition" in res.error.message
    # ... while a malformed scene is distinguishable by detail
    bad = feats.copy()
    bad[mask.argmax()] = np.nan
    r2 = sched.take([sched.submit(coords, bad, mask)]).popitem()[1]
    assert r2.error.code == FLT.REJECTED
    assert r2.error.detail == FLT.MALFORMED

    preds, hit = engine.segment(coords, mask, feats, partition="auto")
    preds = np.asarray(preds)
    st = engine.last_partition_stats
    assert st["n_chunks"] > 1 and st["chunk_errors"] == 0
    assert st["max_chunk_points"] <= ladder.capacities[-1]
    np.testing.assert_array_equal(
        preds[mask], _ref_preds(params, coords, mask, feats)[mask])
    assert (preds[~mask] == -1).all()

    # a repeated frame hits the mapping cache chunk by chunk
    again, hit = engine.segment(coords, mask, feats, partition="auto")
    assert hit is True
    np.testing.assert_array_equal(np.asarray(again), preds)
