"""CPU tests of the benchmark's work counts and scene transforms."""

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import count as C  # noqa: E402
from bench import scenes as S  # noqa: E402


def brute_pairs(level: np.ndarray) -> int:
    """Ordered pairs of voxels within one step along every axis."""
    d = np.abs(level[:, None, :] - level[None, :, :]).max(-1)
    return int((d <= 1).sum())


def small_cloud(seed: int, n: int = 80, extent: int = 9) -> np.ndarray:
    rng = np.random.default_rng(seed)
    pts = rng.integers(-extent, extent, size=(3 * n, 3))
    return np.unique(pts, axis=0)[:n]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_pairs_match_brute_force(seed):
    xyz = small_cloud(seed)
    counts = C.level_counts(xyz, 4)
    for lv, voxels, pairs in zip(C.pyramid(xyz, 4), counts["voxels"],
                                 counts["subm_pairs"]):
        assert voxels == len(np.unique(lv, axis=0))
        assert pairs == brute_pairs(lv)


def test_down_and_up_tables_pair_each_fine_voxel_once():
    xyz = small_cloud(3)
    fine, coarse = C.pyramid(xyz, 2)
    down = C.down_table(fine, coarse)
    hits = down[down < len(fine)]
    assert sorted(hits.tolist()) == list(range(len(fine)))
    parent, k = C.up_index(fine, coarse)
    assert np.array_equal(2 * coarse[parent] + C.CHILD_OFFSETS[k], fine)
    for q in range(len(coarse)):
        for j in range(8):
            if down[q, j] < len(fine):
                assert parent[down[q, j]] == q and k[down[q, j]] == j


def test_subm_table_offsets():
    xyz = small_cloud(4)
    t = C.subm_table(xyz)
    for row in range(len(xyz)):
        for j in range(27):
            if t[row, j] < len(xyz):
                assert np.array_equal(xyz[t[row, j]],
                                      xyz[row] + C.SUBM_OFFSETS[j])
    assert (t[:, 13] == np.arange(len(xyz))).all()     # the self offset


def test_transforms_keep_every_level_count():
    (base,) = S.base_scenes(5, [1500])
    want = C.level_counts(base, 5)
    rng = np.random.default_rng(0)
    for t in [0, S.N_TRANSFORMS - 1, *rng.integers(0, S.N_TRANSFORMS, 12)]:
        moved = S.transform(base, int(t))
        assert C.level_counts(moved, 5) == want
        assert not np.array_equal(moved, base) or t == 0


def test_request_stream_geometry_never_repeats():
    pool = S.base_scenes(6, S.pool_sizes(200, 400, 4))
    stream = S.RequestStream(6, pool, 4, order_seed=9)
    seen = set()
    for r in list(range(40)) + [-1, -2, -3]:
        b, coords, feats = stream.request(r)
        key = coords.tobytes()
        assert key not in seen
        seen.add(key)
        assert coords.shape == (len(pool[b]), 4) and feats.shape[1] == 4
        assert coords[:, 1:].min() >= 0 and coords.max() < 2**15
    assert sorted(stream.base_of(r) for r in range(4)) == [0, 1, 2, 3]


def test_scene_work_of_one_voxel():
    cfg = {"c_in": 4, "n_classes": 19, "stem": 16, "enc_planes": [16, 32],
           "dec_planes": [32, 16], "blocks_per_stage": 1}
    counts = {"voxels": [1, 1, 1], "subm_pairs": [1, 1, 1]}
    w = C.scene_work(cfg, counts)
    # one pair per conv: stem; encoder down + two block convs per stage;
    # decoder up + conv1 (on the concat) + conv2 + projection; head
    stem = 4 * 16
    enc = (16 * 16 + 2 * 16 * 16) + (16 * 32 + 2 * 32 * 32)
    dec = (32 * 32 + 48 * 32 + 32 * 32 + 48 * 32) + \
        (32 * 16 + 32 * 16 + 16 * 16 + 32 * 16)
    want = 2 * (stem + enc + dec + 16 * 19)
    assert w["flops"] == want
