"""Seeded LiDAR-like scenes and the request stream built from them.

`city_scene` is a copy of `repro.data.synthetic.city_scene` (a ground
sheet plus towers), kept here so that no change to the program can move
the yardstick.

A run draws a pool of base scenes once, at set-up, and counts their work
once.  Every request is a base scene under a transform that no other
request of the run uses: an x/y shift by a multiple of 16 voxels, an
x and/or y reflection `x -> c - x` with `c = -1 (mod 16)`, and an
optional swap of x and y.  Each transform maps every stride-2^l grid
(l <= 4) onto itself, so every level's voxel and neighbour-pair counts
are those of the base scene, while the coordinate digest changes, so the
program's geometry caches miss as they would on a new sweep.  Features
are fresh random values per request.
"""

from __future__ import annotations

import numpy as np

GRID = 16            # transforms keep every stride-2^l grid, l <= 4
SHIFT_STEPS = 64     # shifts 0, 16, ..., 1008 voxels along x and y
REFLECT_C = 16 * 32 - 1
N_TRANSFORMS = SHIFT_STEPS * SHIFT_STEPS * 8


def city_scene(seed: int, n_points: int, extent: int | None = None,
               batch_idx: int = 0):
    """Ground sheet plus towers with roughly `n_points` unique voxels.

    Returns (coords (N, 4) int32 with the batch index in column 0, mask
    (N,), feats (N, 4)); valid rows are the unique voxels produced, first.
    """
    rng = np.random.default_rng(np.random.SeedSequence([seed, batch_idx]))
    if extent is None:
        extent = int(np.ceil(np.sqrt(n_points * 2.5)))
    m_ground = int(n_points * 1.1)
    ground = np.stack([rng.integers(0, extent, m_ground),
                       rng.integers(0, extent, m_ground),
                       rng.integers(0, 2, m_ground)], axis=1)
    towers = []
    n_towers = max(4, n_points // 4000)
    per = max(16, n_points // (4 * n_towers))
    for _ in range(n_towers):
        c = rng.integers(8, max(9, extent - 8), size=2)
        w = rng.integers(3, 9)
        h = rng.integers(6, 30)
        t = np.stack([c[0] + rng.integers(0, w, per),
                      c[1] + rng.integers(0, w, per),
                      rng.integers(0, h, per)], axis=1)
        towers.append(t)
    pts = np.concatenate([ground, *towers], axis=0)
    uniq = np.unique(np.clip(pts, 0, extent - 1), axis=0)
    uniq = uniq[rng.permutation(uniq.shape[0])[:n_points]]
    n = uniq.shape[0]
    coords = np.full((n_points, 4), 2**30 - 1, np.int32)
    coords[:n, 0] = batch_idx
    coords[:n, 1:] = uniq
    mask = np.zeros(n_points, bool)
    mask[:n] = True
    feats = np.zeros((n_points, 4), np.float32)
    feats[:n, :3] = uniq / extent - 0.5
    feats[:n, 3] = rng.random(n)
    return coords, mask, feats


def pool_sizes(lo: int, hi: int, n: int) -> list[int]:
    """`n` voxel counts spread evenly over [lo, hi]: the midpoints of n
    equal strata, the same for every seed, so every seed serves the same
    amount of work."""
    return [int(lo + (i + 0.5) * (hi - lo) / n) for i in range(n)]


def base_scenes(seed: int, sizes) -> list[np.ndarray]:
    """One (n, 3) int32 voxel set per size, every row a distinct voxel,
    in the seed's random row order."""
    out = []
    for i, n in enumerate(sizes):
        ss = np.random.SeedSequence([seed, i]).generate_state(1)[0]
        c, m, _ = city_scene(int(ss), n + n // 4)
        if int(m.sum()) < n:
            raise ValueError(f"base scene {i}: {int(m.sum())} voxels, "
                             f"need {n}")
        out.append(np.ascontiguousarray(c[:n, 1:], dtype=np.int32))
    return out


def transform(xyz: np.ndarray, t: int) -> np.ndarray:
    """Transform number `t` (0 <= t < N_TRANSFORMS) of an (n, 3) voxel set.

    t packs (dihedral, shift_x, shift_y); the dihedral part picks the
    x reflection, the y reflection and the x/y swap."""
    if not 0 <= t < N_TRANSFORMS:
        raise ValueError(f"transform {t} outside [0, {N_TRANSFORMS})")
    d, rest = divmod(t, SHIFT_STEPS * SHIFT_STEPS)
    sx, sy = divmod(rest, SHIFT_STEPS)
    x, y, z = xyz[:, 0], xyz[:, 1], xyz[:, 2]
    if d & 1:
        x = REFLECT_C - x
    if d & 2:
        y = REFLECT_C - y
    if d & 4:
        x, y = y, x
    return np.stack([x + GRID * sx, y + GRID * sy, z],
                    axis=1).astype(np.int32)


class RequestStream:
    """Request number r -> (base scene index, coords (n, 4), feats (n, C)).

    Base scenes come in blocks of len(pool): every block serves each base
    scene once, in the pool's order or, given an `order_seed`, in an order
    drawn from it.  Transforms are drawn from the seed without
    replacement.  Warm-up requests use negative numbers, which draw from
    the far end of the same permutation, so they never repeat a request
    of the window."""

    def __init__(self, seed: int, pool: list[np.ndarray], c_in: int,
                 order_seed: int | None = None):
        self.seed = seed
        self.pool = pool
        self.c_in = c_in
        self.order_seed = order_seed
        rng = np.random.default_rng(np.random.SeedSequence([seed, 7001]))
        self._transforms = rng.permutation(N_TRANSFORMS)
        self._orders: dict[int, np.ndarray] = {}

    def base_of(self, r: int) -> int:
        n = len(self.pool)
        block, i = divmod(r, n)
        if self.order_seed is None:
            return i
        order = self._orders.get(block)
        if order is None:
            rng = np.random.default_rng(np.random.SeedSequence(
                [self.order_seed, 7002, block % 2**31]))
            order = self._orders[block] = rng.permutation(n)
        return int(order[i])

    def request(self, r: int, base: int | None = None):
        """(base index, coords, feats) of request r; `base` overrides the
        block order (warm-up picks a scene of each bucket)."""
        b = self.base_of(r) if base is None else base
        xyz = transform(self.pool[b], int(self._transforms[r]))
        coords = np.zeros((xyz.shape[0], 4), np.int32)
        coords[:, 1:] = xyz
        rng = np.random.default_rng(
            np.random.SeedSequence([self.seed, 7003, r % 2**40]))
        feats = rng.uniform(-1.0, 1.0, (xyz.shape[0], self.c_in)) \
            .astype(np.float32)
        return b, coords, feats
