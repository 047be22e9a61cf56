"""Useful work of one scene, counted from its coordinates.

The benchmark's own numpy code (it imports nothing of the program): the
reference's neighbour search, and the useful work that a metric of a
share of the chip's peak reads (`Context.work` of `bench/run.py`), the
same whatever flow, capacity or padding the program uses.

For each stride level l the voxels are the unique rows of
floor(coords / 2^l).  A submanifold k=3 conv pairs every voxel with each
present voxel of its 3x3x3 neighbourhood (itself included), found by a
search over sorted keys; the stride-2 down conv and the transposed up
conv pair every fine voxel with its one parent.  A conv costs
2 * pairs * cin * cout FLOPs; the 1x1 block projections and the head
cost 2 * rows * cin * cout.  The least bytes of a conv are its input and
output activations and its weights, read or written once in float32:
(N_in * cin + N_out * cout + weights) * 4.
"""

from __future__ import annotations

import numpy as np

BITS = 21
BIAS = 1 << (BITS - 1)
SUBM_OFFSETS = np.stack(np.meshgrid(*([np.arange(-1, 2)] * 3),
                                    indexing="ij"), -1).reshape(-1, 3)
CHILD_OFFSETS = np.stack(np.meshgrid(*([np.arange(0, 2)] * 3),
                                     indexing="ij"), -1).reshape(-1, 3)
F32 = 4


def keys(xyz: np.ndarray) -> np.ndarray:
    """One int64 key per (n, 3) integer row, ordered lexicographically."""
    v = xyz.astype(np.int64) + BIAS
    if v.min(initial=0) < 0 or v.max(initial=0) >= (1 << BITS):
        raise ValueError("coordinates outside the key range")
    return (v[:, 0] << (2 * BITS)) | (v[:, 1] << BITS) | v[:, 2]


def unkeys(k: np.ndarray) -> np.ndarray:
    """The (n, 3) rows of `keys`."""
    m = (1 << BITS) - 1
    return np.stack([(k >> (2 * BITS)) & m, (k >> BITS) & m, k & m],
                    axis=1) - BIAS


def offset_keys(offsets: np.ndarray) -> np.ndarray:
    """Key increments of small offsets: keys(x + o) = keys(x) + this, as no
    field of a key in range leaves [0, 2^BITS) by a step of one."""
    o = offsets.astype(np.int64)
    return (o[:, 0] << (2 * BITS)) + (o[:, 1] << BITS) + o[:, 2]


def pyramid(xyz: np.ndarray, n_levels: int) -> list[np.ndarray]:
    """Voxels of each level in that level's own units: level 0 is `xyz`
    in its row order, level l > 0 the sorted unique rows of
    floor(xyz / 2^l)."""
    levels = [np.asarray(xyz, np.int64)]
    for _ in range(1, n_levels):
        levels.append(unkeys(np.unique(keys(levels[-1] >> 1))))
    return levels


def lookup(table_keys: np.ndarray, query: np.ndarray) -> np.ndarray:
    """Row of `table_keys` (unique, any order) holding each query key, or
    len(table_keys) where none does."""
    order = np.argsort(table_keys, kind="stable")
    sk = table_keys[order]
    pos = np.searchsorted(sk, query)
    posc = np.minimum(pos, len(sk) - 1)
    hit = (pos < len(sk)) & (sk[posc] == query)
    return np.where(hit, order[posc], len(sk))


def subm_table(level: np.ndarray) -> np.ndarray:
    """(n, 27) input row of each output row under each k=3 offset, or n
    where that neighbour is absent.  Column k is offset SUBM_OFFSETS[k]."""
    k = keys(level)
    q = k[:, None] + offset_keys(SUBM_OFFSETS)[None, :]
    return lookup(k, q.reshape(-1)).reshape(len(level), 27)


def down_table(fine: np.ndarray, coarse: np.ndarray) -> np.ndarray:
    """(n_coarse, 8) fine row of each coarse voxel's child 2q + o, or
    n_fine where that child is absent.  Column k is CHILD_OFFSETS[k]."""
    q = keys(2 * coarse)[:, None] + offset_keys(CHILD_OFFSETS)[None, :]
    return lookup(keys(fine), q.reshape(-1)).reshape(len(coarse), 8)


def up_index(fine: np.ndarray, coarse: np.ndarray):
    """(parent row, child offset index) of every fine voxel."""
    parent = lookup(keys(coarse), keys(fine >> 1))
    o = fine - 2 * (fine >> 1)
    return parent, o[:, 0] * 4 + o[:, 1] * 2 + o[:, 2]


def level_counts(xyz: np.ndarray, n_levels: int) -> dict:
    """Voxels and submanifold pairs (self-pairs included) per level."""
    levels = pyramid(xyz, n_levels)
    pairs = []
    for lv in levels:
        t = subm_table(lv)
        pairs.append(int((t < len(lv)).sum()))
    return {"voxels": [len(lv) for lv in levels], "subm_pairs": pairs}


def scene_work(cfg: dict, counts: dict) -> dict:
    """FLOPs and least bytes of one forward pass of the configuration's
    MinkUNet over a scene with these level counts."""
    n_stages = len(cfg["enc_planes"])
    nv, sp = counts["voxels"], counts["subm_pairs"]
    flops = 0
    nbytes = 0

    def conv(pairs, n_in, n_out, k, cin, cout):
        nonlocal flops, nbytes
        flops += 2 * pairs * cin * cout
        nbytes += (n_in * cin + n_out * cout + k * cin * cout) * F32

    def block(level, cin, cout):
        conv(sp[level], nv[level], nv[level], 27, cin, cout)
        conv(sp[level], nv[level], nv[level], 27, cout, cout)
        if cin != cout:
            conv(nv[level], nv[level], nv[level], 1, cin, cout)

    c = cfg["stem"]
    conv(sp[0], nv[0], nv[0], 27, cfg["c_in"], c)
    skip_cs = [c]
    for i, planes in enumerate(cfg["enc_planes"]):
        conv(nv[i], nv[i], nv[i + 1], 8, c, planes)
        cb = c = planes
        for _ in range(cfg["blocks_per_stage"]):
            block(i + 1, cb, planes)
            cb = planes
        skip_cs.append(planes)
    for i, planes in enumerate(cfg["dec_planes"]):
        fine = n_stages - 1 - i
        conv(nv[fine], nv[fine + 1], nv[fine], 8, c, planes)
        cb = planes + skip_cs[fine]
        for _ in range(cfg["blocks_per_stage"]):
            block(fine, cb, planes)
            cb = planes
        c = planes
    conv(nv[0], nv[0], nv[0], 1, c, cfg["n_classes"])
    return {"flops": flops, "bytes": nbytes}
