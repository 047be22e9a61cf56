"""Paper Fig. 17 (left): kernel mapping — mergesort-based (PointAcc) vs
hash-table-based (state-of-the-art GPU baseline).

The paper's finding: on CPU/GPU the mergesort algorithm is *slower* than
hashing, but it parallelises into a 14x-smaller circuit; on TPU the story
repeats as 'sort-based maps onto XLA's native sorting network, hashing
vectorises terribly'.  We measure on synthetic LiDAR scenes:
  * sort      — v1 engine: one lexicographic merge-sort per kernel offset
  * packed_v2 — v2 engine: pack coords to one 62-bit key, sort the cloud
                ONCE, binary-search each offset (timed end-to-end including
                the sort, with a parity assert against the hash baseline)
  * hash      — dict-based point lookup (the CPU implementation of [35])
  * bruteforce — O(N*M) coordinate-equality matching, the naive vector form
"""

from __future__ import annotations

import argparse

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import emit, timeit
from repro.core import mapping as M
from repro.data.synthetic import lidar_scene


def hash_kernel_map(coords, mask, out_coords, out_mask, offsets):
    table = {tuple(c): i for i, c in enumerate(coords) if mask[i]}
    n_maps = 0
    for d in offsets:
        for j, q in enumerate(out_coords):
            if out_mask[j]:
                p = (q[0], q[1] + d[0], q[2] + d[1], q[3] + d[2])
                if p in table:
                    n_maps += 1
    return n_maps


def bruteforce_kernel_map(coords, mask, offsets_full):
    # (K, N, M) equality over coordinates, vectorised
    shifted = coords[None] - offsets_full[:, None]           # (K, N, 4)
    eq = (shifted[:, :, None, :] == coords[None, None]).all(-1)
    eq &= (mask[None, :, None] & mask[None, None, :])
    return eq.sum()


def run(n_points: int = 4096):
    coords_np, mask_np, _ = lidar_scene(0, n_points, grid=64)
    pc = M.make_point_cloud(jnp.asarray(coords_np), jnp.asarray(mask_np))

    kmap = jax.jit(lambda c, m: M.kernel_map(
        M.PointCloud(c, m, 1), M.PointCloud(c, m, 1), 3))
    us_sort = timeit(kmap, pc.coords, pc.mask)
    maps = kmap(pc.coords, pc.mask)
    n_maps = int(jnp.sum(maps.valid))
    emit(f"mapping/sort_n{n_points}", us_sort, f"maps={n_maps}")

    # v2: timed end-to-end — the single ranking sort is inside the lambda,
    # so the speedup is the real per-layer cost ratio, not sort-amortised.
    def kmap2_fn(c, m):
        sc = M.sort_cloud(M.PointCloud(c, m, 1))
        return M.kernel_map_v2(sc, sc, 3)

    kmap2 = jax.jit(kmap2_fn)
    us_v2 = timeit(kmap2, pc.coords, pc.mask)
    maps2 = kmap2(pc.coords, pc.mask)
    n_v2 = int(jnp.sum(maps2.valid))
    emit(f"mapping/packed_v2_n{n_points}", us_v2,
         f"maps={n_v2};speedup_vs_sort={us_sort / us_v2:.2f}x")

    offs = M.kernel_offsets(3, 3, 1)
    import time
    t0 = time.perf_counter()
    n_hash = hash_kernel_map(coords_np, mask_np, coords_np, mask_np, offs)
    us_hash = (time.perf_counter() - t0) * 1e6
    emit(f"mapping/hash_n{n_points}", us_hash, f"maps={n_hash}")
    assert n_hash == n_maps, (n_hash, n_maps)
    # parity: the v2 engine finds exactly the hash baseline's map count
    assert n_v2 == n_hash, (n_v2, n_hash)

    if n_points <= 4096:
        offs_full = jnp.asarray(
            np.concatenate([np.zeros((27, 1), np.int32), offs], 1))
        bf = jax.jit(bruteforce_kernel_map)
        us_bf = timeit(bf, pc.coords, pc.mask, offs_full)
        emit(f"mapping/bruteforce_n{n_points}", us_bf,
             f"speedup_vs_bf={us_bf / us_sort:.1f}x")

    emit(f"mapping/summary_n{n_points}", us_sort,
         f"sort_vs_hash={us_hash / us_sort:.2f}x;"
         f"v2_vs_sort={us_sort / us_v2:.2f}x")
    return us_sort, us_v2


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="single small size (CI smoke)")
    args = ap.parse_args(argv)
    for n in (1024,) if args.smoke else (1024, 4096, 16384):
        run(n)


if __name__ == "__main__":
    main()
