"""Mapping Unit tests: ranking-based ops vs brute-force oracles."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from tests.hypothesis_compat import given, settings, st

from repro.core import mapping as M
from repro.core import pointops as P


def random_cloud(rng, n_valid, cap, grid=16, batches=2, d=3):
    """Unique random integer coords with batch column, sentinel padded."""
    seen = set()
    pts = []
    while len(pts) < n_valid:
        c = (rng.integers(0, batches),) + tuple(
            int(x) for x in rng.integers(0, grid, size=d))
        if c not in seen:
            seen.add(c)
            pts.append(c)
    coords = np.full((cap, 1 + d), M.SENTINEL, np.int32)
    coords[:n_valid] = np.array(pts, np.int32)
    mask = np.zeros(cap, bool)
    mask[:n_valid] = True
    # shuffle so valid entries are not contiguous
    perm = rng.permutation(cap)
    return coords[perm], mask[perm]


def oracle_kernel_map(coords, mask, out_coords, out_mask, offsets):
    """dict-based (hash-table) reference: the implementation PointAcc
    replaces.  For output q and offset d, input p = q + d."""
    table = {tuple(c): i for i, c in enumerate(coords) if mask[i]}
    per_offset = []
    for d in offsets:
        pairs = set()
        for j, q in enumerate(out_coords):
            if not out_mask[j]:
                continue
            p = (q[0],) + tuple(q[1:] + d)
            if p in table:
                pairs.add((table[p], j))
        per_offset.append(pairs)
    return per_offset


def maps_to_sets(maps):
    k = maps.in_idx.shape[0]
    out = []
    for i in range(k):
        v = np.asarray(maps.valid[i])
        out.append(set(zip(np.asarray(maps.in_idx[i])[v].tolist(),
                           np.asarray(maps.out_idx[i])[v].tolist())))
    return out


# ---------------------------------------------------------------------------
# quantization
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("stride", [2, 4, 8])
def test_quantize_matches_floor(stride):
    rng = np.random.default_rng(0)
    coords = np.concatenate(
        [rng.integers(0, 2, (64, 1)),
         rng.integers(-64, 64, (64, 3))], axis=1).astype(np.int32)
    q = np.asarray(M.quantize_coords(jnp.asarray(coords), stride))
    expect = np.floor(coords[:, 1:] / stride).astype(np.int64) * stride
    np.testing.assert_array_equal(q[:, 1:], expect)
    np.testing.assert_array_equal(q[:, 0], coords[:, 0])


def test_quantize_idempotent():
    rng = np.random.default_rng(1)
    coords = np.concatenate(
        [np.zeros((32, 1), np.int32),
         rng.integers(-32, 32, (32, 3)).astype(np.int32)], axis=1)
    q1 = M.quantize_coords(jnp.asarray(coords), 4)
    q2 = M.quantize_coords(q1, 4)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))


# ---------------------------------------------------------------------------
# unique / downsample (output cloud construction)
# ---------------------------------------------------------------------------

def test_unique_coords_matches_numpy():
    rng = np.random.default_rng(2)
    coords, mask = random_cloud(rng, 40, 64, grid=4)  # small grid -> dupes
    coords = np.array(M.quantize_coords(jnp.asarray(coords), 2))
    coords[~mask] = M.SENTINEL
    got_c, got_m = M.unique_coords(jnp.asarray(coords), jnp.asarray(mask))
    got = set(map(tuple, np.asarray(got_c)[np.asarray(got_m)].tolist()))
    expect = set(map(tuple, coords[mask].tolist()))
    assert got == expect
    # compacted: valid entries at the front
    gm = np.asarray(got_m)
    assert not np.any(gm[np.argmin(gm):]) or gm.all()


def test_downsample_halves_resolution():
    rng = np.random.default_rng(3)
    coords, mask = random_cloud(rng, 50, 64, grid=8)
    pc = M.make_point_cloud(jnp.asarray(coords), jnp.asarray(mask), stride=1)
    down = M.downsample(pc, 2)
    assert down.stride == 2
    dc = np.asarray(down.coords)[np.asarray(down.mask)]
    assert np.all(dc[:, 1:] % 2 == 0)
    expect = {tuple([c[0]] + [(x // 2) * 2 for x in c[1:]])
              for c in coords[mask].tolist()}
    assert set(map(tuple, dc.tolist())) == expect


# ---------------------------------------------------------------------------
# kernel mapping: sort-merge intersection vs hash oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kernel_size,stride", [(3, 1), (2, 2), (3, 2)])
def test_kernel_map_vs_oracle(kernel_size, stride):
    rng = np.random.default_rng(4)
    coords, mask = random_cloud(rng, 60, 96, grid=10)
    pc = M.make_point_cloud(jnp.asarray(coords), jnp.asarray(mask), stride=1)
    maps, out_pc = M.build_conv_maps(pc, kernel_size, stride)
    oc, om = np.asarray(out_pc.coords), np.asarray(out_pc.mask)
    expect = oracle_kernel_map(np.asarray(pc.coords), np.asarray(pc.mask),
                               oc, om, maps.offsets)
    got = maps_to_sets(maps)
    for k in range(len(expect)):
        assert got[k] == expect[k], f"offset {maps.offsets[k]}"


def test_kernel_map_submanifold_center_identity():
    """stride-1 center offset must map every valid point to itself."""
    rng = np.random.default_rng(5)
    coords, mask = random_cloud(rng, 30, 48)
    pc = M.make_point_cloud(jnp.asarray(coords), jnp.asarray(mask))
    maps, out_pc = M.build_conv_maps(pc, 3, 1)
    center = int(np.where((maps.offsets == 0).all(1))[0][0])
    got = maps_to_sets(maps)[center]
    assert got == {(i, i) for i in range(48) if mask[i]}


@settings(max_examples=25, deadline=None)
@given(n=st.integers(5, 40), grid=st.integers(3, 12), seed=st.integers(0, 99))
def test_kernel_map_property(n, grid, seed):
    """Property: sort-merge intersection == hash oracle on random clouds."""
    rng = np.random.default_rng(seed)
    cap = n + rng.integers(0, 8)
    coords, mask = random_cloud(rng, n, cap, grid=grid)
    pc = M.make_point_cloud(jnp.asarray(coords), jnp.asarray(mask))
    maps, out_pc = M.build_conv_maps(pc, 3, 1)
    expect = oracle_kernel_map(np.asarray(pc.coords), np.asarray(pc.mask),
                               np.asarray(out_pc.coords),
                               np.asarray(out_pc.mask), maps.offsets)
    got = maps_to_sets(maps)
    assert all(g == e for g, e in zip(got, expect))


# ---------------------------------------------------------------------------
# v2 packed-key engine vs v1 lexicographic engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kernel_size,stride", [(3, 1), (2, 2), (3, 2)])
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_engines_agree(kernel_size, stride, seed):
    """v2 kernel_map must equal v1 up to per-offset ordering, and produce
    bit-identical output clouds, on randomized (shuffled, masked) clouds."""
    rng = np.random.default_rng(100 + seed)
    n = int(rng.integers(20, 90))
    coords, mask = random_cloud(rng, n, n + int(rng.integers(0, 16)),
                                grid=int(rng.integers(4, 14)))
    if seed % 2:
        coords[mask.nonzero()[0], 1:] -= 17          # negative coords too
    pc = M.make_point_cloud(jnp.asarray(coords), jnp.asarray(mask))
    m1, o1 = M.build_conv_maps(pc, kernel_size, stride, engine="v1")
    m2, o2 = M.build_conv_maps(pc, kernel_size, stride, engine="v2")
    np.testing.assert_array_equal(np.asarray(o1.coords),
                                  np.asarray(o2.coords))
    np.testing.assert_array_equal(np.asarray(o1.mask), np.asarray(o2.mask))
    assert o1.stride == o2.stride
    for k, (s1, s2) in enumerate(zip(maps_to_sets(m1), maps_to_sets(m2))):
        assert s1 == s2, f"offset {m1.offsets[k]}: {s1 ^ s2}"


def test_v2_inverse_table_matches_v1_scatter():
    """The v2 engine's free inverse table == scatter-inverting the v1 maps."""
    from repro.kernels.spconv import ops as spconv_ops
    rng = np.random.default_rng(11)
    coords, mask = random_cloud(rng, 70, 96, grid=10)
    pc = M.make_point_cloud(jnp.asarray(coords), jnp.asarray(mask))
    for ks, stride in [(3, 1), (2, 2)]:
        m1, o1 = M.build_conv_maps(pc, ks, stride, engine="v1")
        m2, _ = M.build_conv_maps(pc, ks, stride, engine="v2")
        assert m2.inv is not None
        np.testing.assert_array_equal(
            np.asarray(spconv_ops.invert_maps(m1, o1.capacity)),
            np.asarray(m2.inv))
        # swapped strided maps carry the transposed inverse table (search-
        # built, scatter-free) and it matches scatter-inverting the swapped
        # v1 map lists; submanifold maps still fall back to the scatter
        if stride > 1:
            sw = m2.swap()
            assert sw.inv is not None
            np.testing.assert_array_equal(
                np.asarray(spconv_ops.invert_maps(m1.swap(), pc.capacity)),
                np.asarray(sw.inv))
        else:
            assert m2.swap().inv is None


@pytest.mark.parametrize("kernel_size,stride", [(3, 1), (2, 2)])
@pytest.mark.parametrize("cap", [1, 7, 128, 4096])
def test_v2_merge_lookup_matches_binary(monkeypatch, cap, kernel_size,
                                        stride):
    """The TPU's merge lookup of `match_table`, forced onto this
    backend, builds the same maps and inverse tables bit for bit as the
    binary search: in_idx, out_idx, valid, inv and (strided) inv_t, on a
    shuffled cloud (perm not the identity) with masked rows."""
    rng = np.random.default_rng(cap + stride)
    grid = max(4, int(np.ceil((2 * cap) ** (1 / 3))))
    n_valid = max(1, (3 * cap) // 4)
    coords, mask = random_cloud(rng, n_valid, cap, grid=grid)
    pc = M.make_point_cloud(jnp.asarray(coords), jnp.asarray(mask))

    def build():        # traced afresh: the lowering is chosen at trace
        return jax.jit(lambda c, m: M.build_conv_maps(
            M.PointCloud(c, m, 1), kernel_size, stride,
            engine="v2")[0])(pc.coords, pc.mask)

    assert M.lookup_kind(jax.default_backend()) == "binary"
    binary = build()
    monkeypatch.setattr(M, "MERGE_PLATFORMS", (jax.default_backend(),))
    assert M.lookup_kind(jax.default_backend()) == "merge"
    merge = build()
    fields = ["in_idx", "out_idx", "valid", "inv"]
    if stride > 1:
        fields.append("inv_t")
    for f in fields:
        np.testing.assert_array_equal(np.asarray(getattr(merge, f)),
                                      np.asarray(getattr(binary, f)),
                                      err_msg=f)
    n_maps = int(np.sum(np.asarray(merge.valid)))
    # each valid row pairs with itself (k=3), or with its one cell (k=2)
    assert n_maps >= n_valid if stride == 1 else n_maps == n_valid


def test_downsample_sorted_matches_downsample():
    rng = np.random.default_rng(12)
    coords, mask = random_cloud(rng, 60, 80, grid=8)
    pc = M.make_point_cloud(jnp.asarray(coords), jnp.asarray(mask))
    ref = M.downsample(pc, 2)
    got = M.downsample_sorted(M.sort_cloud(pc), 2)
    np.testing.assert_array_equal(np.asarray(ref.coords),
                                  np.asarray(got.pc.coords))
    np.testing.assert_array_equal(np.asarray(ref.mask),
                                  np.asarray(got.pc.mask))
    assert got.pc.stride == ref.stride
    # the downsampled SortedCloud is identity-permuted (already sorted)
    np.testing.assert_array_equal(np.asarray(got.perm), np.arange(80))


def test_kernel_map_v2_explicit_small_cap_compacts():
    rng = np.random.default_rng(13)
    coords, mask = random_cloud(rng, 40, 64, grid=6)
    pc = M.make_point_cloud(jnp.asarray(coords), jnp.asarray(mask))
    full, _ = M.build_conv_maps(pc, 3, 1, engine="v2")
    small, _ = M.build_conv_maps(pc, 3, 1, cap=50, engine="v2")
    assert small.in_idx.shape == (27, 50)
    for sf, ss in zip(maps_to_sets(full), maps_to_sets(small)):
        assert ss <= sf
        # nothing lost when matches fit in cap (40 valid points max)
        assert len(ss) == len(sf)


def test_kernel_map_v2_small_cap_drops_inv():
    """A cap below out-capacity may truncate matches; the inverse table
    must be dropped so the pallas flow can't see matches gms/fod lost."""
    rng = np.random.default_rng(15)
    coords, mask = random_cloud(rng, 40, 64, grid=6)
    pc = M.make_point_cloud(jnp.asarray(coords), jnp.asarray(mask))
    small, _ = M.build_conv_maps(pc, 3, 1, cap=5, engine="v2")
    assert small.inv is None
    full, _ = M.build_conv_maps(pc, 3, 1, engine="v2")
    assert full.inv is not None


def test_v2_out_of_budget_raises_eagerly():
    coords = np.array([[0, 40000, 0, 0], [0, 1, 1, 1]], np.int32)
    pc = M.make_point_cloud(jnp.asarray(coords),
                            jnp.asarray(np.ones(2, bool)))
    with pytest.raises(ValueError, match="packed-key budget"):
        M.build_conv_maps(pc, 3, 1, engine="v2")
    # v1 handles the same cloud
    maps, _ = M.build_conv_maps(pc, 3, 1, engine="v1")
    assert int(np.sum(np.asarray(maps.valid))) >= 2


def test_explicit_v2_raises_for_non_3d_default_falls_back():
    coords = np.array([[0, 1, 2], [0, 3, 4]], np.int32)   # 2 spatial dims
    pc = M.make_point_cloud(jnp.asarray(coords),
                            jnp.asarray(np.ones(2, bool)))
    maps, _ = M.build_conv_maps(pc, 3, 1)                 # default: v1 path
    assert int(np.sum(np.asarray(maps.valid))) == 2
    with pytest.raises(ValueError, match="3 spatial dims"):
        M.build_conv_maps(pc, 3, 1, engine="v2")


def test_build_conv_maps_reuses_cache():
    """A supplied SortedCloud cache must produce the same maps as a fresh
    sort (it IS the same computation, skipped)."""
    rng = np.random.default_rng(14)
    coords, mask = random_cloud(rng, 50, 64, grid=8)
    pc = M.make_point_cloud(jnp.asarray(coords), jnp.asarray(mask))
    sc = M.sort_cloud(pc)
    fresh, _ = M.build_conv_maps(pc, 3, 1)
    cached, _ = M.build_conv_maps(pc, 3, 1, cache=sc)
    np.testing.assert_array_equal(np.asarray(fresh.in_idx),
                                  np.asarray(cached.in_idx))
    np.testing.assert_array_equal(np.asarray(fresh.valid),
                                  np.asarray(cached.valid))


def test_swap_roundtrip():
    rng = np.random.default_rng(6)
    coords, mask = random_cloud(rng, 20, 32)
    pc = M.make_point_cloud(jnp.asarray(coords), jnp.asarray(mask))
    maps, _ = M.build_conv_maps(pc, 2, 2)
    rt = maps.swap().swap()
    np.testing.assert_array_equal(np.asarray(rt.in_idx),
                                  np.asarray(maps.in_idx))
    np.testing.assert_array_equal(rt.offsets, maps.offsets)


# ---------------------------------------------------------------------------
# FPS / kNN / ball query vs numpy oracles
# ---------------------------------------------------------------------------

def test_fps_matches_oracle():
    rng = np.random.default_rng(7)
    xyz = rng.normal(size=(2, 64, 3)).astype(np.float32)
    mask = np.ones((2, 64), bool)
    mask[1, 50:] = False
    got = np.asarray(P.farthest_point_sampling(
        jnp.asarray(xyz), jnp.asarray(mask), 8))

    for b in range(2):
        sel = [int(np.argmax(mask[b]))]
        mind = np.where(mask[b], np.inf, -np.inf)
        for _ in range(7):
            d = ((xyz[b] - xyz[b, sel[-1]]) ** 2).sum(-1)
            d = np.where(mask[b], d, -np.inf)
            mind = np.minimum(mind, d)
            sel.append(int(np.argmax(mind)))
        assert got[b].tolist() == sel


def test_fps_selects_distinct_valid_points():
    rng = np.random.default_rng(8)
    xyz = rng.normal(size=(1, 128, 3)).astype(np.float32)
    mask = np.ones((1, 128), bool)
    mask[0, 100:] = False
    got = np.asarray(P.farthest_point_sampling(
        jnp.asarray(xyz), jnp.asarray(mask), 16))[0]
    assert len(set(got.tolist())) == 16
    assert np.all(got < 100)


@pytest.mark.parametrize("k,chunk", [(4, 1024), (8, 16)])
def test_knn_matches_argsort(k, chunk):
    rng = np.random.default_rng(9)
    q = rng.normal(size=(2, 33, 3)).astype(np.float32)
    r = rng.normal(size=(2, 57, 3)).astype(np.float32)
    qm = np.ones((2, 33), bool)
    rm = np.ones((2, 57), bool)
    rm[0, 40:] = False
    idx, dist = P.knn(jnp.asarray(q), jnp.asarray(qm), jnp.asarray(r),
                      jnp.asarray(rm), k, chunk=chunk)
    idx, dist = np.asarray(idx), np.asarray(dist)
    for b in range(2):
        d = ((q[b][:, None] - r[b][None]) ** 2).sum(-1)
        d[:, ~rm[b]] = 1e10
        expect = np.sort(d, axis=1)[:, :k]
        np.testing.assert_allclose(np.sort(dist[b], axis=1), expect,
                                   rtol=1e-4, atol=1e-4)
        # indices must point at the same distances
        np.testing.assert_allclose(
            np.take_along_axis(d, idx[b], axis=1), dist[b],
            rtol=1e-4, atol=1e-4)


def test_ball_query_radius_and_padding():
    rng = np.random.default_rng(10)
    q = rng.uniform(-1, 1, size=(1, 16, 3)).astype(np.float32)
    r = rng.uniform(-1, 1, size=(1, 64, 3)).astype(np.float32)
    ones_q, ones_r = np.ones((1, 16), bool), np.ones((1, 64), bool)
    radius = 0.5
    idx, valid = P.ball_query(jnp.asarray(q), jnp.asarray(ones_q),
                              jnp.asarray(r), jnp.asarray(ones_r),
                              radius, 8)
    idx, valid = np.asarray(idx), np.asarray(valid)
    d = ((q[0][:, None] - r[0][None]) ** 2).sum(-1)
    for m in range(16):
        inball = idx[0, m][valid[0, m]]
        if len(inball):
            assert np.all(d[m, inball] <= radius ** 2 + 1e-5)
        # padded slots replicate the first neighbour
        if valid[0, m, 0]:
            pad = idx[0, m][~valid[0, m]]
            assert np.all(pad == idx[0, m, 0]) or pad.size == 0
