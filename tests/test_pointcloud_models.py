"""Smoke tests: every paper network runs a forward pass, correct shapes,
no NaNs, masked outputs zeroed."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import mapping as M
from repro.models import pointnets as PN
from repro.models import minkunet as MU
from tests.test_mapping import random_cloud


@pytest.fixture(scope="module")
def cloud():
    rng = np.random.default_rng(0)
    xyz = rng.normal(size=(2, 96, 3)).astype(np.float32)
    mask = np.ones((2, 96), bool)
    mask[1, 80:] = False
    return jnp.asarray(xyz), jnp.asarray(mask)


def _check(x, shape):
    assert x.shape == shape
    assert not np.any(np.isnan(np.asarray(x)))


def test_pointnet(cloud):
    xyz, mask = cloud
    p = PN.pointnet_init(jax.random.key(0), n_classes=40)
    _check(PN.pointnet_apply(p, xyz, mask), (2, 40))


def test_pointnetpp_cls(cloud):
    xyz, mask = cloud
    p = PN.pointnetpp_cls_init(jax.random.key(1), n_classes=40)
    _check(PN.pointnetpp_cls_apply(p, xyz, mask, n1=32, n2=8), (2, 40))


def test_pointnetpp_seg(cloud):
    xyz, mask = cloud
    p = PN.pointnetpp_seg_init(jax.random.key(2), n_classes=13)
    out = PN.pointnetpp_seg_apply(p, xyz, mask, n1=32, n2=8)
    _check(out, (2, 96, 13))
    assert np.all(np.asarray(out)[1, 80:] == 0)


def test_dgcnn(cloud):
    xyz, mask = cloud
    p = PN.dgcnn_init(jax.random.key(3), n_classes=16)
    _check(PN.dgcnn_apply(p, xyz, mask, k=8), (2, 16))


def test_fpointnetpp(cloud):
    xyz, mask = cloud
    p = PN.fpointnetpp_init(jax.random.key(4))
    out = PN.fpointnetpp_apply(p, xyz, mask)
    _check(out["seg"], (2, 96, 2))
    _check(out["center"], (2, 3))
    _check(out["box"], (2, 7))


@pytest.mark.parametrize("flow", ["fod", "gms"])
def test_minkunet(flow):
    rng = np.random.default_rng(5)
    coords, mask = random_cloud(rng, 120, 160, grid=16)
    feats = jnp.asarray(rng.normal(size=(160, 4)).astype(np.float32))
    feats = feats * jnp.asarray(mask)[:, None]
    pc = M.make_point_cloud(jnp.asarray(coords), jnp.asarray(mask))
    p = MU.minkunet_init(jax.random.key(6), c_in=4, n_classes=13,
                         stem=8, enc_planes=(8, 16), dec_planes=(16, 8),
                         blocks_per_stage=1)
    out = MU.minkunet_apply(p, pc, feats, flow=flow)
    assert out.shape == (160, 13)
    assert not np.any(np.isnan(np.asarray(out)))
    assert np.all(np.asarray(out)[~mask] == 0)


def _count_lowered_sorts(fn, *args) -> int:
    """`lax.sort`s in fn lowered for the CPU, whose key lookup is the
    binary search: the cloud-ranking sorts alone.  (A jaxpr holds every
    branch of `match_table`'s backend choice, the TPU merge's query-order
    sorts included; a lowering holds the one that runs.)"""
    lowered = jax.jit(fn).trace(*args).lower(lowering_platforms=("cpu",))
    return lowered.as_text().count("stablehlo.sort")


def test_unet_maps_one_sort_per_level():
    """Acceptance: the packed-key engine ranks each stride level exactly
    once — n_stages+1 `lax.sort` calls for the whole network, versus one
    (plus a compaction sort) per kernel offset per conv in v1."""
    rng = np.random.default_rng(9)
    coords, mask = random_cloud(rng, 100, 128, grid=16)
    n_stages = 2

    def build(c, m):
        levels = MU.build_unet_maps(M.PointCloud(c, m, 1), n_stages)
        return [(l["pc"].coords, l["subm"].in_idx,
                 l.get("down", l["subm"]).in_idx) for l in levels]

    args = (jnp.asarray(coords), jnp.asarray(mask))
    n_sorts = _count_lowered_sorts(build, *args)
    assert n_sorts == n_stages + 1, n_sorts

    def build_v1(c, m):
        levels = MU.build_unet_maps(M.PointCloud(c, m, 1), n_stages,
                                    engine="v1")
        return [(l["pc"].coords, l["subm"].in_idx,
                 l.get("down", l["subm"]).in_idx) for l in levels]

    assert _count_lowered_sorts(build_v1, *args) > 3 * n_sorts


def _build_levels(c, m):
    return MU.build_unet_maps(M.PointCloud(c, m, 1), 2)


def test_unet_maps_tpu_lowering_has_no_loop_or_gather():
    """Structure guard: lowered for the TPU (lowering only, no chip), the
    whole mapping pass at a 65,536-row bucket holds no while loop and no
    gather — every key lookup is the merge network.  The CPU lowering
    keeps the binary search's loops."""
    n = 65536
    shapes = (jax.ShapeDtypeStruct((n, 4), jnp.int32),
              jax.ShapeDtypeStruct((n,), jnp.bool_))

    def lowered(platform):
        return jax.jit(_build_levels).trace(*shapes).lower(
            lowering_platforms=(platform,)).as_text()

    tpu = lowered("tpu")
    assert "stablehlo.while" not in tpu
    assert "gather" not in tpu
    assert "stablehlo.while" in lowered("cpu")


def test_unet_maps_merge_matches_binary(monkeypatch):
    """The default lowering's pyramid (binary search) equals the one the
    TPU's merge builds, forced onto this backend, leaf for leaf and bit
    for bit: maps, inverse tables and clouds at every level."""
    rng = np.random.default_rng(21)
    coords, mask = random_cloud(rng, 300, 512, grid=12)
    args = (jnp.asarray(coords), jnp.asarray(mask))
    binary = jax.jit(_build_levels)(*args)
    monkeypatch.setattr(M, "MERGE_PLATFORMS", (jax.default_backend(),))
    merge = jax.jit(_build_levels)(*args)
    flat_b, tree_b = jax.tree_util.tree_flatten_with_path(binary)
    flat_m, tree_m = jax.tree_util.tree_flatten_with_path(merge)
    assert tree_b == tree_m
    for (path, b), (_, m) in zip(flat_b, flat_m):
        np.testing.assert_array_equal(np.asarray(m), np.asarray(b),
                                      err_msg=jax.tree_util.keystr(path))


def test_minkunet_engines_agree():
    """Forward pass is identical whichever mapping engine built the maps."""
    rng = np.random.default_rng(10)
    coords, mask = random_cloud(rng, 60, 96, grid=12)
    feats = jnp.asarray(rng.normal(size=(96, 4)).astype(np.float32))
    feats = feats * jnp.asarray(mask)[:, None]
    pc = M.make_point_cloud(jnp.asarray(coords), jnp.asarray(mask))
    p = MU.mini_minkunet_init(jax.random.key(11))
    lv2 = MU.build_unet_maps(pc, 2)
    lv1 = MU.build_unet_maps(pc, 2, engine="v1")
    a = MU.minkunet_apply(p, pc, feats, levels=lv2)
    b = MU.minkunet_apply(p, pc, feats, levels=lv1)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-3, atol=2e-3)


def test_minkunet_flows_identical():
    rng = np.random.default_rng(7)
    coords, mask = random_cloud(rng, 60, 96, grid=12)
    feats = jnp.asarray(rng.normal(size=(96, 4)).astype(np.float32))
    feats = feats * jnp.asarray(mask)[:, None]
    pc = M.make_point_cloud(jnp.asarray(coords), jnp.asarray(mask))
    p = MU.mini_minkunet_init(jax.random.key(8))
    a = MU.minkunet_apply(p, pc, feats, flow="fod")
    b = MU.minkunet_apply(p, pc, feats, flow="gms")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-3, atol=2e-3)
