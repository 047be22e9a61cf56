"""Fused-epilogue + streamed-feature-tile Pallas FoD conv: parity vs the
unfused flows, the swapped-maps (transposed) path, streaming for clouds
larger than one feature tile, channel/row padding, and the planner."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import fusion as F
from repro.core import mapping as M
from repro.core import sparseconv as SC
from repro.kernels.spconv import ops as spops
from repro.kernels.spconv.ref import spconv_fod_fused_ref, spconv_fod_ref
from repro.kernels.spconv.spconv import (spconv_fod_fused_pallas,
                                         spconv_fod_pallas)
from repro.models import minkunet as MU
from tests.test_mapping import random_cloud

TOL = dict(rtol=1e-4, atol=1e-4)


def _rand_problem(rng, n, m, cin, cout, k, monotone=False):
    feats = jnp.asarray(rng.normal(size=(n, cin)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(k, cin, cout)).astype(np.float32) * 0.2)
    inv = rng.integers(-1, n, size=(k, m)).astype(np.int32)
    if monotone:
        inv = np.sort(inv, axis=1)
    return feats, jnp.asarray(inv), w


def _fused(feats, inv, w, feat_tile, out_tile=64, **epi):
    n = feats.shape[0]
    wmap, nwin = spops.window_schedule(inv, n, out_tile, feat_tile)
    return spconv_fod_fused_pallas(feats, inv, w, wmap, nwin,
                                   feat_tile=feat_tile, out_tile=out_tile,
                                   interpret=True, **epi)


@pytest.mark.parametrize("feat_tile", [256, 64, 32])
@pytest.mark.parametrize("monotone", [True, False])
def test_fused_kernel_streams_any_window_size(feat_tile, monotone):
    """Correctness must not depend on map ordering: every referenced window
    is visited, each row counted exactly once — including clouds many times
    larger than one feature tile."""
    rng = np.random.default_rng(0)
    feats, inv, w = _rand_problem(rng, 256, 128, 16, 32, 9,
                                  monotone=monotone)
    out = _fused(feats, inv, w, feat_tile)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(spconv_fod_ref(feats, inv, w)),
                               **TOL)


def test_fused_kernel_epilogue_vs_unfused_flows():
    """Full epilogue (bias+LN+residual+ReLU+mask) in the kernel flush ==
    the XLA epilogue applied to the fod/gms flow outputs."""
    rng = np.random.default_rng(1)
    n, m, cin, cout, k = 192, 128, 8, 16, 27
    feats, inv, w = _rand_problem(rng, n, m, cin, cout, k)
    bias = jnp.asarray(rng.normal(size=(cout,)).astype(np.float32))
    ln_s = jnp.asarray(rng.normal(size=(cout,)).astype(np.float32))
    ln_b = jnp.asarray(rng.normal(size=(cout,)).astype(np.float32))
    res = jnp.asarray(rng.normal(size=(m, cout)).astype(np.float32))
    mask = jnp.asarray(rng.integers(0, 2, size=(m,)).astype(np.float32))
    epi = SC.Epilogue(bias=bias, ln_scale=ln_s, ln_bias=ln_b, relu=True,
                      mask=mask, residual=res)
    out = _fused(feats, inv, w, feat_tile=64, bias=bias, ln_scale=ln_s,
                 ln_bias=ln_b, residual=res, mask=mask, relu=True)
    ref = spconv_fod_fused_ref(feats, inv, w, epi)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)


def test_fused_wrapper_pads_odd_shapes():
    """Odd cin with explicit cin_tile, odd m, odd n: the ops wrapper pads
    them all; results match the reference on the unpadded problem."""
    rng = np.random.default_rng(2)
    coords, mask = random_cloud(rng, 70, 90, grid=10)
    pc = M.make_point_cloud(jnp.asarray(coords), jnp.asarray(mask))
    feats = jnp.asarray(rng.normal(size=(90, 5)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(27, 5, 7)).astype(np.float32))
    maps, out_pc = M.build_conv_maps(pc, 3, 1)
    out = spops.sparse_conv_fused(feats, maps, w, out_pc.capacity,
                                  feat_tile=32, out_tile=16, cin_tile=4)
    ref = spops.sparse_conv_fod_ref(feats, maps, w, out_pc.capacity)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)


def test_tile_mismatch_raises_informative_errors():
    rng = np.random.default_rng(3)
    feats, inv, w = _rand_problem(rng, 64, 64, 6, 8, 8)
    with pytest.raises(ValueError, match="cin_tile"):
        spconv_fod_pallas(feats, inv, w, out_tile=32, cin_tile=4,
                          interpret=True)
    with pytest.raises(ValueError, match="out_tile"):
        spconv_fod_pallas(feats, inv, w, out_tile=48, interpret=True)
    wmap, nwin = spops.window_schedule(inv, 64, 32, 32)
    with pytest.raises(ValueError, match="feat_tile"):
        spconv_fod_fused_pallas(feats, inv, w, wmap, nwin, feat_tile=48,
                                out_tile=32, interpret=True)
    with pytest.raises(ValueError, match="ln_scale"):
        spops.sparse_conv_fused(feats, M.KernelMaps(inv, inv, inv >= 0,
                                                    np.zeros((8, 3))), w, 64,
                                epilogue=SC.Epilogue(ln_scale=w[0, 0]))


def test_swapped_maps_carry_inverse_table():
    """Strided v2 maps expose a scatter-free inverse for the transposed
    direction: swap() promotes inv_t, and it equals the scatter-built one."""
    rng = np.random.default_rng(4)
    coords, mask = random_cloud(rng, 100, 128, grid=12)
    pc = M.make_point_cloud(jnp.asarray(coords), jnp.asarray(mask))
    down, out_sc = M.build_conv_maps_cached(M.sort_cloud(pc), 2, 2)
    sw = down.swap()
    assert sw.inv is not None
    scatter = spops.invert_maps(sw._replace(inv=None), pc.capacity)
    assert bool(jnp.all(sw.inv == scatter))


def test_transposed_conv_pallas_fused_matches_fod():
    """Decoder path: transposed conv through the fused kernel on the swapped
    inverse table == the XLA fod flow on the swapped map lists."""
    rng = np.random.default_rng(5)
    coords, mask = random_cloud(rng, 90, 112, grid=10)
    pc = M.make_point_cloud(jnp.asarray(coords), jnp.asarray(mask))
    feats = rng.normal(size=(112, 6)).astype(np.float32)
    feats[~mask] = 0
    w_down = jnp.asarray(rng.normal(size=(8, 6, 12)).astype(np.float32))
    down = SC.sparse_conv(pc, jnp.asarray(feats), w_down, 2, 2)
    w_up = jnp.asarray(rng.normal(size=(8, 12, 5)).astype(np.float32))
    a = SC.sparse_conv_transposed(down.features, down.maps, pc, w_up,
                                  flow="fod")
    b = SC.sparse_conv_transposed(down.features, down.maps, pc, w_up,
                                  flow="pallas_fused")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), **TOL)


@pytest.mark.parametrize("fused_budget", [None, 36_000])
def test_minkunet_pallas_fused_matches_fod(fused_budget):
    """Acceptance: full MinkUNet forward (encoder + decoder with inverse-
    table up-convs) through the fused Pallas flow is numerically identical
    to flow='fod' — also under a tiny VMEM budget, where every cloud is
    larger than one feature tile and the kernel streams windows."""
    rng = np.random.default_rng(6)
    coords, mask = random_cloud(rng, 120, 160, grid=16)
    feats = jnp.asarray(rng.normal(size=(160, 4)).astype(np.float32))
    feats = feats * jnp.asarray(mask)[:, None]
    pc = M.make_point_cloud(jnp.asarray(coords), jnp.asarray(mask))
    p = MU.minkunet_init(jax.random.key(7), c_in=4, n_classes=13, stem=8,
                         enc_planes=(8, 16), dec_planes=(16, 8),
                         blocks_per_stage=1)
    a = MU.minkunet_apply(p, pc, feats, flow="fod")
    b = MU.minkunet_apply(p, pc, feats, flow="pallas_fused",
                          fused_budget=fused_budget)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), **TOL)
    if fused_budget is not None:
        plan = F.plan_conv_epilogue(160, 8, 8, 27,
                                    budget_bytes=fused_budget)
        assert plan.feat_tile < 160      # the tiny budget really streamed


def test_conv_epilogue_planner():
    """Planner picks the largest fitting cache block, shrinks under
    pressure, and declines to fuse only when nothing fits."""
    roomy = F.plan_conv_epilogue(4096, 64, 64, 27)
    assert roomy.fuse and roomy.feat_tile == 4096    # whole cloud resident
    tight = F.plan_conv_epilogue(4096, 64, 64, 27, budget_bytes=1_800_000)
    assert tight.fuse and tight.feat_tile < 4096
    assert tight.onchip_bytes <= 1_800_000
    none = F.plan_conv_epilogue(4096, 64, 64, 27, budget_bytes=1)
    assert not none.fuse
    # DRAM model: fusing removes the pre-activation round trip
    unf = F.dram_bytes_conv_epilogue(1000, 64, residual=True, fused=False)
    fus = F.dram_bytes_conv_epilogue(1000, 64, residual=True, fused=True)
    assert fus < unf
    assert unf - fus == 2 * 1000 * 64 * 4


def test_window_schedule_covers_all_references():
    """Every inverse-table entry falls inside one of its tile's scheduled
    windows (and empty tiles schedule nothing)."""
    rng = np.random.default_rng(8)
    inv = rng.integers(-1, 512, size=(9, 256)).astype(np.int32)
    inv[:, :64] = -1                                  # one empty tile
    wmap, nwin = spops.window_schedule(jnp.asarray(inv), 512, 64, 128)
    wmap, nwin = np.asarray(wmap), np.asarray(nwin)
    assert nwin[0] == 0
    for o in range(4):
        blocks = set(wmap[o, :nwin[o]])
        tile = inv[:, o * 64:(o + 1) * 64]
        for v in tile[tile >= 0]:
            assert v // 128 in blocks
