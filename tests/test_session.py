"""PointAccSession / SparseTensor frontend: parity with the legacy call
sites, one-sort-per-level accounting, the stride-pair transposed lookup,
engine fallbacks (D!=3, packed-key budget), the LRU MappingCache, and the
vmapped batched serving entry point."""

import warnings

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.api import MappingCache, PointAccSession
from repro.core import mapping as M
from repro.core import sparseconv as SC
from repro.core.tensor import MapContext, infer_kernel_size
from repro.models import minkunet as MU
from tests.test_mapping import random_cloud
from tests.test_pointcloud_models import _count_lowered_sorts


def _scene(seed=7, n=60, cap=96, grid=12, cin=4):
    rng = np.random.default_rng(seed)
    coords, mask = random_cloud(rng, n, cap, grid=grid)
    feats = rng.normal(size=(cap, cin)).astype(np.float32)
    feats[~mask] = 0
    return (jnp.asarray(coords), jnp.asarray(mask), jnp.asarray(feats))


# ---------------------------------------------------------------------------
# acceptance: whole-network parity + sort accounting
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("flow", ["fod", "pallas", "pallas_fused"])
def test_session_minkunet_matches_legacy_apply(flow):
    """Acceptance: a whole-network MinkUNet forward through the session is
    numerically identical (atol 1e-5) to the minkunet_apply path, for all
    three flows."""
    coords, mask, feats = _scene()
    pc = M.make_point_cloud(coords, mask)
    params = MU.mini_minkunet_init(jax.random.key(8))
    legacy = MU.minkunet_apply(params, pc, feats, flow=flow)

    session = PointAccSession(flow=flow)
    x = session.tensor(coords, mask, feats)
    out = MU.minkunet_forward(session, params, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(legacy),
                               rtol=1e-5, atol=1e-5)
    # and every flow agrees with the fod baseline
    ref = MU.minkunet_apply(params, pc, feats, flow="fod")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("flow", ["fod", "pallas_fused"])
def test_session_one_sort_per_stride_level(flow):
    """Acceptance: the session builds exactly one ranking sort per stride
    level for the ENTIRE forward — including the fused flow, whose
    packed-key canonicalisation reuses the level-0 sort instead of adding
    one (the legacy path paid n_stages+2 there)."""
    coords, mask, _ = _scene(seed=9, n=100, cap=128, grid=16)
    params = MU.mini_minkunet_init(jax.random.key(1))
    n_stages = len(params["enc"])

    def fwd(c, m, f):
        session = PointAccSession(flow=flow)
        return MU.minkunet_forward(session, params, session.tensor(c, m, f))

    assert _count_lowered_sorts(fwd, coords, mask,
                                jnp.zeros((128, 4))) == n_stages + 1


def test_session_conv_matches_sparse_conv():
    """Single conv: session.conv == the legacy sparse_conv layer wrapper."""
    coords, mask, feats = _scene(seed=3, cin=6)
    rng = np.random.default_rng(3)
    w = jnp.asarray(rng.normal(size=(27, 6, 8)).astype(np.float32))
    pc = M.make_point_cloud(coords, mask)
    ref = SC.sparse_conv(pc, feats, w, 3, 1, flow="fod")

    session = PointAccSession(flow="fod")
    y = session.conv(session.tensor(coords, mask, feats), w)
    np.testing.assert_allclose(np.asarray(y.feats), np.asarray(ref.features),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(y.coords),
                                  np.asarray(ref.pc.coords))
    assert y.stride == ref.pc.stride


def test_session_conv_maps_memoized_and_stride_pairs_registered():
    coords, mask, feats = _scene(seed=4)
    session = PointAccSession()
    x = session.tensor(coords, mask, feats)
    rng = np.random.default_rng(4)
    w_subm = jnp.asarray(rng.normal(size=(27, 4, 4)).astype(np.float32))
    w_down = jnp.asarray(rng.normal(size=(8, 4, 4)).astype(np.float32))
    h1 = session.conv(x, w_subm)
    session.conv(h1, w_subm)                     # same level: reuse
    d = session.conv(h1, w_down, stride=2)
    assert set(x.context.maps) == {(3, 1, 1), (2, 1, 2)}
    assert d.stride == 2 and d.context is x.context
    # the strided v2 map carries the swapped inverse table for the decoder
    assert x.context.maps[(2, 1, 2)].inv_t is not None


# ---------------------------------------------------------------------------
# transposed convs: stride-pair lookup + inverse-table fallback
# ---------------------------------------------------------------------------

def test_transposed_conv_by_stride_pair_matches_legacy():
    coords, mask, feats = _scene(seed=5, cin=6)
    rng = np.random.default_rng(5)
    w_down = jnp.asarray(rng.normal(size=(8, 6, 12)).astype(np.float32))
    w_up = jnp.asarray(rng.normal(size=(8, 12, 5)).astype(np.float32))

    pc = M.make_point_cloud(coords, mask)
    down = SC.sparse_conv(pc, feats, w_down, 2, 2)
    legacy = SC.sparse_conv_transposed(down.features, down.maps, pc, w_up)

    session = PointAccSession()
    x = session.tensor(coords, mask, feats)
    h = session.conv(x, w_down, stride=2)
    y = session.conv_transposed(h, w_up, stride=2)
    assert y.stride == 1
    np.testing.assert_allclose(np.asarray(y.feats), np.asarray(legacy),
                               rtol=1e-5, atol=1e-5)


def test_transposed_conv_without_forward_maps_raises():
    coords, mask, feats = _scene(seed=6)
    session = PointAccSession()
    x = session.tensor(coords, mask, feats, stride=2)
    w_up = jnp.zeros((8, 4, 4))
    with pytest.raises(ValueError, match="stride pair"):
        session.conv_transposed(x, w_up, stride=2)


def test_swap_require_inverse_raises_for_v1_maps():
    """Satellite fix: the transposed path must not silently assume inv_t.
    v1-built maps raise under require_inverse, warn-and-fall-back on the
    Pallas flows, and stay numerically identical to the fod flow."""
    coords, mask, feats = _scene(seed=2, cin=6)
    rng = np.random.default_rng(2)
    w_down = jnp.asarray(rng.normal(size=(8, 6, 12)).astype(np.float32))
    w_up = jnp.asarray(rng.normal(size=(8, 12, 5)).astype(np.float32))
    pc = M.make_point_cloud(coords, mask)

    m1, _ = M.build_conv_maps(pc, 2, 2, engine="v1")
    with pytest.raises(ValueError, match="no inverse table"):
        m1.swap(require_inverse=True)
    m2, _ = M.build_conv_maps(pc, 2, 2, engine="v2")
    assert m2.swap(require_inverse=True).inv is not None

    down = SC.sparse_conv(pc, feats, w_down, 2, 2, engine="v1")
    ref = SC.sparse_conv_transposed(down.features, down.maps, pc, w_up,
                                    flow="fod")
    with pytest.warns(UserWarning, match="scatter-built inverse"):
        out = SC.sparse_conv_transposed(down.features, down.maps, pc, w_up,
                                        flow="pallas_fused")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    # v2-built maps keep the scatter-free path warning-free
    down2 = SC.sparse_conv(pc, feats, w_down, 2, 2, engine="v2")
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        SC.sparse_conv_transposed(down2.features, down2.maps, pc, w_up,
                                  flow="pallas_fused")

    # the session's transposed path surfaces the same downgrade
    v1s = PointAccSession(engine="v1", flow="pallas_fused")
    h = v1s.conv(v1s.tensor(coords, mask, feats), w_down, stride=2)
    with pytest.warns(UserWarning, match="scatter-built inverse"):
        y = v1s.conv_transposed(h, w_up, stride=2)
    np.testing.assert_allclose(np.asarray(y.feats), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    v2s = PointAccSession(engine="v2", flow="pallas_fused")
    h2 = v2s.conv(v2s.tensor(coords, mask, feats), w_down, stride=2)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        v2s.conv_transposed(h2, w_up, stride=2)


# ---------------------------------------------------------------------------
# engine fallbacks through the session: D != 3 and the packed-key budget
# ---------------------------------------------------------------------------

def test_session_non3d_cloud_falls_back_to_v1_with_parity():
    """D=2 clouds: the default engine falls back to v1 under the session,
    matching an explicit v1 build; explicit v2 still raises."""
    rng = np.random.default_rng(20)
    coords, mask = random_cloud(rng, 40, 64, grid=8, d=2)
    feats = rng.normal(size=(64, 5)).astype(np.float32)
    feats[~mask] = 0
    coords, mask, feats = (jnp.asarray(coords), jnp.asarray(mask),
                           jnp.asarray(feats))
    w = jnp.asarray(rng.normal(size=(9, 5, 7)).astype(np.float32))

    session = PointAccSession()
    x = session.tensor(coords, mask, feats)
    assert x.context.engine == "v1"
    y = session.conv(x, w)
    assert infer_kernel_size(9, 2) == 3

    pc = M.make_point_cloud(coords, mask)
    ref = SC.sparse_conv(pc, feats, w, 3, 1, engine="v1")
    np.testing.assert_allclose(np.asarray(y.feats),
                               np.asarray(ref.features),
                               rtol=1e-5, atol=1e-5)

    strict = PointAccSession(engine="v2")
    with pytest.raises(ValueError, match="3 spatial dims"):
        strict.conv(strict.tensor(coords, mask, feats), w)


def test_session_out_of_budget_raises_eagerly_and_saturates_under_jit():
    """Coordinates outside the 62-bit key budget, reached through the
    session: eager v2 raises with the v1 escape hatch named; engine='v1'
    serves the same cloud; under jit the bad point saturates to the
    sentinel key and silently drops out of every map."""
    coords = jnp.asarray(np.array([[0, 40000, 0, 0], [0, 1, 1, 1],
                                   [0, 1, 1, 2]], np.int32))
    mask = jnp.asarray(np.ones(3, bool))
    feats = jnp.asarray(np.ones((3, 2), np.float32))
    w = jnp.asarray(np.ones((27, 2, 2), np.float32))

    session = PointAccSession()
    with pytest.raises(ValueError, match="packed-key budget"):
        session.conv(session.tensor(coords, mask, feats), w)

    v1 = PointAccSession(engine="v1")
    y1 = v1.conv(v1.tensor(coords, mask, feats), w)
    assert float(jnp.abs(y1.feats[0]).max()) > 0   # v1 maps the far point

    @jax.jit
    def conv_v2(c, m, f):
        s = PointAccSession(engine="v2")
        return s.conv(s.tensor(c, m, f), w).feats

    y2 = conv_v2(coords, mask, feats)
    assert float(jnp.abs(y2[0]).max()) == 0        # saturated -> no maps
    # in-budget rows are unaffected by the saturating neighbour
    np.testing.assert_allclose(np.asarray(y2[1:]), np.asarray(y1.feats[1:]),
                               rtol=1e-5, atol=1e-5)


def test_session_v1_vs_v2_parity_on_3d_cloud():
    """Same cloud, both engines through the session: identical forward."""
    coords, mask, feats = _scene(seed=21)
    params = MU.mini_minkunet_init(jax.random.key(11))
    outs = []
    for engine in ("v1", "v2"):
        session = PointAccSession(engine=engine)
        outs.append(MU.minkunet_forward(
            session, params, session.tensor(coords, mask, feats)))
    np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(outs[1]),
                               rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# MappingCache: LRU bound + counters
# ---------------------------------------------------------------------------

def test_mapping_cache_lru_bound_and_counters():
    cache = MappingCache(max_entries=2)
    a = np.arange(4, dtype=np.int32)
    b = np.arange(4, dtype=np.int32) + 1
    c = np.arange(4, dtype=np.int32) + 2
    builds = []

    def builder(tag):
        def build():
            builds.append(tag)
            return tag
        return build

    assert cache.get((a,), builder("a")) == ("a", False)
    assert cache.get((a,), builder("a")) == ("a", True)       # hit
    assert cache.get((b,), builder("b")) == ("b", False)
    assert cache.get((c,), builder("c")) == ("c", False)      # evicts a
    assert len(cache) == 2
    assert cache.get((a,), builder("a2")) == ("a2", False)    # a was evicted
    assert cache.get((c,), builder("c2")) == ("c", True)      # c survived
    assert cache.stats()["hits"] == 2
    assert cache.stats()["misses"] == 4
    assert builds == ["a", "b", "c", "a2"]


def test_mapping_cache_distinguishes_dtype_and_shape():
    cache = MappingCache()
    a32 = np.zeros(4, np.int32)
    a64 = np.zeros(4, np.int64)
    a2d = np.zeros((2, 2), np.int32)
    cache.get((a32,), lambda: 1)
    _, hit = cache.get((a64,), lambda: 2)
    assert not hit
    _, hit = cache.get((a2d,), lambda: 3)
    assert not hit


def test_geometry_digest_near_duplicates():
    """Near-duplicate scenes, pinned at the digest level.

    A row permutation of the same coordinate SET is a different padded
    scene — kernel maps are row-indexed, so reusing the permuted scene's
    pyramid would scatter predictions to the wrong rows.  The digest
    must differ.  Features, by contrast, are NOT geometry: a re-scored
    frame (same coords+mask, new feats) shares the cached pyramid —
    `PointCloudEngine.scene_key` hashes only (coords, mask, bucket)."""
    coords, mask, _ = _scene(seed=21)
    c, m = np.asarray(coords), np.asarray(mask)
    base = MappingCache.digest((c, m))
    assert MappingCache.digest((c.copy(), m.copy())) == base  # value id

    perm = np.random.default_rng(3).permutation(c.shape[0])
    assert not np.array_equal(c[perm], c)
    assert MappingCache.digest((c[perm], m[perm])) != base

    cache = MappingCache()
    cache.get((c, m), lambda: "pyramid")
    _, hit = cache.get((c[perm], m[perm]), lambda: "permuted")
    assert not hit                        # permuted rows: a new entry
    _, hit = cache.get((c, m), lambda: "unused")
    assert hit                            # feats never entered the key

    # same geometry under a different serving bucket must not collide
    assert MappingCache.digest((c, m), extra=("levels", 64)) \
        != MappingCache.digest((c, m), extra=("levels", 128))


# ---------------------------------------------------------------------------
# batched serving: vmapped entry point == per-scene loop
# ---------------------------------------------------------------------------

def test_vmapped_segment_batch_matches_per_scene_loop():
    """Acceptance: the jax.vmap-over-scenes serving entry point produces
    the same segmentation as looping minkunet_apply scene by scene."""
    from repro.data.synthetic import point_cloud_batch
    from repro.serve.engine import PointCloudEngine

    B, N = 3, 128
    coords, mask, feats, _ = point_cloud_batch(seed=1, step=0, batch=B,
                                               n_points=N, grid=16)
    coords = coords.reshape(B, N, 4)
    mask = mask.reshape(B, N)
    feats = feats.reshape(B, N, -1)

    params = MU.mini_minkunet_init(jax.random.key(0), c_in=4, n_classes=2)
    engine = PointCloudEngine(params, n_stages=2, flow="fod")
    preds, hit = engine.segment_batch(coords, mask, feats)
    assert not hit and preds.shape == (B, N)

    for b in range(B):
        pc = M.make_point_cloud(jnp.asarray(coords[b]), jnp.asarray(mask[b]))
        logits = MU.minkunet_apply(params, pc, jnp.asarray(feats[b]),
                                   flow="fod")
        np.testing.assert_array_equal(np.asarray(preds[b]),
                                      np.asarray(jnp.argmax(logits, -1)))

    # identical geometry: the second request's ORDERED composition
    # repeats, so the scheduler's assembly cache serves the whole stacked
    # batch — the per-scene mapping cache is bypassed, not consulted
    _, hit = engine.segment_batch(coords, mask, feats)
    assert hit
    assert engine.cache_stats()["hits"] == 0
    assert engine.scheduler().stats()["assembly_cache"]["hits"] == 1

    # permuted composition: the assembly key misses, and the per-scene
    # digests take over — every scene's pyramid hits individually
    _, hit = engine.segment_batch(coords[::-1], mask[::-1], feats[::-1])
    assert hit
    assert engine.cache_stats()["hits"] == B


def test_levels_roundtrip_through_context():
    """build_unet_maps -> _context_from_levels -> forward == direct."""
    coords, mask, feats = _scene(seed=10)
    pc = M.make_point_cloud(coords, mask)
    params = MU.mini_minkunet_init(jax.random.key(11))
    ref = MU.minkunet_apply(params, pc, feats)
    for engine in (None, "v1"):
        levels = MU.build_unet_maps(pc, 2, engine=engine)
        out = MU.minkunet_apply(params, pc, feats, levels=levels)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3)


def test_infer_kernel_size():
    assert infer_kernel_size(27, 3) == 3
    assert infer_kernel_size(8, 3) == 2
    assert infer_kernel_size(125, 3) == 5
    assert infer_kernel_size(9, 2) == 3
    with pytest.raises(ValueError, match="kernel_size"):
        infer_kernel_size(10, 3)


def test_map_context_rejects_unknown_engine():
    with pytest.raises(ValueError, match="engine"):
        MapContext(engine="v3")
    with pytest.raises(ValueError, match="flow"):
        PointAccSession(flow="warp")
