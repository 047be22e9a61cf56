"""Packed-key engine unit tests: bit budget, saturation, ordering, search.

The v2 ranking engine's correctness rests on three properties of
repro.core.packed:

  1. pack/unpack is a bijection on the in-budget coordinate box;
  2. anything outside the budget (or masked) saturates to the sentinel key
     and can NEVER alias a valid key;
  3. (hi, lo) pair order == lexicographic (batch, x, y, z) coordinate order,
     so every sorted structure matches the v1 lexicographic engine bit for
     bit.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import mapping as M
from repro.core import packed as PK


def pack_np(coords, mask):
    hi, lo = PK.pack_coords(jnp.asarray(coords), jnp.asarray(mask))
    return np.asarray(hi), np.asarray(lo)


# ---------------------------------------------------------------------------
# roundtrip + budget edges
# ---------------------------------------------------------------------------

def test_roundtrip_random_including_negative():
    rng = np.random.default_rng(0)
    coords = np.stack([
        rng.integers(0, PK.BATCH_MAX + 1, 512),
        rng.integers(PK.COORD_MIN, PK.COORD_MAX + 1, 512),
        rng.integers(PK.COORD_MIN, PK.COORD_MAX + 1, 512),
        rng.integers(PK.COORD_MIN, PK.COORD_MAX + 1, 512),
    ], axis=1).astype(np.int32)
    mask = np.ones(512, bool)
    hi, lo = pack_np(coords, mask)
    back = np.asarray(PK.unpack_keys(jnp.asarray(hi), jnp.asarray(lo)))
    np.testing.assert_array_equal(back, coords)


def test_roundtrip_budget_corners():
    corners = np.array([
        [0, PK.COORD_MIN, PK.COORD_MIN, PK.COORD_MIN],
        [0, PK.COORD_MAX, PK.COORD_MAX, PK.COORD_MAX],
        [PK.BATCH_MAX, PK.COORD_MAX, PK.COORD_MIN, PK.COORD_MAX],
        [PK.BATCH_MAX, 0, 0, 0],
    ], np.int32)
    hi, lo = pack_np(corners, np.ones(4, bool))
    assert not np.any(hi == PK.KEY_HI_SENTINEL)
    back = np.asarray(PK.unpack_keys(jnp.asarray(hi), jnp.asarray(lo)))
    np.testing.assert_array_equal(back, corners)


@pytest.mark.parametrize("bad", [
    [0, PK.COORD_MAX + 1, 0, 0],          # +x overflow
    [0, 0, PK.COORD_MIN - 1, 0],          # -y overflow
    [0, 0, 0, PK.COORD_MAX + 1],          # +z overflow
    [PK.BATCH_MAX + 1, 0, 0, 0],          # batch overflow
    [-1, 0, 0, 0],                        # negative batch
    [0, 2**29, -2**29, 5],                # far out of budget
    [int(M.SENTINEL), int(M.SENTINEL), int(M.SENTINEL), int(M.SENTINEL)],
])
def test_overflow_saturates_to_sentinel_never_aliases(bad):
    coords = np.array([bad], np.int32)
    hi, lo = pack_np(coords, np.ones(1, bool))
    assert hi[0] == PK.KEY_HI_SENTINEL and lo[0] == PK.KEY_LO_SENTINEL
    # sentinel unpacks to the masked-row convention, not to a coordinate
    back = np.asarray(PK.unpack_keys(jnp.asarray(hi), jnp.asarray(lo)))
    assert np.all(back == M.SENTINEL)


def test_masked_rows_saturate():
    coords = np.zeros((4, 4), np.int32)
    mask = np.array([True, False, True, False])
    hi, _ = pack_np(coords, mask)
    np.testing.assert_array_equal(hi == PK.KEY_HI_SENTINEL, ~mask)


def test_valid_keys_cannot_reach_sentinel():
    """Max valid hi is (BATCH_MAX<<16)|0xFFFF = 2^30-1 < KEY_HI_SENTINEL:
    the sentinel is outside the image of pack on the valid box."""
    top = np.array([[PK.BATCH_MAX, PK.COORD_MAX, PK.COORD_MAX,
                     PK.COORD_MAX]], np.int32)
    hi, lo = pack_np(top, np.ones(1, bool))
    assert hi[0] == 2**30 - 1
    assert hi[0] < PK.KEY_HI_SENTINEL


# ---------------------------------------------------------------------------
# ordering: packed-pair order == lexicographic coordinate order
# ---------------------------------------------------------------------------

def test_pair_order_matches_lexsort():
    rng = np.random.default_rng(1)
    coords = np.stack([
        rng.integers(0, 4, 256),
        rng.integers(-200, 200, 256),
        rng.integers(-200, 200, 256),
        rng.integers(-200, 200, 256),
    ], axis=1).astype(np.int32)
    hi, lo = pack_np(coords, np.ones(256, bool))
    # numpy lexsort keys are last-significant-first
    lex = np.lexsort((coords[:, 3], coords[:, 2], coords[:, 1],
                      coords[:, 0]))
    pair = np.lexsort((lo, hi))
    np.testing.assert_array_equal(
        coords[lex], coords[pair])


def test_sort_cloud_sorts_and_permutes():
    rng = np.random.default_rng(2)
    coords = np.concatenate([
        rng.integers(0, 2, (64, 1)), rng.integers(-30, 30, (64, 3))],
        axis=1).astype(np.int32)
    mask = rng.random(64) < 0.8
    pc = M.make_point_cloud(jnp.asarray(coords), jnp.asarray(mask))
    sc = M.sort_cloud(pc)
    hi, lo = PK.pack_coords(pc.coords, pc.mask)
    hi, lo = np.asarray(hi), np.asarray(lo)
    perm = np.asarray(sc.perm)
    np.testing.assert_array_equal(np.asarray(sc.sorted_hi), hi[perm])
    np.testing.assert_array_equal(np.asarray(sc.sorted_lo), lo[perm])
    # ascending pair order, sentinels last
    s_hi, s_lo = np.asarray(sc.sorted_hi), np.asarray(sc.sorted_lo)
    key = s_hi.astype(np.int64) * 2**32 + s_lo.astype(np.int64)
    assert np.all(np.diff(key) >= 0)


# ---------------------------------------------------------------------------
# quantization in the key domain
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("stride", [2, 4, 8, 32])
def test_quantize_keys_matches_quantize_coords(stride):
    rng = np.random.default_rng(3)
    coords = np.stack([
        rng.integers(0, 3, 128),
        rng.integers(-500, 500, 128),
        rng.integers(-500, 500, 128),
        rng.integers(-500, 500, 128),
    ], axis=1).astype(np.int32)
    hi, lo = pack_np(coords, np.ones(128, bool))
    qhi, qlo = PK.quantize_keys(jnp.asarray(hi), jnp.asarray(lo), stride)
    expect_hi, expect_lo = pack_np(
        np.asarray(M.quantize_coords(jnp.asarray(coords), stride)),
        np.ones(128, bool))
    np.testing.assert_array_equal(np.asarray(qhi), expect_hi)
    np.testing.assert_array_equal(np.asarray(qlo), expect_lo)


def test_quantize_keys_preserves_sentinel():
    hi = jnp.asarray(np.array([PK.KEY_HI_SENTINEL], np.int32))
    lo = jnp.asarray(np.array([PK.KEY_LO_SENTINEL], np.uint32))
    qhi, qlo = PK.quantize_keys(hi, lo, 4)
    assert int(qhi[0]) == int(PK.KEY_HI_SENTINEL)
    assert int(qlo[0]) == int(PK.KEY_LO_SENTINEL)


# ---------------------------------------------------------------------------
# binary search
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,nq", [(1, 16), (7, 64), (256, 300), (1000, 50)])
def test_searchsorted_pair_matches_numpy(n, nq):
    rng = np.random.default_rng(4)
    hi = np.sort(rng.integers(0, 50, n)).astype(np.int32)
    lo = rng.integers(0, 2**32, n, dtype=np.uint64).astype(np.uint32)
    # sort lo within equal hi groups to get ascending pairs
    order = np.lexsort((lo, hi))
    hi, lo = hi[order], lo[order]
    q_hi = rng.integers(0, 50, nq).astype(np.int32)
    q_lo = rng.integers(0, 2**32, nq, dtype=np.uint64).astype(np.uint32)
    got = np.asarray(PK.searchsorted_pair(
        jnp.asarray(hi), jnp.asarray(lo), jnp.asarray(q_hi),
        jnp.asarray(q_lo)))
    key = hi.astype(np.uint64) * 2**32 + lo.astype(np.uint64)
    qkey = q_hi.astype(np.uint64) * 2**32 + q_lo.astype(np.uint64)
    expect = np.searchsorted(key, qkey, side="left")
    np.testing.assert_array_equal(got, expect)


# ---------------------------------------------------------------------------
# exact-match lookup: the merge and binary lowerings against numpy
# ---------------------------------------------------------------------------

LOOKUP_CASES = ["dense", "masked", "empty", "sentinel_queries",
                "budget_edges"]


def _lookup_clouds(case, cap, seed):
    """(table, query) sorted clouds of `cap` rows for one case, both in
    shuffled row order (perm is not the identity).  The query cloud is
    the table's coords under its own mask, except: "empty" masks every
    table row, "sentinel_queries" every query row.  Under all 27 unit
    offsets neighbours give duplicate query keys across offsets."""
    rng = np.random.default_rng(seed)
    g = int(np.ceil(cap ** (1 / 3))) + 2
    lin = rng.choice(2 * g ** 3, size=cap, replace=False)
    b, rest = np.divmod(lin, g ** 3)
    xyz = np.stack(np.unravel_index(rest, (g, g, g)), axis=1)
    if case == "budget_edges":
        # half of each axis at COORD_MIN.., half at ..COORD_MAX: their
        # +-1 shifts leave the key budget and saturate to the sentinel
        xyz = np.where(xyz < g // 2, PK.COORD_MIN + xyz, PK.COORD_MAX - xyz)
    coords = np.concatenate([b[:, None], xyz], axis=1).astype(np.int32)
    mask = rng.random(cap) < 0.6 if case == "masked" else np.ones(cap, bool)

    def cloud(m):
        c = np.where(m[:, None], coords, M.SENTINEL)
        return M.sort_cloud(M.PointCloud(jnp.asarray(c), jnp.asarray(m), 1))

    table = cloud(np.zeros(cap, bool) if case == "empty" else mask)
    query = cloud(np.zeros(cap, bool) if case == "sentinel_queries"
                  else np.ones(cap, bool) if case == "empty" else mask)
    return table, query


@pytest.mark.parametrize("case", LOOKUP_CASES)
@pytest.mark.parametrize("cap", [1, 7, 128, 4096])
def test_lookup_merge_matches_binary_and_numpy(cap, case):
    """Both lookups behind `match_table` give the numpy answer bit for
    bit: np.searchsorted over the composed uint64 keys of the shifted
    query coords, hit where the key at the position equals the
    (non-sentinel) query.  The merge takes the query cloud's sorted keys,
    the binary search its packed shifted coords."""
    table, query = _lookup_clouds(case, cap, seed=cap)
    offs = M.kernel_offsets(3, 3, 1)
    q = np.asarray(query.pc.coords)[None].repeat(len(offs), 0)
    q[..., 1:] += offs[:, None, :]
    q_hi, q_lo = PK.pack_coords(jnp.asarray(q), query.pc.mask[None])
    t = (table.sorted_hi, table.sorted_lo, table.perm)
    binary = np.asarray(jax.jit(PK.lookup_binary)(*t, q_hi, q_lo))
    merge = np.asarray(jax.jit(
        lambda *a: PK.lookup_merge(*a, offs))(
            *t, query.sorted_hi, query.sorted_lo, query.perm))

    key = PK.compose_key64(table.sorted_hi, table.sorted_lo)
    qkey = PK.compose_key64(q_hi, q_lo)
    pos = np.minimum(np.searchsorted(key, qkey, side="left"), cap - 1)
    hit = (key[pos] == qkey) & (qkey != PK.KEY64_SENTINEL)
    expect = np.where(hit, np.asarray(table.perm)[pos], -1)

    assert merge.shape == binary.shape == (27, cap)
    np.testing.assert_array_equal(merge, expect)
    np.testing.assert_array_equal(binary, expect)
    if case in ("empty", "sentinel_queries"):
        assert not hit.any()
    else:
        # every valid row finds itself under the zero offset
        mask = np.asarray(query.pc.mask)
        assert (merge[13][mask] == np.arange(cap)[mask]).all()
