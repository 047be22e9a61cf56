"""Packed coordinate keys: the v2 ranking engine's scalar key domain.

PointAcc's Mapping Unit (paper §4.1) ranks *coordinates*; the v1 software
realisation sorted 4-6 parallel int32 columns lexicographically for every
kernel offset.  This module collapses a (batch, x, y, z) coordinate into one
62-bit packed key so each ranking op touches a single logical scalar:

    bit 61..48   batch  (14 bits, unsigned,  0 .. 16383)
    bit 47..32   x+2^15 (16 bits, biased,   -32768 .. 32767)
    bit 31..16   y+2^15 (16 bits, biased)
    bit 15..0    z+2^15 (16 bits, biased)

The key is stored as an (int32 hi, uint32 lo) word pair — hi carries
(batch | x), lo carries (y | z) — because int64 is a second-class citizen in
32-bit-default JAX and on TPU, where XLA would emulate it as an i32 pair
anyway.  Lexicographic (hi, lo) order over the pair IS ascending order of the
logical 62-bit key, which in turn IS the lexicographic (batch, x, y, z) order
the v1 engine used: the per-axis bias is monotone, so every downstream
structure (sorted clouds, deduped output clouds) is bit-identical to v1's.

Invalid/overflowing coordinates saturate to the sentinel key
(KEY_HI_SENTINEL is unreachable by any in-range coordinate: the max valid hi
is (16383<<16)|65535 = 2^30-1 < 2^31-1), so an out-of-budget coordinate can
never alias a valid key — it sorts to the end and fails every equality test.

Quantization (paper §2.1.1, "clearing the lowest log2(ts) bits") works
directly in the key domain: the bias 2^15 is divisible by every power-of-two
stride <= 2^15, so clearing the low log2(ts) bits of each 16-bit field is
exactly quantize-then-pack.
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp
from jax import lax

# Coordinate-domain sentinel (shared with repro.core.mapping.SENTINEL).
COORD_SENTINEL = np.int32(2**30 - 1)

BATCH_BITS = 14
SPATIAL_BITS = 16
BIAS = 1 << (SPATIAL_BITS - 1)              # 32768
COORD_MIN = -BIAS                           # -32768
COORD_MAX = BIAS - 1                        # 32767
BATCH_MAX = (1 << BATCH_BITS) - 1           # 16383

KEY_HI_SENTINEL = np.int32(2**31 - 1)
KEY_LO_SENTINEL = np.uint32(2**32 - 1)

_LO16 = np.uint32(0xFFFF)


def pack_coords(coords: jnp.ndarray, mask: jnp.ndarray | None = None):
    """(..., 4) int32 coords -> (hi int32, lo uint32) packed key words.

    Rows that are masked out, or whose batch/coordinate falls outside the
    per-field bit budget, saturate to the sentinel key — never to an aliased
    valid key.
    """
    b = coords[..., 0]
    x = coords[..., 1]
    y = coords[..., 2]
    z = coords[..., 3]
    ok = (b >= 0) & (b <= BATCH_MAX)
    for c in (x, y, z):
        ok = ok & (c >= COORD_MIN) & (c <= COORD_MAX)
    if mask is not None:
        ok = ok & mask
    # Out-of-range lanes may wrap below; `ok` discards them.
    hi = (b << SPATIAL_BITS) | (x + BIAS)
    lo = ((y + BIAS).astype(jnp.uint32) << SPATIAL_BITS) \
        | (z + BIAS).astype(jnp.uint32)
    hi = jnp.where(ok, hi, KEY_HI_SENTINEL)
    lo = jnp.where(ok, lo, KEY_LO_SENTINEL)
    return hi, lo


def is_sentinel_key(hi: jnp.ndarray) -> jnp.ndarray:
    """Valid keys have hi <= 2^30-1, so the hi word alone identifies them."""
    return hi == KEY_HI_SENTINEL


def unpack_keys(hi: jnp.ndarray, lo: jnp.ndarray) -> jnp.ndarray:
    """Inverse of pack_coords: (hi, lo) -> (..., 4) int32 coords.

    Sentinel keys unpack to all-COORD_SENTINEL rows (the masked-row
    convention of repro.core.mapping).
    """
    valid = ~is_sentinel_key(hi)
    b = hi >> SPATIAL_BITS
    x = (hi & np.int32(0xFFFF)) - BIAS
    y = (lo >> SPATIAL_BITS).astype(jnp.int32) - BIAS
    z = (lo & _LO16).astype(jnp.int32) - BIAS
    coords = jnp.stack([b, x, y, z], axis=-1)
    return jnp.where(valid[..., None], coords, COORD_SENTINEL)


def quantize_keys(hi: jnp.ndarray, lo: jnp.ndarray, stride: int):
    """Clear the low log2(stride) bits of every spatial field, in place in
    the key domain.  Sentinel keys are preserved (clearing their bits would
    fabricate a valid-looking key)."""
    if stride == 1:
        return hi, lo
    k = int(np.log2(stride))
    if 2 ** k != stride:
        raise ValueError(f"stride must be a power of two, got {stride}")
    if k > SPATIAL_BITS - 1:
        raise ValueError(f"stride {stride} exceeds the per-axis bit budget")
    low = stride - 1
    qhi = hi & np.int32(~low)
    qlo = lo & np.uint32(~((low << SPATIAL_BITS) | low) & 0xFFFFFFFF)
    sent = is_sentinel_key(hi)
    return (jnp.where(sent, KEY_HI_SENTINEL, qhi),
            jnp.where(sent, KEY_LO_SENTINEL, qlo))


# -- host-side (numpy) key helpers ------------------------------------------
#
# The partition planner (repro.partition) ranks and range-splits CITY-SCALE
# clouds on the host, where shapes are dynamic and a device round-trip per
# binary search would dominate.  These mirror pack/quantize exactly in a
# single uint64 word: the packed 62-bit key fits uint64 with bits 63..62
# zero, so unsigned uint64 order == lexicographic (hi signed-nonnegative,
# lo unsigned) order == logical key order.

KEY64_BITS = BATCH_BITS + 3 * SPATIAL_BITS          # 62
KEY64_SENTINEL = np.uint64(
    (np.uint64(np.uint32(KEY_HI_SENTINEL)) << np.uint64(32))
    | np.uint64(KEY_LO_SENTINEL))


def compose_key64(hi, lo) -> np.ndarray:
    """(hi int32, lo uint32) word pairs -> one uint64 key, order-preserving
    (valid hi is never negative, so the unsigned composition keeps the
    lexicographic pair order)."""
    return ((np.asarray(hi).astype(np.int64).astype(np.uint64)
             << np.uint64(32))
            | np.asarray(lo, np.uint32).astype(np.uint64))


def pack_coords_host(coords, mask=None) -> np.ndarray:
    """Host mirror of `pack_coords`, composed to uint64: (..., 4) int32
    coords -> (...,) uint64 keys with out-of-budget / masked rows saturated
    to KEY64_SENTINEL."""
    coords = np.asarray(coords)
    b = coords[..., 0].astype(np.int64)
    x = coords[..., 1].astype(np.int64)
    y = coords[..., 2].astype(np.int64)
    z = coords[..., 3].astype(np.int64)
    ok = (b >= 0) & (b <= BATCH_MAX)
    for c in (x, y, z):
        ok = ok & (c >= COORD_MIN) & (c <= COORD_MAX)
    if mask is not None:
        ok = ok & np.asarray(mask, bool)
    key = ((b << (3 * SPATIAL_BITS))
           | ((x + BIAS) << (2 * SPATIAL_BITS))
           | ((y + BIAS) << SPATIAL_BITS)
           | (z + BIAS)).astype(np.uint64)
    return np.where(ok, key, KEY64_SENTINEL)


def unpack_key64(keys) -> np.ndarray:
    """Inverse of `pack_coords_host`: (...,) uint64 -> (..., 4) int32
    coords; sentinel keys unpack to all-COORD_SENTINEL rows."""
    keys = np.asarray(keys, np.uint64)
    k = keys.astype(np.int64)
    b = k >> (3 * SPATIAL_BITS)
    x = ((k >> (2 * SPATIAL_BITS)) & 0xFFFF) - BIAS
    y = ((k >> SPATIAL_BITS) & 0xFFFF) - BIAS
    z = (k & 0xFFFF) - BIAS
    coords = np.stack([b, x, y, z], axis=-1).astype(np.int32)
    return np.where((keys == KEY64_SENTINEL)[..., None],
                    np.int32(COORD_SENTINEL), coords)


def quantize_key64(keys, stride: int) -> np.ndarray:
    """Host mirror of `quantize_keys` on composed keys: clear the low
    log2(stride) bits of each 16-bit spatial field; sentinels preserved."""
    if stride == 1:
        return np.asarray(keys, np.uint64)
    k = int(np.log2(stride))
    if 2 ** k != stride:
        raise ValueError(f"stride must be a power of two, got {stride}")
    if k > SPATIAL_BITS - 1:
        raise ValueError(f"stride {stride} exceeds the per-axis bit budget")
    low = stride - 1
    clear = np.uint64((low << (2 * SPATIAL_BITS)) | (low << SPATIAL_BITS)
                      | low)
    keys = np.asarray(keys, np.uint64)
    q = keys & ~clear
    return np.where(keys == KEY64_SENTINEL, KEY64_SENTINEL, q)


def searchsorted_pair(s_hi: jnp.ndarray, s_lo: jnp.ndarray,
                      q_hi: jnp.ndarray, q_lo: jnp.ndarray) -> jnp.ndarray:
    """side='left' binary search of query keys in an ascending key array.

    The sorted operands are the (hi, lo) words of a key array ordered by
    lexicographic (hi signed, lo unsigned) comparison — i.e. by the logical
    62-bit key.  Queries may have any shape; returns int32 positions in
    [0, n].  This is the paper's log-depth comparison network: ceil(log2 n)
    rounds of vectorised gather + compare, no data movement.
    """
    n = s_hi.shape[0]
    lo_i = jnp.zeros(q_hi.shape, jnp.int32)
    hi_i = jnp.full(q_hi.shape, n, jnp.int32)

    def step(_, carry):
        lo_i, hi_i = carry
        active = lo_i < hi_i
        mid = (lo_i + hi_i) >> 1
        midc = jnp.clip(mid, 0, n - 1)
        m_hi = s_hi[midc]
        m_lo = s_lo[midc]
        less = (m_hi < q_hi) | ((m_hi == q_hi) & (m_lo < q_lo))
        lo_i = jnp.where(active & less, mid + 1, lo_i)
        hi_i = jnp.where(active & ~less, mid, hi_i)
        return lo_i, hi_i

    n_steps = max(1, int(np.ceil(np.log2(n + 1))) + 1)
    lo_i, _ = lax.fori_loop(0, n_steps, step, (lo_i, hi_i))
    return lo_i


# -- exact-match lookup: table row of each shifted query key, or -1 ---------
#
# Two implementations of one lookup, bit-identical in result on every cloud
# inside the key budget; which one a backend takes is decided in
# repro.core.mapping.match_table.

def lookup_binary(s_hi: jnp.ndarray, s_lo: jnp.ndarray, rows: jnp.ndarray,
                  q_hi: jnp.ndarray, q_lo: jnp.ndarray) -> jnp.ndarray:
    """Row of each packed query key (any shape) in a sorted key table, or
    -1: binary search, then three gathers at the hit position.  `rows`
    maps sorted position -> table row; sentinel queries never match.
    Cheap where a gather is cheap (CPU); on the TPU each of its ~log2(n)
    rounds is an element-by-element gather over every query."""
    n = s_hi.shape[0]
    pos = jnp.clip(searchsorted_pair(s_hi, s_lo, q_hi, q_lo), 0, n - 1)
    hit = (s_hi[pos] == q_hi) & (s_lo[pos] == q_lo) & ~is_sentinel_key(q_hi)
    return jnp.where(hit, rows[pos], jnp.int32(-1))


def shift_keys(hi: jnp.ndarray, lo: jnp.ndarray, offsets):
    """Packed keys (m,) of coords c -> keys (K, m) of c + offsets[k] in
    the key domain, plus (K, m) `ok`: the shifted coords lie inside the
    key budget and the source key was not the sentinel.

    The shift is one two-word integer add of the constant
    dx*2^32 + dy*2^16 + dz, so it preserves key order along m: a sorted
    key array stays sorted under every offset.  Where `ok` holds the
    result is exactly `pack_coords(c + offsets[k])`; elsewhere it is an
    order-keeping placeholder (sentinel sources stay the sentinel) that
    may alias a valid key, so callers must mask it with `ok`.
    """
    offsets = jnp.asarray(offsets, jnp.int32)
    dx, dy, dz = (offsets[:, i:i + 1] for i in range(3))
    low = dy * (1 << SPATIAL_BITS) + dz
    s_lo = lo[None] + low.astype(jnp.uint32)
    s_hi = (hi[None] + dx - (low < 0).astype(jnp.int32)
            + (s_lo < lo[None]).astype(jnp.int32))
    src = ~is_sentinel_key(hi)[None]
    ok = src
    for field, d in (((hi & np.int32(0xFFFF)), dx),
                     ((lo >> SPATIAL_BITS).astype(jnp.int32), dy),
                     ((lo & _LO16).astype(jnp.int32), dz)):
        f = field[None] + d
        ok = ok & (f >= 0) & (f <= 0xFFFF)
    return (jnp.where(src, s_hi, KEY_HI_SENTINEL),
            jnp.where(src, s_lo, KEY_LO_SENTINEL), ok)


def _after(a, b):
    """a sorts after b: (hi signed, lo unsigned, aux descending)."""
    (ah, al, ax), (bh, bl, bx) = a, b
    return (ah > bh) | ((ah == bh) & ((al > bl) | ((al == bl) & (ax < bx))))


def _partner(x: jnp.ndarray, s: int, upper: jnp.ndarray) -> jnp.ndarray:
    """Compare-exchange partner at distance s along the last axis."""
    return jnp.where(upper, jnp.roll(x, s, axis=-1), jnp.roll(x, -s, axis=-1))


def lookup_merge(t_hi: jnp.ndarray, t_lo: jnp.ndarray, t_rows: jnp.ndarray,
                 q_hi: jnp.ndarray, q_lo: jnp.ndarray, q_rows: jnp.ndarray,
                 offsets) -> jnp.ndarray:
    """table[k, j] = row of the sorted key table at key(query row j +
    offsets[k]), or -1: the paper's merge sorter + DetectIntersection,
    with neither a gather nor a loop.

    Both clouds are sorted key arrays (`t_rows`/`q_rows` map sorted
    position -> row).  `shift_keys` keeps each offset's queries sorted, so
    each of the K rows is one bitonic merge: the table ascending, padding
    that sorts last, then the queries descending, merged by log2(L)
    compare-exchange stages of rolls and selects over (K, L).  Ties put
    the table first (the third word: table row >= 0, query -1, masked
    query -2, padding -3), so a query matches iff the element just before
    it is a table row with its key: queries of one offset are distinct
    and table keys are distinct.  Replaying the recorded swaps in reverse
    routes each answer back to its query, and one single-key sort on
    `q_rows` returns the rows to query order.
    """
    n, m = t_hi.shape[0], q_hi.shape[0]
    s_hi, s_lo, ok = shift_keys(q_hi, q_lo, offsets)
    k = s_hi.shape[0]
    width = 1 << int(np.ceil(np.log2(n + m)))

    def row(table, pad, queries):
        return jnp.concatenate(
            [jnp.broadcast_to(table, (k, n)),
             jnp.full((k, width - n - m), pad, queries.dtype),
             queries[:, ::-1]], axis=1)

    # masked table rows share the sentinel key: one word for all of them
    # keeps the table's run ascending (they never match)
    t_aux = jnp.where(is_sentinel_key(t_hi), 0, t_rows.astype(jnp.int32))
    words = (row(t_hi, KEY_HI_SENTINEL, s_hi),
             row(t_lo, KEY_LO_SENTINEL, s_lo),
             row(t_aux, -3, jnp.where(ok, jnp.int32(-1), jnp.int32(-2))))
    pos = lax.broadcasted_iota(jnp.int32, (k, width), 1)
    stages = []
    s = width // 2
    while s >= 1:
        upper = (pos & s) != 0
        other = tuple(_partner(w, s, upper) for w in words)
        first = tuple(jnp.where(upper, o, w) for w, o in zip(words, other))
        second = tuple(jnp.where(upper, w, o) for w, o in zip(words, other))
        swap = _after(first, second)
        words = tuple(jnp.where(swap, o, w) for w, o in zip(words, other))
        stages.append((s, upper, swap))
        s //= 2

    hi, lo, aux = words
    p_hi, p_lo, p_aux = (jnp.roll(w, 1, axis=1) for w in words)
    hit = (aux == -1) & (p_aux >= 0) & (p_hi == hi) & (p_lo == lo)
    found = jnp.where(hit, p_aux, jnp.int32(-1))
    for s, upper, swap in reversed(stages):
        found = jnp.where(swap, _partner(found, s, upper), found)
    found = found[:, width - m:][:, ::-1]         # sorted query order
    _, found = lax.sort((jnp.broadcast_to(q_rows, (k, m)), found),
                        dimension=1, num_keys=1)
    return found
