"""Ranking-based mapping operations (PointAcc Mapping Unit, paper §4.1).

PointAcc's key insight: every mapping operation a point-cloud network needs
(kernel mapping, k-nearest-neighbours, ball query, farthest-point sampling,
coordinate quantization) can be expressed through *ranking* primitives —
MergeSort / TopK / Max over coordinate or distance keys — instead of hash
tables.  Hash tables need random parallel SRAM access (an O(N^2) crossbar in
silicon); sorting networks are log-depth and fully parallel.  The same
trade-off holds on TPU: XLA has no efficient random-access hash path, but its
bitonic `lax.sort` *is* a sorting network.  This module is therefore a direct
software embodiment of the paper's Mapping Unit:

  * kernel mapping  -> sort-merge intersection of the (-delta)-shifted input
                       cloud with the output cloud (paper Fig. 9), realised as
                       one lexicographic `lax.sort` + adjacent-equality
                       detection (paper's DetectIntersection stage).
  * quantization    -> clearing the low log2(ts) bits of the coordinates
                       (paper §2.1.1), i.e. arithmetic shift right then left.
  * unique (output cloud construction) -> sort + adjacent-dedup + re-sort
                       (compaction without dynamic shapes).

All functions are jit-friendly: point clouds are fixed-capacity arrays with
validity masks; invalid slots hold SENTINEL coordinates which sort to the end.

Coordinate convention: `coords` is (N, 1+D) int32 with the batch index in
column 0 and D spatial dims after it.  `stride` (the paper's tensor stride
`ts`) is a static python int and always a power of two.

Two ranking engines coexist:

  * v1 ("lex"): one full lexicographic merge-sort of both clouds per kernel
    offset (the original, any spatial dimensionality).
  * v2 ("packed", default for D=3): bit-pack each coordinate into one 62-bit
    key (repro.core.packed), sort every cloud ONCE into a `SortedCloud`
    cache, and realise each kernel offset as a lookup of the shifted
    output keys in the sorted input keys (`match_table`) — K merge-sorts
    collapse to 1 sort + K lookups, and the sorted cloud is reused by every
    mapping op at the same stride (all submanifold convs of a network level
    share one sort; `downsample_sorted` dedups the already-packed keys).
    The lookup is chosen by backend when the program is lowered: on the
    TPU a bitonic merge of the (already sorted) shifted output keys with
    the input keys, gather- and loop-free; elsewhere a vectorised binary
    search.
"""

from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import packed as PK

# Large-but-safe sentinel: room to add kernel offsets without int32 overflow.
SENTINEL = PK.COORD_SENTINEL

# Engine used when callers don't pass one explicitly.  "v2" is the packed-key
# engine; "v1" is the per-offset lexicographic merge-sort kept for
# cross-checking and for spatial dimensionalities != 3.
DEFAULT_ENGINE = "v2"


class PointCloud(NamedTuple):
    """A fixed-capacity, masked, sparse voxel point cloud."""

    coords: jnp.ndarray  # (N, 1+D) int32; invalid rows = SENTINEL
    mask: jnp.ndarray    # (N,) bool
    stride: int          # static tensor stride (power of two)

    @property
    def capacity(self) -> int:
        return self.coords.shape[0]

    @property
    def ndim_spatial(self) -> int:
        return self.coords.shape[1] - 1

    def num_valid(self) -> jnp.ndarray:
        return jnp.sum(self.mask.astype(jnp.int32))


class KernelMaps(NamedTuple):
    """Input/output maps for one sparse convolution (paper's map tuples).

    For each kernel offset k (the weight index w_n), row k lists the matched
    (input index, output index) pairs, padded with -1 / valid=False.

    `inv` is the inverse table inv[k, j] = input index feeding output j under
    offset k (-1 if none).  The v2 engine emits it for free — its key
    lookup is indexed by output row, so the hits ARE the inverse table —
    letting the Pallas FoD kernel skip the scatter pass that v1 needed
    (kernels/spconv/ops.invert_maps).  None on the v1 path.

    `inv_t` is the same table for the *swapped* maps: inv_t[k, i] = output
    index feeding input i when the maps are used transposed (decoder
    up-convolution).  The v2 engine computes it with one extra key lookup
    per offset (mapping.match_table) so `swap()` hands the Pallas kernel a
    ready inverse table — the decoder never falls back to a scatter pass.
    """

    in_idx: jnp.ndarray   # (K, cap) int32, -1 padded
    out_idx: jnp.ndarray  # (K, cap) int32, -1 padded
    valid: jnp.ndarray    # (K, cap) bool
    offsets: np.ndarray   # (K, D) static numpy offsets (units of input stride)
    inv: jnp.ndarray | None = None    # (K, out_cap) int32, -1 = no map
    inv_t: jnp.ndarray | None = None  # (K, in_cap) int32, -1 = no map

    def swap(self, require_inverse: bool = False) -> "KernelMaps":
        """Transpose the maps: used for transposed (up-sampling) convolution.

        MinkowskiEngine-style: an upsample conv from coarse->fine reuses the
        maps of the corresponding fine->coarse conv with in/out roles swapped
        (and mirrored weight offsets).  The inverse tables swap roles with
        them, so a v2-built map keeps its scatter-free Pallas path in both
        directions.

        Maps built by the v1 engine (or a v2 build whose explicit `cap`
        dropped the tables) carry NO transposed inverse table: the Pallas
        flows then rebuild one with a scatter pass (numerically identical,
        just not scatter-free).  Pass `require_inverse=True` to make that
        silent downgrade a loud error instead.
        """
        if require_inverse and self.inv_t is None:
            raise ValueError(
                "swapped maps carry no inverse table (inv_t is None): the "
                "maps were built by the v1 engine or with an explicit cap "
                "that dropped them.  The Pallas flows would fall back to a "
                "scatter-built inverse; rebuild the maps with engine='v2' "
                "and the default cap for the scatter-free transposed path")
        return KernelMaps(self.out_idx, self.in_idx, self.valid,
                          -self.offsets, inv=self.inv_t, inv_t=self.inv)


def make_point_cloud(coords: jnp.ndarray, mask: jnp.ndarray,
                     stride: int = 1) -> PointCloud:
    """Normalise a raw (coords, mask) pair: sentinel-fill invalid rows."""
    coords = jnp.where(mask[:, None], coords.astype(jnp.int32), SENTINEL)
    return PointCloud(coords, mask, stride)


# ---------------------------------------------------------------------------
# Coordinate quantization (paper §2.1.1: "clearing the lowest log2(ts) bits")
# ---------------------------------------------------------------------------

def quantize_coords(coords: jnp.ndarray, stride: int) -> jnp.ndarray:
    """q = floor(p / ts) * ts for ts a power of two, batch col untouched.

    Arithmetic shift right then left implements floor-division semantics for
    negative coordinates too (two's complement), exactly the paper's
    "clearing the lowest log2(ts) bits" hardware trick.
    """
    if stride == 1:
        return coords
    k = int(np.log2(stride))
    if 2 ** k != stride:
        raise ValueError(f"stride must be a power of two, got {stride}")
    spatial = (coords[:, 1:] >> k) << k
    return jnp.concatenate([coords[:, :1], spatial], axis=1)


# ---------------------------------------------------------------------------
# Lexicographic sort helpers (the MergeSort stage of the Mapping Unit)
# ---------------------------------------------------------------------------

def _lex_sort(columns: Sequence[jnp.ndarray], num_keys: int):
    """Stable lexicographic sort of parallel 1-D arrays on the first
    `num_keys` columns.  This is the software analogue of the paper's
    merge-sorting network (stage MS)."""
    return lax.sort(tuple(columns), dimension=0, num_keys=num_keys,
                    is_stable=True)


def unique_coords(coords: jnp.ndarray, mask: jnp.ndarray):
    """Deduplicate a masked coordinate set without dynamic shapes.

    Ranking-based: sort lexicographically, mark first occurrences (adjacent
    inequality), overwrite duplicates with SENTINEL, re-sort to compact valid
    entries to the front.  Two passes through the sorting network — the same
    dataflow PointAcc uses for output-cloud construction during
    downsampling.
    """
    n, d = coords.shape
    coords = jnp.where(mask[:, None], coords, SENTINEL)
    cols = tuple(coords[:, i] for i in range(d))
    sorted_cols = _lex_sort(cols, num_keys=d)
    sorted_coords = jnp.stack(sorted_cols, axis=1)
    prev = jnp.roll(sorted_coords, 1, axis=0)
    is_first = jnp.any(sorted_coords != prev, axis=1)
    is_first = is_first.at[0].set(True)
    new_mask = is_first & jnp.all(sorted_coords != SENTINEL, axis=1)
    deduped = jnp.where(new_mask[:, None], sorted_coords, SENTINEL)
    # compaction pass: invalids (SENTINEL) sort to the end
    cols2 = tuple(deduped[:, i] for i in range(d))
    compact_cols = _lex_sort(cols2, num_keys=d)
    compact = jnp.stack(compact_cols, axis=1)
    out_mask = jnp.all(compact != SENTINEL, axis=1)
    return compact, out_mask


def downsample(pc: PointCloud, factor: int = 2) -> PointCloud:
    """Output point cloud construction for a strided sparse conv.

    Quantize to the coarser stride then deduplicate (both ranking-based).
    """
    new_stride = pc.stride * factor
    q = quantize_coords(pc.coords, new_stride)
    q = jnp.where(pc.mask[:, None], q, SENTINEL)
    coords, mask = unique_coords(q, pc.mask)
    return PointCloud(coords, mask, new_stride)


# ---------------------------------------------------------------------------
# Kernel mapping (paper §4.1.1 + Fig. 9): sort-merge intersection
# ---------------------------------------------------------------------------

def kernel_offsets(kernel_size: int, ndim: int,
                   stride: int) -> np.ndarray:
    """All kernel offsets delta in {-(k//2)..k//2}^D, scaled by the input
    tensor stride.  Static (numpy) — offsets index the weight tensor."""
    half = kernel_size // 2
    rng = np.arange(-half, half + 1) if kernel_size % 2 == 1 else \
        np.arange(0, kernel_size)
    grids = np.meshgrid(*([rng] * ndim), indexing="ij")
    offs = np.stack([g.reshape(-1) for g in grids], axis=1)
    return (offs * stride).astype(np.int32)


def _intersect_one_offset(shifted: jnp.ndarray, in_mask: jnp.ndarray,
                          out_coords: jnp.ndarray, out_mask: jnp.ndarray,
                          cap: int):
    """Find coordinate-equal pairs between one shifted input cloud and the
    output cloud.  Paper Fig. 9: merge-sort both clouds into one array and
    detect adjacent duplicates (DetectIntersection stage).

    Both clouds are coordinate-*sets* (no internal duplicates), so each match
    is 1:1 and adjacency detection is exact.  The tag column (input=0,
    output=1) is the last sort key, guaranteeing the input element of a
    matching pair immediately precedes the output element.
    """
    n, d = shifted.shape
    m = out_coords.shape[0]
    shifted = jnp.where(in_mask[:, None], shifted, SENTINEL)
    out_c = jnp.where(out_mask[:, None], out_coords, SENTINEL)

    merged = jnp.concatenate([shifted, out_c], axis=0)          # (n+m, d)
    tag = jnp.concatenate([jnp.zeros(n, jnp.int32),
                           jnp.ones(m, jnp.int32)])
    payload = jnp.concatenate([jnp.arange(n, dtype=jnp.int32),
                               jnp.arange(m, dtype=jnp.int32)])
    valid = jnp.concatenate([in_mask, out_mask])

    cols = tuple(merged[:, i] for i in range(d)) + (tag, payload, valid)
    sorted_cols = _lex_sort(cols, num_keys=d + 1)
    s_coords = jnp.stack(sorted_cols[:d], axis=1)
    s_tag, s_payload, s_valid = sorted_cols[d], sorted_cols[d + 1], \
        sorted_cols[d + 2]

    nxt_coords = jnp.roll(s_coords, -1, axis=0)
    nxt_tag = jnp.roll(s_tag, -1)
    nxt_payload = jnp.roll(s_payload, -1)
    nxt_valid = jnp.roll(s_valid, -1)

    is_pair = (jnp.all(s_coords == nxt_coords, axis=1)
               & (s_tag == 0) & (nxt_tag == 1)
               & s_valid & nxt_valid)
    is_pair = is_pair.at[-1].set(False)

    in_i = jnp.where(is_pair, s_payload, jnp.int32(-1))
    out_i = jnp.where(is_pair, nxt_payload, jnp.int32(-1))

    # Compact matches to the front (one more ranking pass): sort by
    # (!is_pair) keeps relative (coordinate) order of the matches.
    order_key = (~is_pair).astype(jnp.int32)
    _, in_i, out_i, pair_sorted = _lex_sort(
        (order_key, in_i, out_i, is_pair), num_keys=1)
    return in_i[:cap], out_i[:cap], pair_sorted[:cap]


def kernel_map(in_pc: PointCloud, out_pc: PointCloud, kernel_size: int,
               cap: int | None = None) -> KernelMaps:
    """Build the full kernel maps {(p_i, q_k, w_n)} for a sparse convolution.

    For each weight offset delta, intersects the (-delta)-shifted input cloud
    with the output cloud (paper §4.1.1).  vmapped over offsets — the
    point-level parallelism the paper exploits, with offset-level parallelism
    on top.
    """
    offs = kernel_offsets(kernel_size, in_pc.ndim_spatial, in_pc.stride)
    cap = cap if cap is not None else min(in_pc.capacity, out_pc.capacity)
    # shift only spatial dims; batch column gets zero offset
    offs_full = np.concatenate(
        [np.zeros((offs.shape[0], 1), np.int32), offs], axis=1)

    def one(off):
        shifted = in_pc.coords - off[None, :]   # I' = {p - delta}
        return _intersect_one_offset(shifted, in_pc.mask, out_pc.coords,
                                     out_pc.mask, cap)

    in_idx, out_idx, valid = jax.vmap(one)(jnp.asarray(offs_full))
    return KernelMaps(in_idx, out_idx, valid, offs)


# ---------------------------------------------------------------------------
# v2 packed-key engine: one sort per cloud, one key lookup per offset
# ---------------------------------------------------------------------------

class SortedCloud(NamedTuple):
    """A point cloud plus its once-computed packed-key ranking structure.

    This is the cache the v2 engine threads through a network: every mapping
    op against the same cloud (27 submanifold offsets, the stride-2 down
    conv, coordinate dedup) reuses the single sort instead of re-ranking.

    sorted_hi/sorted_lo are the packed key words in ascending (logical
    62-bit) key order with sentinels (invalid rows) at the end; perm maps
    sorted position -> original row: sorted = keys[perm].
    """

    pc: PointCloud
    sorted_hi: jnp.ndarray  # (N,) int32
    sorted_lo: jnp.ndarray  # (N,) uint32
    perm: jnp.ndarray       # (N,) int32


def sort_cloud(pc: PointCloud) -> SortedCloud:
    """Rank a cloud once: pack coords to 62-bit keys and sort them.

    The only ranking sort the v2 engine runs for a given cloud — every
    kernel-offset lookup afterwards is `match_table` against it.
    """
    if pc.ndim_spatial != 3:
        raise ValueError("packed-key engine requires 3 spatial dims, got "
                         f"{pc.ndim_spatial}; use engine='v1'")
    hi, lo = PK.pack_coords(pc.coords, pc.mask)
    if not isinstance(hi, jax.core.Tracer):
        # Eager call: fail loudly on valid points outside the key budget
        # instead of silently dropping them from every map.  (Under jit the
        # data is unavailable; the saturate-to-sentinel semantics — and the
        # v1 escape hatch — are documented in README.)
        n_bad = int(jnp.sum(PK.is_sentinel_key(hi) & pc.mask))
        if n_bad:
            raise ValueError(
                f"{n_bad} valid point(s) outside the packed-key budget "
                f"(batch 0..{PK.BATCH_MAX}, coords {PK.COORD_MIN}.."
                f"{PK.COORD_MAX}); use engine='v1' for such clouds")
    iota = jnp.arange(pc.capacity, dtype=jnp.int32)
    s_hi, s_lo, perm = lax.sort((hi, lo, iota), dimension=0, num_keys=2,
                                is_stable=True)
    return SortedCloud(pc, s_hi, s_lo, perm)


def downsample_sorted(sc: SortedCloud, factor: int = 2) -> SortedCloud:
    """Output cloud construction reusing the packed keys: quantize in the
    key domain, one single-key sort, adjacent dedup, then compact with a
    cumsum scatter instead of v1's second sorting pass.

    The result is bit-identical to `downsample` (same coords/mask order —
    packed-key order IS lexicographic coordinate order) and arrives already
    sorted, so the next level's SortedCloud costs nothing extra.
    """
    new_stride = sc.pc.stride * factor
    qhi, qlo = PK.quantize_keys(sc.sorted_hi, sc.sorted_lo, new_stride)
    s_hi, s_lo = lax.sort((qhi, qlo), dimension=0, num_keys=2,
                          is_stable=True)
    prev_hi = jnp.roll(s_hi, 1)
    prev_lo = jnp.roll(s_lo, 1)
    is_first = (s_hi != prev_hi) | (s_lo != prev_lo)
    is_first = is_first.at[0].set(True)
    valid = is_first & ~PK.is_sentinel_key(s_hi)

    n = s_hi.shape[0]
    dest = jnp.where(valid, jnp.cumsum(valid.astype(jnp.int32)) - 1, n)
    c_hi = jnp.full(n, PK.KEY_HI_SENTINEL, jnp.int32) \
        .at[dest].set(s_hi, mode="drop")
    c_lo = jnp.full(n, PK.KEY_LO_SENTINEL, jnp.uint32) \
        .at[dest].set(s_lo, mode="drop")
    mask = jnp.zeros(n, bool).at[dest].set(True, mode="drop")
    pc = PointCloud(PK.unpack_keys(c_hi, c_lo), mask, new_stride)
    # compacted keys are already ascending: the sorted view is the identity
    return SortedCloud(pc, c_hi, c_lo, jnp.arange(n, dtype=jnp.int32))


# Backends on which `match_table` lowers to the gather-free merge: a TPU
# gathers element by element, so the binary search's ~log2(n) gather rounds
# dominate the mapping there, while on a CPU they are cheap and the merge
# network's log2(n + m) full passes are not.
MERGE_PLATFORMS = ("tpu",)


def lookup_kind(platform: str) -> str:
    """"merge" or "binary": the lookup `match_table` lowers to on
    `platform` (a JAX backend name such as `jax.default_backend()`)."""
    return "merge" if platform in MERGE_PLATFORMS else "binary"


def match_table(sc: SortedCloud, query: SortedCloud,
                offsets) -> jnp.ndarray:
    """table[k, j] = row of sc.pc at coords (query.pc.coords[j] +
    offsets[k]), or -1 when that site is absent.

    The one key lookup of the v2 engine, behind every map and inverse
    table: pure ranking — no scatter, no hash.  `offsets` is (K, 3)
    static (numpy or jnp); the batch column is never shifted.  The
    backend picks the implementation when the program is lowered
    (`lookup_kind`): on the TPU the bitonic merge of the shifted sorted
    query keys with the sorted table (`PK.lookup_merge`), elsewhere a
    binary search of the shifted query coords (`PK.lookup_binary`).  Both
    give the same table bit for bit on clouds inside the key budget (the
    only clouds the v2 engine accepts).
    """
    def binary(t_hi, t_lo, t_rows, coords, mask, *_):
        q_spatial = coords[:, 1:][None] + jnp.asarray(offsets)[:, None, :]
        q_batch = jnp.broadcast_to(coords[:, :1][None],
                                   (q_spatial.shape[0], coords.shape[0], 1))
        q_hi, q_lo = PK.pack_coords(
            jnp.concatenate([q_batch, q_spatial], -1), mask[None, :])
        return PK.lookup_binary(t_hi, t_lo, t_rows, q_hi, q_lo)

    def merge(t_hi, t_lo, t_rows, _, __, q_hi, q_lo, q_rows):
        return PK.lookup_merge(t_hi, t_lo, t_rows, q_hi, q_lo, q_rows,
                               offsets)

    return lax.platform_dependent(
        sc.sorted_hi, sc.sorted_lo, sc.perm, query.pc.coords,
        query.pc.mask, query.sorted_hi, query.sorted_lo, query.perm,
        default=binary, **{p: merge for p in MERGE_PLATFORMS})


def kernel_map_v2(in_sc: SortedCloud, out_sc: SortedCloud, kernel_size: int,
                  cap: int | None = None) -> KernelMaps:
    """Packed-key kernel mapping: for output q and offset delta, the paired
    input is p = q + delta — found by looking key(q + delta) up in the
    input cloud's sorted keys (`match_table`: a binary search, or on the
    TPU a merge of the already-sorted output keys).  One vectorised
    lookup of all offsets replaces v1's full merge-sort per offset, and
    because the lookup is indexed by output row the hit table IS the
    inverse table the Pallas FoD kernel wants (KernelMaps.inv) — no
    scatter pass.
    """
    offs = kernel_offsets(kernel_size, 3, in_sc.pc.stride)
    m = out_sc.pc.capacity
    n = in_sc.pc.capacity
    cap = cap if cap is not None else min(n, m)

    in_idx = match_table(in_sc, out_sc, offs)
    hit = in_idx >= 0
    out_idx = jnp.where(
        hit, jnp.broadcast_to(jnp.arange(m, dtype=jnp.int32), hit.shape),
        jnp.int32(-1))
    # (K, m): inv[k, j] = i.  Only valid while the maps carry every match —
    # a cap below m may truncate matches, and an inv that still held them
    # would make the pallas flow disagree with gms/fod.
    inv = in_idx if cap >= m else None

    if cap < m:
        # explicit small cap: compact matches to the front (one cheap
        # single-key row sort — only reachable via user-supplied cap)
        order = (~hit).astype(jnp.int32)
        _, in_idx, out_idx, hit = lax.sort((order, in_idx, out_idx, hit),
                                           dimension=1, num_keys=1,
                                           is_stable=True)
    if cap != m:
        in_idx = _fit_cols(in_idx, cap, -1)
        out_idx = _fit_cols(out_idx, cap, -1)
        hit = _fit_cols(hit, cap, False)
    return KernelMaps(in_idx, out_idx, hit, offs, inv=inv)


def _fit_cols(a: jnp.ndarray, cap: int, fill) -> jnp.ndarray:
    if cap <= a.shape[1]:
        return a[:, :cap]
    pad = jnp.full((a.shape[0], cap - a.shape[1]), fill, a.dtype)
    return jnp.concatenate([a, pad], axis=1)


def build_conv_maps_cached(sc: SortedCloud, kernel_size: int, stride: int,
                           cap: int | None = None,
                           out_sc: SortedCloud | None = None):
    """v2 `build_conv_maps` against an existing SortedCloud cache.

    Returns (maps, out_sorted_cloud) so callers building a whole network can
    chain the cache level-to-level (core.tensor.MapContext does).  Pass
    `out_sc` when the downsampled output cloud is already ranked (a context
    cache) to skip recomputing it.

    Strided maps additionally carry the swapped inverse table `inv_t`
    (looking the fine coords up in the coarse cloud), so the decoder's
    transposed convs run the scatter-free Pallas path via `maps.swap()`.
    The table is only exact while `cap` drops no matches — the default cap
    covers every match, a user-supplied smaller one may not.
    """
    if out_sc is None:
        out_sc = sc if stride == 1 else downsample_sorted(sc, stride)
    maps = kernel_map_v2(sc, out_sc, kernel_size, cap=cap)
    resolved_cap = cap if cap is not None else min(sc.pc.capacity,
                                                   out_sc.pc.capacity)
    if stride > 1 and resolved_cap >= out_sc.pc.capacity:
        # swapped orientation: fine output i under swapped offset -delta is
        # fed by the coarse row at (fine_coords[i] - delta)
        inv_t = match_table(out_sc, sc, -maps.offsets)
        maps = maps._replace(inv_t=inv_t)
    return maps, out_sc


# ---------------------------------------------------------------------------
# Stride-aware convenience wrappers used by the SparseConv layer
# ---------------------------------------------------------------------------

def build_conv_maps(in_pc: PointCloud, kernel_size: int, stride: int,
                    cap: int | None = None, engine: str | None = None,
                    cache: SortedCloud | None = None):
    """Maps + output cloud for a (possibly strided) sparse convolution.

    stride == 1  -> submanifold conv: output sites == input sites (the
                    paper's no-dilation invariant: nonzeros never dilate).
    stride == 2  -> output cloud from quantization + unique, offsets in units
                    of the *input* stride.

    engine: "v2" (packed keys, default) or "v1" (per-offset lexicographic
    merge-sort; required for ndim_spatial != 3, kept selectable for
    cross-checking).  `cache` short-circuits the v2 sort with an existing
    SortedCloud of `in_pc`.  The default engine falls back to v1 for
    non-3D clouds; an *explicit* engine="v2" raises there instead (a
    silent downgrade would defeat cross-checking).
    """
    requested = engine
    engine = engine or DEFAULT_ENGINE
    if engine == "v2" and in_pc.ndim_spatial != 3 and requested is None:
        engine = "v1"
    if engine == "v2":
        sc = cache if cache is not None else sort_cloud(in_pc)
        maps, out_sc = build_conv_maps_cached(sc, kernel_size, stride,
                                              cap=cap)
        return maps, out_sc.pc
    if engine != "v1":
        raise ValueError(f"unknown mapping engine {engine!r}")
    if stride == 1:
        out_pc = in_pc
    else:
        out_pc = downsample(in_pc, stride)
    maps = kernel_map(in_pc, out_pc, kernel_size, cap=cap)
    return maps, out_pc
